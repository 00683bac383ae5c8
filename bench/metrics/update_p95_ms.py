"""95th percentile, over every (client, object version) pair of the
window, of the row's due time to the framed packet that first gives the
client that version or a newer one (ms)."""
from bench.stats import percentile


def read(run):
    return percentile(run["samples"].get("update", ()), 95)
