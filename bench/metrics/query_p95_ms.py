"""95th percentile, over every query due in the window, of its due time
to its result on the host (ms)."""
from bench.stats import percentile


def read(run):
    return percentile(run["samples"].get("query", ()), 95)
