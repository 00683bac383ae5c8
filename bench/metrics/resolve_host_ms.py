"""Host time per tick resolving query results to the host
(``serving.resolve``: the device->host reads of each result and the
latency accounting) (ms/tick, profiled seconds)."""
from bench.span_args import host_ms_per_tick


def read(run):
    return host_ms_per_tick(run, "serving.resolve", layer="serving.query")
