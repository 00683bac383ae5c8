"""Host time per tick in the loop's ``serving.query`` span: arrivals
submitted, batches assembled and dispatched (ms/tick, profiled seconds)."""
from bench.layers import span_ms_per_tick


def read(run):
    return span_ms_per_tick(run, ("serving.query",))
