"""Mean number of queries still waiting in the scheduler at the end of a
tick's query step (the ``waiting`` arg of ``serving.query``), over the
profiled seconds (requests)."""
from bench.span_args import args_of


def read(run):
    waits = args_of(run, "serving.query", "waiting", layer="serving.query")
    return None if waits is None else sum(waits) / len(waits)
