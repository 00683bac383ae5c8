"""Host time per tick in the loop's ``serving.sync`` span: poses, the zone
mirror's refresh and the collects' issue (ms/tick, profiled seconds)."""
from bench.layers import span_ms_per_tick


def read(run):
    return span_ms_per_tick(run, ("serving.sync",))
