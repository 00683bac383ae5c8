"""Share of the profiled seconds in which no operation ran on the device
(%), in the serving cells."""
from bench.layers import idle_pct


def read(run):
    return idle_pct(run)
