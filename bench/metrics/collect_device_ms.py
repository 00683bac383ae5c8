"""Device time per tick of the session collect executables
(``_collect_fleet_impl``, donated or not) (ms/tick, profiled seconds)."""
from bench.layers import module_seconds, ticks


def read(run):
    n = ticks(run)
    s = module_seconds(run, "_collect_fleet_impl")
    return None if not n or s is None else 1e3 * s / n
