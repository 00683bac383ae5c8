"""Host time per tick framing packets (``fleet.tick_finish``) and applying
the clients' acks (``fleet.ack_tick``, the driver's span around
``FleetServer.ack_tick``) (ms/tick, profiled seconds)."""
from bench.layers import span_ms_per_tick


def read(run):
    return span_ms_per_tick(run, ("fleet.tick_finish", "fleet.ack_tick"))
