"""Rows a covered client is still owed after a zone collect's budget cut:
the ``rows_owed`` of every ``session.collect_finish`` span over its
``clients``, summed over the profiled seconds (rows)."""
from bench.span_args import args_of


def read(run):
    layer = "session.collect_finish"
    owed = args_of(run, layer, "rows_owed", layer=layer)
    if owed is None:
        return None
    clients = sum(args_of(run, layer, "clients", layer=layer))
    return sum(owed) / clients if clients else None
