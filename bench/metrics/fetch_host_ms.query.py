"""Host time per tick in the blocking device->host reads of query results
(``host.fetch`` with ``what`` "result", inside ``serving.resolve``),
waiting on the device included (ms/tick, profiled seconds)."""
from bench.span_args import host_ms_per_tick


def read(run):
    return host_ms_per_tick(run, "host.fetch", layer="query.batch",
                            what=("result",))
