"""Host time per tick in the blocking device->host reads of the update
path (``host.fetch`` with ``what`` "store": the zone mirror's store reads;
"counts" and "rows": a collect's results), waiting on the device included
(ms/tick, profiled seconds).  The "owed" read exists only while tracing
and is left out."""
from bench.span_args import host_ms_per_tick


def read(run):
    return host_ms_per_tick(run, "host.fetch", layer="zones.refresh",
                            what=("store", "counts", "rows"))
