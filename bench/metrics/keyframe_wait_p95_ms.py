"""95th percentile (nearest rank) of each keyframe's wait, its
``mapping.ingest`` dispatch start minus its due time (the span's
``wait_ms``), over the profiled seconds (ms): a queue of keyframes shows
here, a slow layer in ``detect_host_ms`` and ``ingest_device_ms``."""
from bench.span_args import args_of
from bench.stats import percentile


def read(run):
    waits = args_of(run, "mapping.ingest", "wait_ms", layer="mapping.ingest")
    return None if waits is None else percentile(waits, 95)
