"""Device time per keyframe of the fused ingest executable
(``jit_ingest_frame``), keyframes counted by their ``mapping.ingest``
spans (ms/keyframe, profiled seconds)."""
from bench.layers import module_seconds
from bench.mapping import INGEST_MODULE, spans


def read(run):
    s = module_seconds(run, INGEST_MODULE)
    kf = spans(run, "mapping.ingest")
    return None if s is None or kf is None else 1e3 * s / len(kf)
