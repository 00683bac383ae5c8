"""Host time per keyframe in ``mapping.detect``: the detector stand-in
over the full-resolution instance map and the padding of the fused
ingest's inputs (ms/keyframe, profiled seconds)."""
from bench.mapping import spans


def read(run):
    s = spans(run, "mapping.detect")
    return None if s is None else sum(b - a for a, b in s) / 1e6 / len(s)
