"""Share of the roofline of the fused keyframe ingest: the least time for
a keyframe's algorithmic work (``bench.mapping.ingest_bytes`` and
``ingest_flops``) at peak, times the keyframes, over the device time of
``jit_ingest_frame`` (%)."""
from bench.costs import roofline_share
from bench.layers import module_seconds
from bench.mapping import INGEST_MODULE, ingest_bytes, ingest_flops, spans


def read(run):
    s = module_seconds(run, INGEST_MODULE)
    kf = spans(run, "mapping.ingest")
    if not s or kf is None or run["peaks"] is None:
        return None
    sh = run["shapes"]
    args = (sh["n_slots"], sh["embed_dim"], sh["max_detections"],
            sh["server_points"], sh["depth_hw"])
    n = len(kf)
    return roofline_share(n * ingest_bytes(*args), n * ingest_flops(*args),
                          s, run["peaks"])
