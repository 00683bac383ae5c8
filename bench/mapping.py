"""Keyframe ingest in a ``--trace 1`` run: its spans within the profiled
seconds, and the bytes and operations one keyframe's fused ingest must
move, computed from shapes (see ``bench.costs`` for the rule)."""
from __future__ import annotations

INGEST_MODULE = "jit_ingest_frame"   # the fused ingest's executable


def spans(run, name: str) -> list | None:
    """(t0_ns, t1_ns) of each span called ``name`` in the profiled
    seconds; None for an untraced run or where none ran."""
    tr = run.get("trace")
    if tr is None:
        return None
    out = [(a, b) for n, a, b, _ in tr["spans"] if n == name]
    return out or None


def ingest_bytes(n_slots: int, embed_dim: int, max_detections: int,
                 server_points: int, depth_hw) -> int:
    """One keyframe: read the downsampled f32 depth and the [D, h, w]
    bool masks; sweep every slot's f32 embedding and centroid and its
    active flag for the [D, cap] scores; read the targeted rows' clouds
    and embeddings, and write the merged rows' clouds and embeddings.
    The per-row scalars are a few hundred bytes and are left out."""
    h, w = depth_hw
    frame = 4 * h * w + max_detections * h * w
    sweep = n_slots * (4 * embed_dim + 4 * 3 + 1)
    rows = 2 * max_detections * (4 * 3 * server_points + 4 * embed_dim)
    return frame + sweep + rows


def ingest_flops(n_slots: int, embed_dim: int, max_detections: int,
                 server_points: int, depth_hw) -> int:
    """Multiply-adds of the [D, cap] scores (cosine and distance) plus the
    back-projection of every pixel of the depth grid."""
    h, w = depth_hw
    return max_detections * n_slots * (2 * embed_dim + 8) + 20 * h * w
