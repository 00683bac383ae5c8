"""Bytes and operations the algorithms must move, computed from shapes.

A roofline share is the least time the chip could take for this work
(bytes over peak bandwidth, or operations over peak rate, whichever is
larger) divided by the measured device time.  The counts are of what the
algorithm has to touch, whatever the implementation does, so a faster
implementation raises the share and a wasteful one lowers it.
"""
from __future__ import annotations


def query_sweep_bytes(n_slots: int, embed_dim: int, n_queries: int) -> int:
    """One batched near-predicate query sweep over a flat store: every
    slot's f32 embedding, f32 centroid (the near predicate) and bool
    active flag, plus the batch's embeddings and centres.  The top-k
    writes and id gathers are a few hundred bytes and are left out."""
    per_slot = 4 * embed_dim + 4 * 3 + 1
    per_query = 4 * embed_dim + 4 * 3 + 4
    return n_slots * per_slot + n_queries * per_query


def query_sweep_flops(n_slots: int, embed_dim: int, n_queries: int) -> int:
    """Multiply-adds of the cosine scores plus the distance test."""
    return n_queries * n_slots * (2 * embed_dim + 8)


def roofline_share(bytes_moved: float, flops: float, device_s: float,
                   peaks: dict) -> float | None:
    """Least possible time over measured device time, in percent."""
    if device_s <= 0:
        return None
    least = max(bytes_moved / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / device_s
