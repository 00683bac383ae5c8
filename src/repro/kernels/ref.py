"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Score matmuls contract in full f32 (``HIGHEST``) so an oracle evaluated on
the TPU, whose default f32 matmul is a single bf16 pass, stays an f32
reference for the kernels (which contract in f32 too)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def query_topk_ref(q: jax.Array, embeds: jax.Array, active: jax.Array,
                   k: int):
    """q: [E]; embeds: [N, E]; active: [N] bool -> (scores [k], idx [k])."""
    sim = jnp.matmul(embeds, q, precision=HIGHEST)
    sim = jnp.where(active, sim, -jnp.inf)
    return jax.lax.top_k(sim, k)


def query_topk_multi_ref(qs: jax.Array, embeds: jax.Array, active: jax.Array,
                         k: int):
    """qs: [Q, E]; embeds: [N, E]; active: [N] -> ([Q, k], [Q, k])."""
    return jax.vmap(lambda q: query_topk_ref(q, embeds, active, k))(qs)


def query_topk_bias_ref(qs: jax.Array, embeds: jax.Array, bias: jax.Array,
                        k: int, *, neg: float = -1e30):
    """qs: [Q, E]; embeds: [N, E]; bias: [Q, N] -> ([Q, k], [Q, k]).
    bias == neg masks the slot out; finite bias is additive."""
    sim = jnp.matmul(qs, embeds.T, precision=HIGHEST)
    sim = jnp.where(bias > neg * 0.5, sim + bias, -jnp.inf)
    return jax.lax.top_k(sim, k)


def lift_compact_ref(depth: jax.Array, masks: jax.Array,
                     intrinsics: jax.Array, pose: jax.Array, *,
                     stride: int = 1, budget: int, lift_cap: int = 4096):
    """Seed-composition oracle for kernels/lift_compact.py: per object,
    ``lift_depth`` (argsort compaction) -> ``downsample`` -> ``centroid_bbox``
    exactly as the pre-fusion pipeline ran them.  Returns
    (points [D, budget, 3], n [D], centroid [D, 3], bbox_min, bbox_max)."""
    from repro.core import geometry as geo

    def one(mask):
        pts, n, _ = geo.lift_depth(depth, mask, intrinsics, pose,
                                   stride=stride, max_points=lift_cap)
        pts, n = geo.downsample(pts, n, budget)
        c, mn, mx = geo.centroid_bbox(pts, n)
        return pts, n, c, mn, mx

    return jax.vmap(one)(masks)


def nearest_dist_ref(a: jax.Array, b: jax.Array, b_valid: jax.Array):
    """a: [M, D]; b: [N, D]; b_valid: [N] -> min squared distance per a row.
    (the association/chamfer spatial primitive)"""
    d2 = jnp.sum(jnp.square(a[:, None, :] - b[None, :, :]), axis=-1)
    d2 = jnp.where(b_valid[None, :], d2, jnp.inf)
    return jnp.min(d2, axis=1)

