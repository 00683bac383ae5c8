"""Object-level geometry: depth lifting, downsampling, centroids/bboxes.

TPU adaptation of the paper's geometry path: per-object point clouds live in
fixed-capacity masked buffers (capacity == the paper's max_object_points
knob), so downsampling is a deterministic gather instead of the CPU-side
random subsample — same quality role (Sec. 3.1), but shape-stable for
jit/vmap over the object batch.

The production ingest path no longer composes ``lift_depth`` ->
``downsample`` -> ``centroid_bbox`` per frame: kernels/lift_compact fuses
all three into one streaming pass with prefix-count destination indexing
(no per-object argsort, no [D, HW, 3] intermediate).  The functions here
remain the semantic ground truth (the fused path's oracle,
``ref.lift_compact_ref``, is built from them), the B / B+P Fig. 3 ablation
arms, and the merge/update primitives used outside frame ingest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lift_depth(depth: jax.Array, mask: jax.Array, intrinsics: jax.Array,
               pose: jax.Array, *, stride: int = 1, max_points: int = 2048):
    """Back-project masked depth pixels to world points.

    depth: [H, W] metres; mask: [H, W] bool (one object's instance mask);
    intrinsics: [fx, fy, cx, cy] at FULL resolution; pose: [4,4] cam->world.
    ``stride``: depth was downsampled by this factor per dim (Sec. 3.3) —
    pixel coordinates are scaled back to full-res units before projection.
    Returns (points [max_points,3], n [], valid mask [max_points]).
    """
    H, W = depth.shape
    fx, fy, cx, cy = intrinsics
    ys, xs = jnp.meshgrid(jnp.arange(H), jnp.arange(W), indexing="ij")
    xs_full = (xs.astype(jnp.float32) + 0.5) * stride
    ys_full = (ys.astype(jnp.float32) + 0.5) * stride
    z = depth
    valid = mask & (z > 1e-4)
    x = (xs_full - cx) / fx * z
    y = (ys_full - cy) / fy * z
    pts_cam = jnp.stack([x, y, z], axis=-1).reshape(-1, 3)
    valid = valid.reshape(-1)
    # world = R @ p + t, in full f32 (TPU's default matmul pass is bf16)
    pts_w = jnp.matmul(pts_cam, pose[:3, :3].T,
                       precision=jax.lax.Precision.HIGHEST) + pose[:3, 3]
    # deterministic top-max_points selection of valid pixels
    order = jnp.argsort(~valid)                     # valid first, stable
    take = order[:max_points]
    pts = pts_w[take]
    ok = valid[take]
    n = jnp.minimum(valid.sum(), max_points)
    return jnp.where(ok[:, None], pts, 0.0), n.astype(jnp.int32), ok


def downsample(points: jax.Array, n: jax.Array, budget: int):
    """Cap a masked point cloud at ``budget`` points (Sec. 3.1).

    Deterministic stride gather over the valid prefix: index i of the output
    reads floor(i * n / budget) — uniform coverage, shape-stable.
    Returns (points [budget,3], n_out []).
    """
    P = points.shape[0]
    n = jnp.maximum(n, 1)
    ar = jnp.arange(budget)
    # stride-gather only when over budget; identity below budget (a
    # compressive gather at n < budget would duplicate-and-drop points)
    idx = jnp.where(n > budget, (ar * n) // budget, ar)
    idx = jnp.minimum(idx, P - 1)
    out = points[idx]
    n_out = jnp.minimum(n, budget)
    valid = jnp.arange(budget) < n_out
    return jnp.where(valid[:, None], out, 0.0), n_out.astype(jnp.int32)


def downsample_dyn(points: jax.Array, n: jax.Array, budget: jax.Array,
                   out_cap: int):
    """``downsample`` with a *traced* per-call budget (<= static out_cap).

    The budget only shapes the valid prefix, not the output buffer, so it
    can vary per row without retracing — updates._gather_batch uses this to
    honor per-class client point budgets (Knobs.class_point_overrides) in
    one gather over a mixed-class packet.  For budget == out_cap this is
    exactly ``downsample(points, n, out_cap)``.
    Returns (points [out_cap, 3], n_out []).
    """
    P = points.shape[0]
    n = jnp.maximum(n, 1)
    b = jnp.maximum(jnp.minimum(budget, out_cap), 1)
    ar = jnp.arange(out_cap)
    idx = jnp.where(n > b, (ar * n) // b, ar)
    idx = jnp.minimum(idx, P - 1)
    out = points[idx]
    n_out = jnp.minimum(n, b)
    valid = ar < n_out
    return jnp.where(valid[:, None], out, 0.0), n_out.astype(jnp.int32)


def centroid_bbox(points: jax.Array, n: jax.Array):
    """(centroid [3], bbox_min [3], bbox_max [3]) of a masked cloud."""
    P = points.shape[0]
    valid = (jnp.arange(P) < n)[:, None]
    denom = jnp.maximum(n, 1).astype(jnp.float32)
    c = jnp.sum(jnp.where(valid, points, 0.0), axis=0) / denom
    big = 1e9
    mn = jnp.min(jnp.where(valid, points, big), axis=0)
    mx = jnp.max(jnp.where(valid, points, -big), axis=0)
    mn = jnp.where(n > 0, mn, 0.0)
    mx = jnp.where(n > 0, mx, 0.0)
    return c, mn, mx


def merge_clouds(pts_a, n_a, pts_b, n_b, budget: int):
    """Merge two masked clouds and re-cap at budget (association merge).

    Validity is positional (``arange < n``), so "compact valid-a then
    valid-b" is just the concatenation of the two prefixes — the merged
    cloud's row i is ``a[i]`` for i < n_a else ``b[i - n_a]``.  Composing
    that with the downsample stride gather gives the whole merge as TWO
    gathers and a select: no [Pa+Pb] intermediate, no argsort compaction
    (the seed hot-spot, kept as ``merge_clouds_argsort`` below as the
    benchmark baseline).  Outputs are identical to the seed path whenever
    ``n_a <= budget`` — which the mapping pipeline guarantees by passing
    ``budget == max_object_points_server`` (the store row size bounding
    n_a).  Beyond that regime the seed path counted phantom valid points
    (its n included the part of cloud a past the budget crop) and read
    rows past the valid prefix, which this version does not reproduce.
    """
    Pa = min(budget, pts_a.shape[0])
    Pb = pts_b.shape[0]
    n_a = jnp.minimum(n_a, Pa)
    n = jnp.minimum(n_a + jnp.minimum(n_b, Pb), Pa + Pb).astype(jnp.int32)
    nn = jnp.maximum(n, 1)                      # downsample's empty-cloud quirk
    ar = jnp.arange(budget)
    idx = jnp.where(nn > budget, (ar * nn) // budget, ar)
    from_a = idx < n_a
    out = jnp.where(from_a[:, None],
                    pts_a[jnp.minimum(idx, Pa - 1)],
                    pts_b[jnp.clip(idx - n_a, 0, Pb - 1)])
    n_out = jnp.minimum(nn, budget)
    valid = ar < n_out
    return jnp.where(valid[:, None], out, 0.0), n_out.astype(jnp.int32)


def merge_clouds_argsort(pts_a, n_a, pts_b, n_b, budget: int):
    """Seed implementation of merge_clouds (argsort compaction) — the
    baseline for the association microbenchmark and equivalence tests."""
    both = jnp.concatenate([pts_a[:budget], pts_b], axis=0)
    # compact: valid-a first, then valid-b
    Pa = pts_a[:budget].shape[0]
    va = jnp.arange(Pa) < n_a
    vb = jnp.arange(pts_b.shape[0]) < n_b
    valid = jnp.concatenate([va, vb])
    order = jnp.argsort(~valid)
    both = both[order]
    n = (n_a + n_b).astype(jnp.int32)
    return downsample(both, jnp.minimum(n, both.shape[0]), budget)


def bbox_pixel_area(mask: jax.Array, stride: int = 1) -> jax.Array:
    """Projected bbox area of an instance mask, in FULL-res pixel units
    (min_mapping_bbox_area gate, Sec. 3.3)."""
    H, W = mask.shape
    ys = jnp.any(mask, axis=1)
    xs = jnp.any(mask, axis=0)
    def extent(v):
        idx = jnp.arange(v.shape[0])
        mn = jnp.min(jnp.where(v, idx, v.shape[0]))
        mx = jnp.max(jnp.where(v, idx, -1))
        return jnp.maximum(mx - mn + 1, 0)
    return extent(ys) * extent(xs) * (stride * stride)
