"""Fused frame-ingest geometry kernel: lift -> compact -> downsample -> stats.

The seed server hot path ran, per frame, a vmapped ``geometry.lift_depth``
(an O(HW log HW) ``argsort`` per object to compact valid pixels, plus a
materialized [D, HW, 3] world-point intermediate), then a SEPARATE
``downsample`` dispatch and per-object ``centroid_bbox`` work inside
association.  After PR 1-3 batched everything else, that lift stage was
~54% of B+P+SD mapping latency (BENCH_tab4_fig3_mapping.json).

This module replaces the whole composition with ONE streaming pass over the
depth frame that serves all D detections at once:

  * back-projection is computed per pixel tile ONCE and shared across
    objects (the seed recomputed nothing per object either, but paid the
    [D, HW, 3] gather instead);
  * per-object compaction uses prefix-count destination indexing — the
    r-th valid pixel of object d has rank r by construction, O(HW), no
    sort of any kind;
  * the stride-downsample to the point budget is folded into the same
    indexing (rank r is kept iff some output slot i maps to it under
    ``floor(i * n / budget)`` — at most one i per rank since n >= budget
    makes the map strictly increasing), so ``downsample`` disappears as a
    separate dispatch;
  * centroid / bbox are one reduction over the [D, budget, 3] output, so
    association no longer needs a per-detection ``centroid_bbox`` pass.

Output semantics are bit-for-bit those of the seed composition
``downsample(lift_depth(...), budget)`` + ``centroid_bbox`` (oracle:
``ref.lift_compact_ref``; property tests in tests/test_lift_compact.py),
with ONE deliberate divergence: a detection with zero valid pixels gets the
true ``n = 0`` here, where the seed's ``downsample`` floor (``max(n, 1)``)
reported a phantom single point at the origin.  Same spirit as the
documented ``merge_clouds`` fix — the quirky path counted points that do
not exist; all real clouds are identical.

Two implementations of the same algorithm:

  * ``lift_compact_pallas`` — the TPU deploy kernel.  Grid over (output
    slot block, pixel tile); the lane-dense [D, 8, P] output block is the
    cross-step carry along the tile axis (grids are sequential on TPU).
    Ranks come from a triangular-matmul prefix count, and the per-tile
    scatter is a one-hot MXU matmul ([8, T] x [Pb, T]^T per object, fp32
    contraction so every copied coordinate is exact), which Mosaic handles
    natively where a per-element scatter would not.
  * ``lift_compact_xla`` — the algorithmically identical XLA formulation
    used off-TPU (ops.lift_compact keys off the backend): the one-hot
    matmul trick only pays for itself on the MXU; on CPU/GPU the rank
    composition inverts to a searchsorted gather, back-projecting ONLY the
    <= D*budget selected pixels.  Neither path ever materializes a
    [D, HW, 3] intermediate (asserted by jaxpr inspection in the tests and
    the mapping benchmark).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e9
Z_EPS = 1e-4          # matches geometry.lift_depth's valid-depth floor
BLOCK_P = 512         # output slots per kernel block (lane-dense, 4 x 128)


# ----------------------------------------------------------------------
# XLA formulation (CPU/GPU path + the jit'd production path off-TPU)
# ----------------------------------------------------------------------

def lift_compact_xla(depth: jax.Array, masks: jax.Array,
                     intrinsics: jax.Array, pose: jax.Array, *,
                     stride: int = 1, budget: int, lift_cap: int = 4096):
    """depth: [H, W]; masks: [D, H, W] bool; intrinsics: [fx, fy, cx, cy]
    at FULL resolution; pose: [4, 4] cam->world.

    Returns (points [D, budget, 3], n [D], centroid [D, 3],
    bbox_min [D, 3], bbox_max [D, 3]).

    Gather formulation: one cumsum over [D, HW] gives every pixel's rank,
    a searchsorted inverts rank -> pixel for the <= budget selected ranks,
    and back-projection runs only on those pixels.
    """
    D = masks.shape[0]
    H, W = depth.shape
    HW = H * W
    fx, fy, cx, cy = intrinsics
    z_flat = depth.reshape(HW)
    v = masks.reshape(D, HW) & (z_flat > Z_EPS)[None, :]
    c = jnp.cumsum(v.astype(jnp.int32), axis=1)            # inclusive ranks
    n = jnp.minimum(c[:, -1], lift_cap)                    # [D]
    n_out = jnp.minimum(n, budget).astype(jnp.int32)

    i = jnp.arange(budget)
    r = jnp.where((n > budget)[:, None], (i[None, :] * n[:, None]) // budget,
                  jnp.broadcast_to(i[None, :], (D, budget)))
    # pixel of rank r = first j with c[j] == r + 1 (c is nondecreasing)
    pix = jax.vmap(lambda cd, rd: jnp.searchsorted(cd, rd + 1))(c, r)
    pix = jnp.minimum(pix, HW - 1)                         # padded ranks only

    zb = z_flat[pix]                                       # [D, budget]
    xs_full = ((pix % W).astype(jnp.float32) + 0.5) * stride
    ys_full = ((pix // W).astype(jnp.float32) + 0.5) * stride
    x = (xs_full - cx) / fx * zb
    y = (ys_full - cy) / fy * zb
    pts_cam = jnp.stack([x, y, zb], axis=-1)               # [D, budget, 3]
    pts_w = jnp.matmul(pts_cam, pose[:3, :3].T,
                       precision=jax.lax.Precision.HIGHEST) + pose[:3, 3]

    valid = (i[None, :] < n_out[:, None])[..., None]
    pts = jnp.where(valid, pts_w, 0.0)
    return (pts, n_out) + _cloud_stats(pts, n_out)


def _cloud_stats(pts, n_out):
    """Centroid and bbox of zero-padded [D, budget, 3] clouds holding
    ``n_out`` points each (all zeros for an empty cloud)."""
    valid = (jnp.arange(pts.shape[1])[None, :] < n_out[:, None])[..., None]
    denom = jnp.maximum(n_out, 1).astype(jnp.float32)[:, None]
    cent = jnp.sum(pts, axis=1) / denom
    mn = jnp.min(jnp.where(valid, pts, BIG), axis=1)
    mx = jnp.max(jnp.where(valid, pts, -BIG), axis=1)
    nz = (n_out > 0)[:, None]
    return cent, jnp.where(nz, mn, 0.0), jnp.where(nz, mx, 0.0)


# ----------------------------------------------------------------------
# Pallas streaming kernel (TPU deploy path)
# ----------------------------------------------------------------------

def _ceil_div(a, b):
    """ceil(a / b) for int32 arrays with 0 <= a < 2**24 and b > 0.

    Mosaic has no vector integer division, so the quotient is an f32
    estimate (off by at most one: a and b are exact in f32) corrected one
    step each way with exact integer products.  The same function serves
    the kernel and the tile-span prologue, so both agree bit for bit."""
    q = jnp.ceil(a.astype(jnp.float32) / b.astype(jnp.float32))
    q = q.astype(jnp.int32)
    q = jnp.where(q * b < a, q + 1, q)
    return jnp.where((q - 1) * b >= a, q - 1, q)


def _slot_of(rank, nl, budget: int, lift_cap: int):
    """Output slot of valid-pixel ``rank`` under the fused downsample.

    Inverts downsample's ``idx(i) = floor(i * n / budget)``: below the
    budget the map is the identity; above it the unique candidate slot for
    rank r is ceil(r * budget / n), which is kept iff it maps back to r
    (see ``_kernel``).  ``lift_cap * budget < 2**24`` keeps the products
    exact."""
    rc = jnp.minimum(rank, lift_cap)
    return jnp.where(nl > budget,
                     _ceil_div(rc * budget, jnp.maximum(nl, 1)), rc)


def _tile_spans(cnt, nl, budget: int, lift_cap: int):
    """[D, n_t] valid-pixel counts per tile -> flat int32 [n_t * D * 2]
    (lo, hi) slot bounds of what tile t can write for object d; an empty
    span is (2**30, -1).  Slots are monotone in rank, so the kept slots of
    the tile's ranks [base, base + cnt) lie in [slot(first), slot(last)]:
    the kernel skips every (tile, object, slot block) the span misses."""
    base = jnp.cumsum(cnt, axis=1) - cnt
    last = jnp.minimum(base + cnt, nl) - 1
    lo = _slot_of(base, nl, budget, lift_cap)
    hi = jnp.minimum(_slot_of(last, nl, budget, lift_cap), budget - 1)
    live = (last >= base) & (lo <= hi)
    lo = jnp.where(live, lo, 1 << 30)
    hi = jnp.where(live, hi, -1)
    return jnp.stack([lo, hi], axis=-1).transpose(1, 0, 2).reshape(-1)


def _kernel(span_ref, depth_ref, valid_ref, nl_ref, params_ref, out_ref,
            base_scr, slot_scr, *, W: int, stride: int, block_t: int,
            block_p: int, budget: int, lift_cap: int, n_obj: int):
    pb = pl.program_id(0)              # output slot block
    t = pl.program_id(1)               # pixel tile

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        base_scr[...] = jnp.zeros_like(base_scr)

    # --- shared back-projection: once per tile, for ALL objects
    z = depth_ref[...]                                     # [1, T]
    fx, fy, cx, cy = (params_ref[0], params_ref[1], params_ref[2],
                      params_ref[3])
    j = t * block_t + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)
    row = j // W
    xs_full = ((j - row * W).astype(jnp.float32) + 0.5) * stride
    ys_full = (row.astype(jnp.float32) + 0.5) * stride
    x = (xs_full - cx) / fx * z
    y = (ys_full - cy) / fy * z
    wx = params_ref[4] * x + params_ref[5] * y + params_ref[6] * z + \
        params_ref[13]
    wy = params_ref[7] * x + params_ref[8] * y + params_ref[9] * z + \
        params_ref[14]
    wz = params_ref[10] * x + params_ref[11] * y + params_ref[12] * z + \
        params_ref[15]
    r8 = jax.lax.broadcasted_iota(jnp.int32, (8, block_t), 0)
    w8 = jnp.where(r8 == 0, wx, jnp.where(r8 == 1, wy,
                                          jnp.where(r8 == 2, wz, 0.0)))

    # --- per-object exclusive prefix count: a strictly-upper-triangular
    # [T, T] matmul.  0/1 operands are exact in any MXU pass and the f32
    # accumulator holds counts <= T exactly.
    vi = valid_ref[...]                                    # [D, T] 0/1 f32
    ii = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    upper = jnp.where(ii < jj, 1.0, 0.0)
    excl = jnp.dot(vi, upper, preferred_element_type=jnp.float32)
    base = base_scr[...]                                   # [D, 1]
    rho = base + excl.astype(jnp.int32)
    base_scr[...] = base + jnp.sum(vi, axis=1,
                                   keepdims=True).astype(jnp.int32)

    # --- destination slot per pixel (-1 = not emitted), int32 throughout:
    # rank rho is kept iff it is a valid pixel, inside the lift cap, and
    # its candidate slot maps back to it (slot * n - rho * budget < budget)
    nlb = nl_ref[...] + jnp.zeros_like(rho)                # [D, T]
    slot = _slot_of(rho, nlb, budget, lift_cap)
    miss = jnp.where(nlb > budget,
                     slot * nlb - jnp.minimum(rho, lift_cap) * budget, 0)
    slot_scr[...] = jnp.where(
        vi > 0, jnp.where(rho < nlb, jnp.where(
            miss < budget, jnp.where(slot < budget, slot, -1), -1), -1), -1)

    # --- one-hot MXU scatter per object into this slot block: each kept
    # pixel owns exactly one slot, so with fp32 contraction the
    # accumulated value is the exact point (0 everywhere else)
    p_ids = pb * block_p + jax.lax.broadcasted_iota(
        jnp.int32, (block_p, block_t), 0)

    def scatter(d, carry):
        k = (t * n_obj + d) * 2
        hit = (span_ref[k + 1] >= pb * block_p) & \
            (span_ref[k] < (pb + 1) * block_p)

        @pl.when(hit)
        def _():
            oh = jnp.where(p_ids == slot_scr[pl.ds(d, 1), :], 1.0, 0.0)
            out_ref[d] += jax.lax.dot_general(
                w8, oh, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)        # [8, Pb]
        return carry

    jax.lax.fori_loop(0, n_obj, scatter, 0)


def lift_compact_pallas(depth: jax.Array, masks: jax.Array,
                        intrinsics: jax.Array, pose: jax.Array, *,
                        stride: int = 1, budget: int, lift_cap: int = 4096,
                        block_t: int = 512, interpret: bool | None = None):
    """Streaming-kernel variant of ``lift_compact_xla`` (same contract).

    Grid ``(slot blocks, pixel tiles)``.  The depth + valid-mask stream is
    the only HW-sized traffic (re-read once per slot block); the points
    leave as a lane-dense ``[D, 8, P_pad]`` slab (rows x, y, z, then zero
    padding up to the 8-sublane tile) that the wrapper slices to
    ``[D, budget, 3]``.  Per-tile valid counts, the lift-capped object
    counts and each (tile, object) slot span come from one cheap reduction
    outside the kernel; the spans ride in SMEM (scalar prefetch) so a
    tile skips the objects it holds no kept pixel of.  Centroid and bbox
    are a reduction over the ``[D, budget, 3]`` output, as in the XLA
    formulation.

    VMEM at the knob defaults (D=32, block_t=512, 512-slot blocks): double-
    buffered blocks = valid 2 * 64 KiB + depth 2 * 16 KiB + points
    2 * 512 KiB; scratch = slots 64 KiB + counts 16 KiB; per-step values =
    the [T, T] triangle and its iotas 3 MiB, the [Pb, T] slot ids and
    one-hot 2 MiB, the fp32-contraction splits of the one-hot about 2 MiB.
    About 9 MiB in all, inside v5e's 16 MiB default scoped limit, so no
    ``vmem_limit_bytes`` is set.
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    assert lift_cap * budget < 1 << 24, "slot arithmetic must stay exact"
    D, H, W = masks.shape
    HW = H * W
    z_flat = depth.reshape(1, HW)
    v = (masks.reshape(D, HW) & (z_flat > Z_EPS)).astype(jnp.int32)
    pad = (-HW) % block_t
    if pad:
        z_flat = jnp.pad(z_flat, ((0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, pad)))
    n_t = (HW + pad) // block_t
    cnt = v.reshape(D, n_t, block_t).sum(axis=2)           # [D, n_t]
    nl = jnp.minimum(cnt.sum(axis=1), lift_cap)[:, None]   # [D, 1]
    n_out = jnp.minimum(nl[:, 0], budget)
    spans = _tile_spans(cnt, nl, budget, lift_cap)

    block_p = min(BLOCK_P, -(-budget // 128) * 128)
    p_pad = -(-budget // block_p) * block_p
    params = jnp.concatenate([
        jnp.asarray(intrinsics, jnp.float32).reshape(4),
        jnp.asarray(pose, jnp.float32)[:3, :3].reshape(9),
        jnp.asarray(pose, jnp.float32)[:3, 3].reshape(3),
    ])
    out = pl.pallas_call(
        functools.partial(_kernel, W=W, stride=stride, block_t=block_t,
                          block_p=block_p, budget=budget, lift_cap=lift_cap,
                          n_obj=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p_pad // block_p, n_t),
            in_specs=[
                pl.BlockSpec((1, block_t), lambda p, t, s: (0, t)),
                pl.BlockSpec((D, block_t), lambda p, t, s: (0, t)),
                pl.BlockSpec((D, 1), lambda p, t, s: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),     # intr + pose
            ],
            out_specs=pl.BlockSpec((D, 8, block_p), lambda p, t, s: (0, 0, p)),
            scratch_shapes=[pltpu.VMEM((D, 1), jnp.int32),
                            pltpu.VMEM((D, block_t), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((D, 8, p_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(spans, z_flat, v.astype(jnp.float32), nl, params)
    pts = out[:, :3, :budget].transpose(0, 2, 1)           # [D, budget, 3]
    return (pts, n_out.astype(jnp.int32)) + _cloud_stats(pts, n_out)
