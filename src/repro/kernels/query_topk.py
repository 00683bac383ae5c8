"""Fused query-score + running top-k Pallas kernels.

SemanticXR's query hot-spot (Sec. 2.3.2 / Fig. 5): score text embeddings
against every object embedding and keep the best k — the per-query cost that
grows with map size.  The jnp path materializes the full [N] similarity
vector in HBM, then runs a full top-k pass (second HBM sweep).  These kernels
stream the embedding table through VMEM once: each grid step matmuls an
[Nb, E] block against the query batch (MXU), adds the block's per-slot score
bias, and folds the block's candidates into a [k]-sized running top-k held in
the output refs — one HBM pass, no [N] intermediate.

The ``bias`` input is how the declarative query engine (core/query.py) rides
the same sweep: predicate masks are injected as ``NEG`` bias (an excluded
slot can never enter the running list) and score-combination terms (e.g. the
proximity bonus) as finite bias.  The [Q, N] bias is computed outside the
kernel and streamed through it alongside the [N, E] table — O(Q*N) extra
traffic, small next to the table's O(N*E) — so a predicate-heavy query
stays within a few percent of the embedding-only dispatch and never pays a
gather/compaction pass over the table.

The block fold is a proper top-k merge: k rounds of max-and-mask over the
running list and the block (O(k * Nb) work on the VPU, k <= 16 in
practice), with ``lax.top_k``'s tie order.

The same kernel shape serves BOTH levels of the hierarchical query plan
(repro.index.search): stage 1 streams the [M, E] cluster-summary mean
table with the conservative gate slack as bias (top-m cells by score
upper bound), stage 2 streams the gathered member slab — so a two-stage
query is two instances of this sweep at a fraction of the flat row count.

Variants:
  * ``query_topk_bias_pallas``   — [Q, E] queries + [Q, N] bias (the engine
    entry point; the query batch is resident in VMEM, the table and bias
    stream through HBM once for all Q queries).
  * ``query_topk_multi_pallas``  — active-mask compatibility wrapper
    (bias = 0/NEG from the mask).
  * ``query_topk_pallas``        — the Q=1 special case.

Grids are sequential on TPU, so outputs act as cross-step carries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _merge_topk(run_v, run_i, sim, base, k: int):
    """Fold one block's scores into the running (vals, idx) top-k lists.

    run_v/run_i: [Q, k] running top-k; sim: [Q, Nb] block scores.
    Exactly ``lax.top_k`` of the concatenation [running, block] — ties go
    to the earlier position, so to the lower global index — as k rounds of
    max-and-mask (Mosaic lowers no sort or top_k).  Each round takes the
    row max, picks its first position (the running list precedes the
    block), and retires that position with -inf, which no real candidate
    holds (masked slots score NEG).
    """
    Q, nb = sim.shape
    big = jnp.int32(2 ** 30)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1)
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (Q, nb), 1)
    cv, bv = run_v, sim
    out_v = jnp.full((Q, k), NEG, jnp.float32)
    out_i = jnp.full((Q, k), -1, jnp.int32)
    for r in range(k):
        m = jnp.maximum(jnp.max(cv, axis=1, keepdims=True),
                        jnp.max(bv, axis=1, keepdims=True))        # [Q, 1]
        pc = jnp.min(jnp.where(cv == m, lane_k, big), axis=1, keepdims=True)
        pb = jnp.min(jnp.where(bv == m, lane_b, big), axis=1, keepdims=True)
        in_run = pc < big
        ic = jnp.max(jnp.where(lane_k == pc, run_i, -1), axis=1,
                     keepdims=True)
        idx = jnp.where(in_run, ic, base + pb)
        out_v = jnp.where(lane_k == r, m, out_v)
        out_i = jnp.where(lane_k == r, idx, out_i)
        cv = jnp.where(lane_k == pc, -jnp.inf, cv)
        bv = jnp.where((lane_b == pb) & ~in_run, -jnp.inf, bv)
    return out_v, out_i


def query_topk_pallas(q: jax.Array, embeds: jax.Array, active: jax.Array,
                      k: int, *, block_n: int = 1024,
                      interpret: bool | None = None):
    """q: [E]; embeds: [N, E]; active: [N] -> (scores [k], idx [k]).

    The Q=1 special case of the multi-query kernel below."""
    vals, idx = query_topk_multi_pallas(q[None, :], embeds, active, k,
                                        block_n=block_n, interpret=interpret)
    return vals[0], idx[0]


def _bias_kernel(q_ref, e_ref, b_ref, vals_ref, idx_ref, *, k: int,
                 block_n: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    # [Q, E] x [Nb, E]^T -> [Q, Nb] on the MXU — one matmul serves all
    # queries; fp32 contraction, like the jnp engine path
    sim = jax.lax.dot_general(q_ref[...], e_ref[...],
                              (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [Q, Nb]
    b = b_ref[...]                                             # [Q, Nb]
    # bias == NEG marks a predicate-excluded slot; finite bias is additive
    sim = jnp.where(b > NEG * 0.5, sim + b, NEG)
    base = step * block_n
    mv, mi = _merge_topk(vals_ref[...], idx_ref[...], sim, base, k)
    vals_ref[...] = mv
    idx_ref[...] = mi


def query_topk_bias_pallas(qs: jax.Array, embeds: jax.Array,
                           bias: jax.Array, k: int, *,
                           block_n: int = 1024,
                           interpret: bool | None = None):
    """qs: [Q, E]; embeds: [N, E]; bias: [Q, N] -> ([Q, k], [Q, k]).

    score[q, n] = qs[q] . embeds[n] + bias[q, n], with bias == NEG masking
    slot n out for query q entirely.  The query batch stays resident in
    VMEM; the embedding table and bias stream through once for ALL Q
    queries (vs Q independent sweeps when vmapping a single-query kernel).
    ``interpret=None`` keys off the backend via ``ops._interpret()``.
    """
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    Q, E = qs.shape
    N = embeds.shape[0]
    pad = (-N) % block_n
    if pad:
        embeds = jnp.pad(embeds, ((0, pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=NEG)
    Np = N + pad
    grid = (Np // block_n,)
    vals, idx = pl.pallas_call(
        functools.partial(_bias_kernel, k=k, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Q, E), lambda i: (0, 0)),            # queries resident
            pl.BlockSpec((block_n, E), lambda i: (i, 0)),      # stream blocks
            pl.BlockSpec((Q, block_n), lambda i: (0, i)),      # stream bias
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(qs, embeds, bias)
    return vals, idx


def query_topk_multi_pallas(qs: jax.Array, embeds: jax.Array,
                            active: jax.Array, k: int, *,
                            block_n: int = 1024,
                            interpret: bool | None = None):
    """qs: [Q, E]; embeds: [N, E]; active: [N] -> ([Q, k], [Q, k]).

    Active-mask compatibility wrapper over the bias kernel: an inactive
    slot is a NEG bias, an active one a 0 bias (identical scores to the
    seed mask kernel)."""
    Q = qs.shape[0]
    N = embeds.shape[0]
    bias = jnp.broadcast_to(
        jnp.where(active, 0.0, NEG).astype(jnp.float32)[None, :], (Q, N))
    return query_topk_bias_pallas(qs, embeds, bias, k, block_n=block_n,
                                  interpret=interpret)
