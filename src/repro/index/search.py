"""Two-stage (coarse-to-fine) execution of ``Query`` specs over a
ClusterIndex, plus the first-class cluster-level query mode.

Object-level plan (``two_stage_query``), provably equal to the flat sweep:

1. **Stage 1** scores every cluster summary with a *conservative upper
   bound* on the best score any member could achieve, and with predicate
   masks that can only over-include (a cell passes if ANY member could
   pass).  With ``use_pallas`` the ranking runs through the same
   ``query_topk_bias`` kernel as the flat sweep — queries x
   ``summaries.embed_mean`` with the slack/mask bias streamed alongside —
   so the coarse stage is literally the fine stage at 1/cell_cap the rows.
2. **Stage 2** gathers the surviving cells' member slots (ascending slot
   order, so tie-breaking matches the flat sweep) into a fixed candidate
   slab and reuses ``core.query._execute`` — the identical fused
   predicate+score+top-k dispatch, over ~1-10% of the table.
3. **Certificate**: the k-th result score is compared against the max
   upper bound over every *unselected* cluster.  If any unselected cluster
   could still beat rank k, the selection width doubles (escalation) until
   the certificate passes or every cluster is selected — at which point
   the result is the flat sweep's by construction.  Equal-score ties
   *across* the certificate boundary may resolve to a different member
   than the flat sweep (same score, documented); ties among candidates
   resolve identically (ascending slot order).

Upper-bound derivations (all exact-math bounds; the certificate adds a
small epsilon for f32 evaluation-order noise):

* semantic: ``s = w q . e_j = w q . mean + w q . (e_j - mean)
  <= w q . mean + ||w q|| * res_max``                (Cauchy-Schwarz —
  holds for either sign of ``sem_weight``).
* proximity: ``pw / (1 + d)`` with ``d`` in [dmin, dmax] to the member
  AABB — ``pw >= 0`` maximizes at dmin, ``pw < 0`` at dmax.
* predicates: labels via per-cell class presence; near/aabb via member-
  AABB geometry; min_points/min_obs/since via per-cell maxima; zones via
  member-AABB x allowed-zone-rectangle intersection (border zones extend
  to infinity, mirroring ``ZoneGrid.zone_of``'s clamp).

Cluster-level mode (``Query(level="cluster")``): the summaries ARE the
results — score = semantic (query x mean embedding) + proximity (to the
cluster centroid) + ``density_weight * log1p(count)``, top-k cells
returned as a ``ClusterResult`` ("where is the densest region matching
this text").
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.query import (NEG, QueryResult, _Cols, _columns, _execute,
                              _promote)
from repro.core.updates import bucket as _bucket
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span as obs_span

_C0 = 64              # initial stage-1 selection width (cells per query) —
                      # must exceed the typical gate-surviving cell count
                      # (~30-50 on hotspot scenes) or the selection is
                      # clipped, the certificate can't pass, and every
                      # query pays one escalation round
_CERT_EPS = 1e-5      # f32 slack on the exactness certificate
_KERNEL_MAX_K = 1024  # query_topk_bias top-k must fit one block


def candidate_fraction_buckets() -> tuple:
    """Fixed log-spaced fraction buckets (1e-4 .. 1.0) for the
    candidate-fraction histogram — stable across runs like
    ``default_latency_buckets``."""
    return tuple(round(10.0 ** (e / 4.0), 8) for e in range(-16, 1))


# ---------------------------------------------------------------------------
# conservative cluster gating (shared by stage 1 and the cluster-level mode)
# ---------------------------------------------------------------------------
def _zone_rects(zones: tuple, grid: tuple):
    """Static allowed-zone rectangles [Z, 2] lo/hi per axis, border zones
    extended to infinity (mirrors ``ZoneGrid.overlaps``)."""
    x0, z0, zs, nx, nz = grid
    inf = float("inf")
    xlo, xhi, zlo, zhi = [], [], [], []
    for z in zones:
        ix, iz = divmod(int(z), int(nz))
        xlo.append(-inf if ix == 0 else x0 + ix * zs)
        xhi.append(inf if ix == nx - 1 else x0 + (ix + 1) * zs)
        zlo.append(-inf if iz == 0 else z0 + iz * zs)
        zhi.append(inf if iz == nz - 1 else z0 + (iz + 1) * zs)
    mk = lambda v: jnp.asarray(np.asarray(v, np.float32))
    return mk(xlo), mk(xhi), mk(zlo), mk(zhi)


def _cluster_gate(spec, summ, *, has_obs: bool, has_seen: bool):
    """Conservative per-cell predicate mask [Q, M] + the finite upper-bound
    slack [Q, M] (res_max semantic slack + proximity bound) for stage 1.

    Over-inclusion is safe (stage 2 re-checks members exactly); exclusion
    is only allowed when NO member can pass — each test uses the cell's
    member AABB / class presence / attribute maxima."""
    M = summ.count.shape[0]
    ok = jnp.broadcast_to((summ.count > 0)[None, :], (1, M))
    if spec.labels is not None:
        lab = jnp.asarray(spec.labels, jnp.int32)
        ok = ok & summ.label_any[:, lab].any(axis=1)[None, :]
    if spec.min_points is not None:
        ok = ok & (summ.n_points_max[None, :] >= spec.min_points[:, None])
    if spec.min_obs is not None and has_obs:
        ok = ok & (summ.obs_max[None, :] >= spec.min_obs[:, None])
    if spec.since is not None and has_seen:
        ok = ok & (summ.last_seen_max[None, :] >= spec.since[:, None])
    if spec.aabb is not None:
        lo, hi = spec.aabb
        inter = ((summ.aabb_min[None] <= hi[:, None, :])
                 & (summ.aabb_max[None] >= lo[:, None, :])).all(-1)
        ok = ok & inter
    if spec.zones is not None:
        xlo, xhi, zlo, zhi = _zone_rects(spec.zones, spec.grid)
        hit = ((summ.aabb_min[:, None, 0] <= xhi[None])
               & (summ.aabb_max[:, None, 0] >= xlo[None])
               & (summ.aabb_min[:, None, 2] <= zhi[None])
               & (summ.aabb_max[:, None, 2] >= zlo[None])).any(axis=1)
        ok = ok & hit[None, :]

    leaves = jax.tree.leaves(spec)
    Q = int(leaves[0].shape[0]) if leaves else 1
    slack = jnp.zeros((Q, M), jnp.float32)
    if spec.embed is not None:
        qs = spec.embed
        if spec.sem_weight is not None:
            qs = qs * spec.sem_weight[:, None]
        qn = jnp.linalg.norm(qs, axis=-1)                  # [Q]
        slack = slack + qn[:, None] * summ.res_max[None, :]
    if spec.near is not None:
        center, radius = spec.near
        c = center[:, None, :]                             # [Q, 1, 3]
        # min / max distance from the query center to the member AABB
        dmin = jnp.linalg.norm(
            jnp.maximum(jnp.maximum(summ.aabb_min[None] - c,
                                    c - summ.aabb_max[None]), 0.0), axis=-1)
        ok = ok & (dmin <= radius[:, None])
        if spec.prox_weight is not None:
            dmax = jnp.linalg.norm(
                jnp.maximum(jnp.abs(c - summ.aabb_min[None]),
                            jnp.abs(c - summ.aabb_max[None])), axis=-1)
            pw = spec.prox_weight[:, None]
            slack = slack + jnp.where(pw >= 0, pw / (1.0 + dmin),
                                      pw / (1.0 + dmax))
    ok = jnp.broadcast_to(ok, (Q, M))
    # empty cells carry inf/-inf AABBs: their dmin/dmax are inf (0*inf-safe
    # here since slack multiplies finite terms), and count>0 masks them —
    # scrub any NaN the inf arithmetic produced so NEG masking wins
    slack = jnp.nan_to_num(slack, nan=0.0, posinf=0.0, neginf=0.0)
    return ok, slack


# ---------------------------------------------------------------------------
# stage 1: rank clusters by upper bound, select a width-m union
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "use_pallas", "has_obs",
                                             "has_seen"))
def _stage1(spec, summ, *, m: int, use_pallas: bool, has_obs: bool,
            has_seen: bool):
    """Returns (cells [Q*m] int32 — the deduped union of each query's top-m
    cells by upper bound, ascending, -1 padded — and excl_max [Q]: each
    query's max upper bound over every UNSELECTED cluster, the certificate
    threshold)."""
    spec = _promote(spec)
    M = summ.count.shape[0]
    ok, slack = _cluster_gate(spec, summ, has_obs=has_obs, has_seen=has_seen)
    bias = jnp.where(ok, slack, NEG)
    if spec.embed is not None:
        qs = spec.embed
        if spec.sem_weight is not None:
            qs = qs * spec.sem_weight[:, None]
        # fp32 contraction: a bf16-pass bound could undercut a member's
        # exact score and void the certificate
        sim = jnp.matmul(qs, summ.embed_mean.T,
                         precision=jax.lax.Precision.HIGHEST)   # [Q, M]
        ub = jnp.where(bias > NEG * 0.5, sim + bias, NEG)
        if use_pallas and m <= _KERNEL_MAX_K:
            from repro.kernels import ops as kops
            vals, picks = kops.query_topk_bias(qs, summ.embed_mean, bias, m)
        else:
            vals, picks = jax.lax.top_k(ub, m)
    else:
        ub = jnp.where(bias > NEG * 0.5, bias, NEG)
        vals, picks = jax.lax.top_k(ub, m)

    # union the per-query selections: sort, mark duplicates/invalid as -1
    flat = jnp.where(vals > NEG * 0.5, picks, M).reshape(-1)   # [Q*m]
    srt = jnp.sort(flat)
    dup = jnp.concatenate([jnp.zeros((1,), bool), srt[1:] == srt[:-1]])
    cells = jnp.where(dup | (srt >= M), -1, srt).astype(jnp.int32)

    sel = jnp.zeros((M + 1,), bool) \
        .at[jnp.where(cells >= 0, cells, M)].set(True)[:M]
    ub_f = jnp.where(ub > NEG * 0.5, ub, -jnp.inf)
    excl_max = jnp.where(sel[None, :], -jnp.inf, ub_f).max(axis=1)   # [Q]
    return cells, excl_max


# ---------------------------------------------------------------------------
# stage 2: the existing fused sweep over the surviving members only
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _stage2(spec, cols: _Cols, slot_map, *, use_pallas: bool):
    """Sweep an ascending, ``cap``-padded candidate slot slab through the
    SAME ``_execute`` dispatch the flat path uses, then map result slots
    back to target rows.  The slab is assembled host-side from the exact
    per-cell member lists, so its (bucketed) length tracks the TRUE
    candidate count — a fixed cells x cell_cap gather would pad 4-8x past
    reality on occupancy-skewed scenes and the slab sweep is the dominant
    cost of a two-stage query."""
    cap = cols.active.shape[0]
    valid = slot_map < cap
    idx = jnp.where(valid, slot_map, 0)
    cand = _Cols(
        ids=jnp.where(valid, cols.ids[idx], 0),
        active=jnp.where(valid, cols.active[idx], False),
        embed=cols.embed[idx],
        label=cols.label[idx],
        n_points=cols.n_points[idx],
        centroid=cols.centroid[idx],
        obs_count=None if cols.obs_count is None else cols.obs_count[idx],
        last_seen=None if cols.last_seen is None else cols.last_seen[idx])
    res = _execute(spec, cand, use_pallas=use_pallas)
    slots = jnp.where(res.slots >= 0,
                      slot_map[jnp.maximum(res.slots, 0)].astype(jnp.int32),
                      -1)
    return QueryResult(oids=res.oids, scores=res.scores, slots=slots)


# ---------------------------------------------------------------------------
def two_stage_query(spec, target, index, *,
                    use_pallas: bool = False) -> QueryResult:
    """Execute an object-level ``Query`` through the cluster index with the
    exactness certificate + escalation loop (module docstring)."""
    cols = _columns(target)
    has_obs = cols.obs_count is not None
    has_seen = cols.last_seen is not None
    M = index.grid.n_cells
    k = max(int(spec.k), 1)
    m = min(_C0, M)
    escalations = 0
    while True:
        with obs_span("query.index.stage1", cat="query", m=m):
            cells, excl = _stage1(spec, index.summaries, m=m,
                                  use_pallas=use_pallas, has_obs=has_obs,
                                  has_seen=has_seen)
        # assemble the candidate slab host-side from the surviving cells'
        # exact member lists (the index's host bookkeeping): the slab
        # length is the bucketed TRUE candidate count, ascending so the
        # flat sweep's slot-order tie-break is preserved bit-for-bit
        cells_np = np.asarray(cells)
        live = cells_np[cells_np >= 0]
        n_cand = int(index._size[live].sum()) if live.size else 0
        cap_t = int(cols.active.shape[0])
        P = min(_bucket(max(n_cand, 1)), _bucket(cap_t))
        slab = np.full((P,), cap_t, np.int64)
        if n_cand:
            slab[:n_cand] = np.sort(np.concatenate(
                [index._members[c][:int(index._size[c])] for c in live]))
        with obs_span("query.index.stage2", cat="query", cells=live.size,
                      slab=P) as sp:
            res = _stage2(spec, cols, jnp.asarray(slab),
                          use_pallas=use_pallas)
            sp.fence(res.scores)
        sk = np.atleast_1d(
            np.asarray(res.scores)[..., min(k, res.scores.shape[-1]) - 1])
        ex = np.asarray(excl)
        exf = np.where(np.isneginf(ex), 0.0, ex)   # keep -inf out of the
        certified = np.isneginf(ex) \
            | (sk >= exf + _CERT_EPS * np.maximum(1.0, np.abs(exf)))
        if certified.all() or m >= M:
            break
        m = min(2 * m, M)
        escalations += 1

    reg = obs_metrics.get_registry()
    if reg is not None:
        reg.counter("query_index_two_stage_total",
                    "queries served by the cluster index").inc()
        if escalations:
            reg.counter("query_index_escalations_total",
                        "certificate-failure selection doublings").inc(
                            escalations)
        frac = n_cand / max(int(cols.active.shape[0]), 1)
        reg.histogram("query_index_candidate_fraction",
                      "stage-2 candidates / table size",
                      bounds=candidate_fraction_buckets()).observe(frac)
    return res


# ---------------------------------------------------------------------------
# cluster-level queries: the summaries ARE the results
# ---------------------------------------------------------------------------
class ClusterResult(NamedTuple):
    """Top-k *clusters* (``Query(level="cluster")``).  Padded ranks: score
    -inf, cell/zone -1, count 0."""
    zones: jax.Array      # [k] / [Q, k] int32 zone id (-1 on flat targets)
    cells: jax.Array      # [k] / [Q, k] int32 grid cell id (-1 = no match)
    scores: jax.Array     # [k] / [Q, k] f32
    counts: jax.Array     # [k] / [Q, k] int32 member count
    centroids: jax.Array  # [k, 3] / [Q, k, 3] f32 cluster centroid


@functools.partial(jax.jit, static_argnames=("has_obs", "has_seen"))
def _cluster_execute(spec, summ, *, has_obs: bool, has_seen: bool):
    """Score cells directly: semantic (query x mean embedding) + proximity
    (to the cluster centroid) + density_weight * log1p(count), under the
    same conservative predicate gate, one top-k over [Q, M]."""
    squeeze = not spec.batched
    spec = _promote(spec)
    M = summ.count.shape[0]
    k = min(spec.k, M)
    ok, _ = _cluster_gate(spec, summ, has_obs=has_obs, has_seen=has_seen)
    leaves = jax.tree.leaves(spec)
    Q = int(leaves[0].shape[0]) if leaves else 1
    score = jnp.zeros((Q, M), jnp.float32)
    if spec.embed is not None:
        qs = spec.embed
        if spec.sem_weight is not None:
            qs = qs * spec.sem_weight[:, None]
        score = score + jnp.matmul(qs, summ.embed_mean.T,
                                   precision=jax.lax.Precision.HIGHEST)
    if spec.near is not None and spec.prox_weight is not None:
        center, _ = spec.near
        d = jnp.linalg.norm(summ.centroid[None] - center[:, None, :],
                            axis=-1)
        score = score + spec.prox_weight[:, None] / (1.0 + d)
    if spec.density_weight is not None:
        score = score + spec.density_weight[:, None] \
            * jnp.log1p(summ.count.astype(jnp.float32))[None, :]
    score = jnp.where(ok, score, -jnp.inf)
    vals, cells = jax.lax.top_k(score, k)
    bad = jnp.isneginf(vals)
    cells = jnp.where(bad, -1, cells)
    take = jnp.maximum(cells, 0)
    counts = jnp.where(bad, 0, summ.count[take])
    cents = jnp.where(bad[..., None], 0.0, summ.centroid[take])
    if k < spec.k:
        pad = spec.k - k
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        cells = jnp.pad(cells, ((0, 0), (0, pad)), constant_values=-1)
        counts = jnp.pad(counts, ((0, 0), (0, pad)))
        cents = jnp.pad(cents, ((0, 0), (0, pad), (0, 0)))
    out = ClusterResult(zones=jnp.full_like(cells, -1), cells=cells,
                        scores=vals, counts=counts, centroids=cents)
    if squeeze:
        out = ClusterResult(*(x[0] for x in out))
    return out


def cluster_query(spec, items) -> ClusterResult:
    """Run a cluster-level query over ``items = [(zone_or_None, index,
    target)]`` and merge to one top-k (stable: zone order breaks ties)."""
    parts = []
    for zone, index, target in items:
        cols = _columns(target)
        r = _cluster_execute(spec, index.summaries,
                             has_obs=cols.obs_count is not None,
                             has_seen=cols.last_seen is not None)
        z = -1 if zone is None else int(zone)
        parts.append(ClusterResult(
            zones=jnp.where(r.cells >= 0, z, -1), cells=r.cells,
            scores=r.scores, counts=r.counts, centroids=r.centroids))
    if len(parts) == 1:
        return parts[0]
    cat = ClusterResult(*(jnp.concatenate([getattr(p, f) for p in parts],
                                          axis=-1 if f != "centroids"
                                          else -2)
                          for f in ClusterResult._fields))
    vals, sel = jax.lax.top_k(cat.scores, min(spec.k, cat.scores.shape[-1]))
    take = lambda x: jnp.take_along_axis(x, sel, axis=-1)
    return ClusterResult(zones=take(cat.zones), cells=take(cat.cells),
                         scores=vals, counts=take(cat.counts),
                         centroids=jnp.take_along_axis(
                             cat.centroids, sel[..., None], axis=-2))
