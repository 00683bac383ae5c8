"""Incremental object association + merge (paper Sec. 2.3.1 / 3.1).

New per-frame detections are matched to existing map objects by combined
spatial proximity (centroid distance, normalized by bbox scale) and semantic
similarity (embedding cosine).  Matches merge in place (running-mean
embedding, re-downsampled merged geometry, version bump); misses insert new
objects; transient observations are pruned by obs_count gating downstream.

TPU adaptation: the per-detection greedy loop of the reference pipelines
becomes a batched cost matrix [max_detections, capacity] (an MXU matmul for
the cosine term, the pairwise-distance kernel in kernels/pairwise for the
spatial term) + a fully batched resolve: argmax per detection, within-frame
conflict resolution (detections are distinct objects by construction, so at
most one detection may merge into a store slot), one vmapped merge over the
detection batch, and one scatter per store field.  No per-detection scan —
the whole frame is a single XLA dispatch under jit.

``associate_reference`` keeps the original sequential-scan semantics as the
equivalence oracle (tests/test_batched_equivalence.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import geometry as geo
from repro.core import store as store_mod
from repro.core.store import ObjectStore


class Detections(NamedTuple):
    """Fixed-capacity batch of per-frame object observations."""
    embed: jax.Array      # [D, E] f32 unit-norm
    label: jax.Array      # [D] int32
    points: jax.Array     # [D, P, 3]
    n_points: jax.Array   # [D] int32
    valid: jax.Array      # [D] bool


def association_scores(store: ObjectStore, det: Detections, *,
                       spatial_sigma: float = 0.75, det_centroid=None):
    """[D, cap] combined match score in [0,1]; inactive slots = -inf.

    ``det_centroid`` ([D, 3]) skips the per-detection centroid pass when
    the caller already has it — the fused lift kernel (kernels/lift_compact)
    folds centroid accumulation into its streaming sweep, so the ingest
    path never recomputes it here."""
    if det_centroid is not None:
        cent_d = det_centroid
    else:
        cent_d = jax.vmap(lambda p, n: geo.centroid_bbox(p, n)[0])(
            det.points, det.n_points)                      # [D,3]
    dist2 = jnp.sum(
        jnp.square(cent_d[:, None, :] - store.centroid[None, :, :]), axis=-1)
    spatial = jnp.exp(-dist2 / (2 * spatial_sigma ** 2))   # [D,cap]
    # cosine of unit vectors at full f32: the TPU's default f32 matmul is
    # one bf16 pass (~1e-3 off), which moves decisions near the threshold
    semantic = jnp.matmul(det.embed, store.embed.T,
                          precision=jax.lax.Precision.HIGHEST)
    score = 0.5 * spatial + 0.5 * semantic
    score = jnp.where(store.active[None, :], score, -jnp.inf)
    score = jnp.where(det.valid[:, None], score, -jnp.inf)
    return score, cent_d


class Resolution(NamedTuple):
    """How each detection of a frame was resolved ([D] each)."""
    slot: jax.Array       # int32 slot written; cap where nothing was
    target: jax.Array     # int32 best existing slot (argmax score)
    score: jax.Array      # f32 its score; -inf for an invalid detection
    matched: jax.Array    # bool merged into ``target`` (else inserted)


def associate(store: ObjectStore, det: Detections, **kw) -> ObjectStore:
    """``associate_rows`` without the per-detection resolution."""
    return associate_rows(store, det, **kw)[0]


def associate_rows(store: ObjectStore, det: Detections, *, frame: jax.Array,
                   match_threshold: float = 0.6, point_budget: int = 2000,
                   ema: float = 0.25, det_centroid=None):
    """Associate one frame's detections into the store. jit-able.
    Returns (store, ``Resolution``).

    Fully batched resolve — no per-detection scan:

      1. argmax over the [D, cap] score matrix picks each detection's best
         existing object; within-frame conflicts (two detections claiming the
         same slot) are resolved to the highest-scoring claimant, losers fall
         through to the insert path (detections in one frame come from
         instance segmentation and are distinct objects by construction).
      2. merge values (embedding EMA, merged+recapped cloud, centroid/bbox)
         are computed for the whole detection batch with one vmap.
      3. inserts are assigned free slots in detection order (matching the
         sequential semantics: the r-th inserting detection takes the r-th
         free slot by ascending index and id ``next_id + r``).
      4. each store field is written with ONE scatter; rows that neither
         merge nor insert target index ``cap``, which JAX scatter drops.
    """
    score, _ = association_scores(store, det, det_centroid=det_centroid)
    D, cap = score.shape
    frame = jnp.asarray(frame, jnp.int32)
    point_budget = min(point_budget, store.points.shape[1])

    # --- 1. resolve matches + within-frame conflicts
    j_star = jnp.argmax(score, axis=1)                          # [D]
    best = jnp.take_along_axis(score, j_star[:, None], 1)[:, 0]
    wants = (best >= match_threshold) & det.valid
    claim = wants[:, None] & (j_star[:, None] == jnp.arange(cap)[None, :])
    claim_score = jnp.where(claim, best[:, None], -jnp.inf)     # [D, cap]
    winner = jnp.argmax(claim_score, axis=0)                    # [cap]
    is_match = wants & (winner[j_star] == jnp.arange(D))

    # --- 2. geometry for the whole batch with ONE vmapped merge: selecting
    # the inputs (store cloud for matches, an empty n_a=0 cloud for inserts,
    # under which merge_clouds degenerates to downsample(det.points)) is
    # cheaper than computing both the merge and insert variants per row.
    tgt_emb = store.embed[j_star]                               # [D, E]
    memb = (1 - ema) * tgt_emb + ema * det.embed
    memb = memb / jnp.maximum(
        jnp.linalg.norm(memb, axis=-1, keepdims=True), 1e-9)
    n_a = jnp.where(is_match, store.n_points[j_star], 0)
    npts, nn = jax.vmap(
        lambda pa, na, pb, nb: geo.merge_clouds(pa, na, pb, nb, point_budget)
    )(store.points[j_star], n_a, det.points, det.n_points)
    nc, nmn, nmx = jax.vmap(geo.centroid_bbox)(npts, nn)

    # --- 3. free-slot assignment for inserts in detection order.  A slot
    # is free only when neither live nor tombstoned: a pending deletion
    # still owns its slot until the protocol retires it
    # (store.release_tombstones) — reusing it would hide the new object
    # behind clients' synced versions.
    occupied = store.active | store_mod.deleted_mask(store)
    do_insert = det.valid & ~is_match
    rank = jnp.maximum(jnp.cumsum(do_insert) - 1, 0)            # [D]
    free_order = jnp.argsort(occupied)          # stable: free slots, asc idx
    n_free = (~occupied).sum()
    ins_ok = do_insert & (jnp.cumsum(do_insert) - 1 < n_free)
    ins_slot = free_order[jnp.minimum(rank, cap - 1)]

    # --- 4. one scatter per field; non-writing rows hit index cap (dropped)
    tgt = jnp.where(is_match, j_star, jnp.where(ins_ok, ins_slot, cap))
    new_emb = jnp.where(is_match[:, None], memb, det.embed)
    new_obs = jnp.where(is_match, store.obs_count[j_star] + 1, 1)
    new_ver = jnp.where(is_match, store.version[j_star] + 1, 1)
    new_ids = jnp.where(is_match, store.ids[j_star], store.next_id + rank)
    n_inserted = jnp.minimum(do_insert.sum(), n_free).astype(jnp.int32)
    res = Resolution(slot=tgt.astype(jnp.int32),
                     target=j_star.astype(jnp.int32), score=best,
                     matched=is_match)
    return store._replace(
        ids=store.ids.at[tgt].set(new_ids),
        active=store.active.at[tgt].set(True),
        embed=store.embed.at[tgt].set(new_emb),
        label=store.label.at[tgt].set(
            jnp.where(is_match, store.label[j_star], det.label)),
        points=store.points.at[tgt].set(npts),
        n_points=store.n_points.at[tgt].set(nn),
        centroid=store.centroid.at[tgt].set(nc),
        bbox_min=store.bbox_min.at[tgt].set(nmn),
        bbox_max=store.bbox_max.at[tgt].set(nmx),
        obs_count=store.obs_count.at[tgt].set(new_obs),
        version=store.version.at[tgt].set(new_ver),
        last_seen=store.last_seen.at[tgt].set(frame),
        next_id=store.next_id + n_inserted,
    ), res


def associate_reference(store: ObjectStore, det: Detections, *,
                        frame: jax.Array, match_threshold: float = 0.6,
                        point_budget: int = 2000,
                        ema: float = 0.25) -> ObjectStore:
    """Seed sequential-scan associate — the equivalence oracle for the
    batched path above (identical semantics on conflict-free frames)."""
    score, cent_d = association_scores(store, det)
    D, cap = score.shape
    frame = jnp.asarray(frame, jnp.int32)
    point_budget = min(point_budget, store.points.shape[1])

    def step(st: ObjectStore, i):
        row = score[i]
        j = jnp.argmax(row)
        best = row[j]
        is_match = (best >= match_threshold) & det.valid[i]

        # --- merge path
        def merge(st: ObjectStore) -> ObjectStore:
            new_emb = (1 - ema) * st.embed[j] + ema * det.embed[i]
            new_emb = new_emb / jnp.maximum(jnp.linalg.norm(new_emb), 1e-9)
            mpts, mn_ = geo.merge_clouds_argsort(
                st.points[j], st.n_points[j], det.points[i],
                det.n_points[i], point_budget)
            c, mn, mx = geo.centroid_bbox(mpts, mn_)
            return st._replace(
                embed=st.embed.at[j].set(new_emb),
                points=st.points.at[j].set(mpts),
                n_points=st.n_points.at[j].set(mn_),
                centroid=st.centroid.at[j].set(c),
                bbox_min=st.bbox_min.at[j].set(mn),
                bbox_max=st.bbox_max.at[j].set(mx),
                obs_count=st.obs_count.at[j].add(1),
                version=st.version.at[j].add(1),
                last_seen=st.last_seen.at[j].set(frame),
            )

        # --- insert path (first free slot)
        def insert(st: ObjectStore) -> ObjectStore:
            free = jnp.argmin(st.active)       # first False
            can = ~st.active[free] & det.valid[i]
            pts, n = geo.downsample(det.points[i], det.n_points[i],
                                    point_budget)
            c, mn, mx = geo.centroid_bbox(pts, n)

            def do(st: ObjectStore) -> ObjectStore:
                return st._replace(
                    ids=st.ids.at[free].set(st.next_id),
                    active=st.active.at[free].set(True),
                    embed=st.embed.at[free].set(det.embed[i]),
                    label=st.label.at[free].set(det.label[i]),
                    points=st.points.at[free].set(pts),
                    n_points=st.n_points.at[free].set(n),
                    centroid=st.centroid.at[free].set(c),
                    bbox_min=st.bbox_min.at[free].set(mn),
                    bbox_max=st.bbox_max.at[free].set(mx),
                    obs_count=st.obs_count.at[free].set(1),
                    version=st.version.at[free].set(1),
                    last_seen=st.last_seen.at[free].set(frame),
                    next_id=st.next_id + 1,
                )
            return jax.lax.cond(can, do, lambda s: s, st)

        st = jax.lax.cond(is_match, merge, insert, st)
        return st, None

    store, _ = jax.lax.scan(step, store, jnp.arange(D))
    return store


def prune_transients(store: ObjectStore, *, frame: jax.Array,
                     min_obs: int = 2, max_age: int = 30) -> ObjectStore:
    """Deactivate objects never confirmed by repeat observation (Sec. 2.3.1):
    an object seen fewer than ``min_obs`` times and not re-observed within
    ``max_age`` frames is dropped as a transient detection."""
    frame = jnp.asarray(frame, jnp.int32)
    stale = (frame - store.last_seen > max_age) & (store.obs_count < min_obs)
    return store._replace(active=store.active & ~stale)
