"""Declarative query engine over the SemanticXR object maps (Sec. 2.3.2).

The paper's headline capability is a *queryable* semantic map: open-vocabulary
AND spatial object search with sub-100 ms latency at 10k objects.  One
``Query`` pytree spec expresses the whole request —

  * semantic similarity        ``embed`` (text embedding, optionally scaled
                               by ``sem_weight``)
  * spatial predicates         ``near=(center, radius)``, ``aabb=(lo, hi)``,
                               ``zones``+``grid`` (zone membership)
  * attribute filters          ``labels`` (allowed class ids), ``min_points``,
                               ``min_obs`` (observation-count confidence
                               proxy), ``since`` (recency: last seen frame)
  * score combination          ``sem_weight`` * cosine + ``prox_weight`` *
                               1/(1+dist-to-center)
  * top-k                      ``k``

— and ``compile_query(spec, target)`` lowers the whole predicate + score +
top-k plan into ONE fused jitted dispatch, executable uniformly against the
device ``LocalMap``, the server ``ObjectStore``, and the fleet's
``ZoneShardedStore`` (where zone/near predicates prune shards *before*
dispatch; each selected shard then runs the same fused plan and a [k]-sized
merge combines them).

Predicates are fused as ``-inf`` score injection — never a gather/compaction
pass — so a predicate-heavy query costs about the same single table sweep as
an embedding-only top-k (measured ≤1.05x at 10k objects; the predicate mask
itself is O(N) elementwise work XLA fuses into the dispatch).  With
``use_pallas`` the sweep runs in the bias-kernel variant of
``kernels/query_topk.py``: scores = MXU matmul + per-slot bias, with the
[Q, N] bias computed outside the kernel and streamed through it alongside
the [N, E] table — small next to the table traffic, and never a
gather/compaction of the table itself.

Static plan structure (which predicates are present, ``k``, label/zone sets)
lives in pytree aux data; dynamic values (embeddings, centers, radii,
thresholds) are array leaves — re-running a compiled plan with new values
never retraces.

The seed's six embedding-only entry points (``query_local``,
``query_server``, ``batched_query_local/server``, the serving step-fn and
the fleet SQ path) survive as thin deprecated wrappers over this engine.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass, replace
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.local_map import LocalMap
from repro.core.store import ObjectStore
from repro.obs.trace import span as obs_span

NEG = -1e30          # kernel-side mask value (see kernels/query_topk.py)

_DYN_FIELDS = ("embed", "sem_weight", "near", "aabb", "prox_weight",
               "min_points", "min_obs", "since", "density_weight")
_STATIC_FIELDS = ("labels", "zones", "grid", "k", "batched", "level")


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Query:
    """One declarative map query.  Unset (None) fields are compiled away.

    Dynamic leaves (arrays — new values never retrace):
      embed        [E] f32 (or [Q, E] when ``batched``) text embedding
      sem_weight   scalar weight on the cosine term (default 1)
      near         (center [3], radius scalar): keep objects with
                   ||centroid - center|| <= radius
      aabb         (lo [3], hi [3]): keep objects whose centroid lies inside
      prox_weight  scalar: add prox_weight / (1 + dist-to-near-center) to the
                   score (requires ``near``)
      min_points   scalar: keep objects with n_points >= min_points
      min_obs      scalar: keep objects with obs_count >= min_obs
                   (vacuous on targets without obs_count, e.g. LocalMap)
      since        scalar frame index: keep objects with last_seen >= since
                   (vacuous on targets without last_seen)
      density_weight  scalar (cluster-level queries only): add
                   density_weight * log1p(member count) to a cluster's
                   score — "the densest region matching this text"

    Static plan structure (participates in the jit cache key):
      labels       tuple of allowed class ids
      zones        tuple of zone ids (requires ``grid``); on a
                   ZoneShardedStore also prunes shards before dispatch
      grid         (x0, z0, zone_size, nx, nz) — XZ zone grid parameters
                   (see ``Query.grid_of``)
      k            top-k size
      batched      leaves carry a leading query dim Q (see stack_queries)
      level        "object" (default) returns top-k objects;
                   "cluster" returns top-k *cluster summaries* (a
                   ``repro.index.ClusterResult``) — requires a
                   ClusterIndex on the target / compile call
    """
    embed: Any = None
    sem_weight: Any = None
    near: Any = None
    aabb: Any = None
    prox_weight: Any = None
    min_points: Any = None
    min_obs: Any = None
    since: Any = None
    density_weight: Any = None
    labels: tuple | None = None
    zones: tuple | None = None
    grid: tuple | None = None
    k: int = 5
    batched: bool = False
    level: str = "object"

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in _DYN_FIELDS),
                tuple(getattr(self, f) for f in _STATIC_FIELDS))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(zip(_DYN_FIELDS, children)),
                   **dict(zip(_STATIC_FIELDS, aux)))

    @staticmethod
    def grid_of(grid) -> tuple:
        """ZoneGrid (duck-typed: .origin/.zone_size/.nx/.nz) -> grid tuple."""
        return (float(grid.origin[0]), float(grid.origin[1]),
                float(grid.zone_size), int(grid.nx), int(grid.nz))


class QueryResult(NamedTuple):
    """Top-k hits.  Padded ranks (k exceeds the matching object count) are
    masked: score -inf, oid 0, slot -1 — stale slot ids never surface."""
    oids: jax.Array       # [k] / [Q, k] int32 (0 = no match)
    scores: jax.Array     # [k] / [Q, k] f32 (-inf = no match)
    slots: jax.Array      # [k] / [Q, k] int32 target row (-1 = no match)


def stack_queries(specs: list, pad_to: int | None = None) -> Query:
    """Stack Q same-structure specs into one batched spec (SoA leading dim).

    All specs must share plan structure (same fields set, same static
    labels/zones/grid/k).  ``pad_to`` repeats the first spec to a fixed Q so
    the stack and the downstream sweep see one shape per scheduler batch
    size.  The stack is one compiled dispatch (``_stack``) whatever Q and
    the number of leaves; values, dtypes and weak types are those of
    ``jnp.stack`` over the leaves.
    """
    if not specs:
        raise ValueError("stack_queries needs at least one spec")
    first = specs[0]
    if first.batched:
        raise ValueError("stack_queries takes unbatched specs")
    if not jax.tree.leaves(first):
        raise ValueError("stack_queries needs at least one dynamic field "
                         "(all-static specs have no per-query dimension)")
    aux0 = specs[0].tree_flatten()[1]
    for s in specs[1:]:
        if s.tree_flatten()[1] != aux0:
            raise ValueError("stack_queries: mismatched static plan "
                             "(labels/zones/grid/k must agree)")
    if pad_to is not None and pad_to > len(specs):
        specs = specs + [first] * (pad_to - len(specs))
    return _stack(tuple(specs))


@jax.jit
def _stack(specs: tuple) -> Query:
    """Q unbatched specs -> one batched spec; keyed by Q, the leaf shapes
    and dtypes, and the static plan."""
    return replace(jax.tree.map(lambda *xs: jnp.stack(xs), *specs),
                   batched=True)


# ---------------------------------------------------------------------------
# the fused execution path
# ---------------------------------------------------------------------------
class _Cols(NamedTuple):
    """Uniform columnar view of any query target (geometry stays behind)."""
    ids: jax.Array
    active: jax.Array
    embed: jax.Array
    label: jax.Array
    n_points: jax.Array
    centroid: jax.Array
    obs_count: Any        # None on targets without it (LocalMap)
    last_seen: Any        # None on targets without it (LocalMap)


def _columns(target) -> _Cols:
    return _Cols(ids=target.ids, active=target.active, embed=target.embed,
                 label=target.label, n_points=target.n_points,
                 centroid=target.centroid,
                 obs_count=getattr(target, "obs_count", None),
                 last_seen=getattr(target, "last_seen", None))


def _promote(spec: Query) -> Query:
    """Give every dynamic leaf a leading Q=1 dim (single -> batched form)."""
    if spec.batched:
        return spec
    dyn, aux = spec.tree_flatten()
    dyn = tuple(jax.tree.map(lambda x: jnp.asarray(x)[None], d)
                for d in dyn)
    out = Query.tree_unflatten(aux, dyn)
    return replace(out, batched=True)


def _zone_ids(centroid: jax.Array, grid: tuple) -> jax.Array:
    """jnp mirror of server.zones.ZoneGrid.zone_of (clamped XZ grid)."""
    x0, z0, zs, nx, nz = grid
    ix = jnp.clip(jnp.floor((centroid[:, 0] - x0) / zs), 0, nx - 1)
    iz = jnp.clip(jnp.floor((centroid[:, 2] - z0) / zs), 0, nz - 1)
    return (ix * nz + iz).astype(jnp.int32)


def _mask_and_bonus(spec: Query, cols: _Cols):
    """All predicates as one [Q, cap] bool mask + the proximity bonus term.

    Pure elementwise math over the columns — XLA fuses it with the
    similarity matmul and the top-k into a single dispatch; there is no
    per-predicate pass and never a gather/compaction.
    """
    cap = cols.active.shape[0]
    ok = jnp.broadcast_to(cols.active[None, :], (1, cap))
    if spec.labels is not None:
        ok = ok & jnp.isin(cols.label,
                           jnp.asarray(spec.labels, jnp.int32))[None, :]
    if spec.zones is not None:
        if spec.grid is None:
            raise ValueError("Query.zones requires Query.grid")
        zid = _zone_ids(cols.centroid, spec.grid)
        ok = ok & jnp.isin(zid, jnp.asarray(spec.zones, jnp.int32))[None, :]
    if spec.min_points is not None:
        ok = ok & (cols.n_points[None, :] >= spec.min_points[:, None])
    if spec.min_obs is not None and cols.obs_count is not None:
        ok = ok & (cols.obs_count[None, :] >= spec.min_obs[:, None])
    if spec.since is not None and cols.last_seen is not None:
        ok = ok & (cols.last_seen[None, :] >= spec.since[:, None])
    if spec.aabb is not None:
        lo, hi = spec.aabb
        inside = ((cols.centroid[None] >= lo[:, None, :])
                  & (cols.centroid[None] <= hi[:, None, :])).all(-1)
        ok = ok & inside
    bonus = None
    if spec.near is not None:
        center, radius = spec.near
        d = jnp.linalg.norm(cols.centroid[None] - center[:, None, :],
                            axis=-1)                       # [Q, cap]
        ok = ok & (d <= radius[:, None])
        if spec.prox_weight is not None:
            bonus = spec.prox_weight[:, None] / (1.0 + d)
    elif spec.prox_weight is not None:
        raise ValueError("Query.prox_weight requires Query.near")
    return ok, bonus


def _finalize(ids: jax.Array, scores: jax.Array,
              slots: jax.Array) -> QueryResult:
    """Mask padded ranks: -inf score, sentinel slot -1, oid 0."""
    invalid = (scores <= NEG) | (slots < 0)
    slots = jnp.where(invalid, -1, slots)
    oids = jnp.where(invalid, 0, ids[jnp.maximum(slots, 0)])
    scores = jnp.where(invalid, -jnp.inf, scores)
    return QueryResult(oids=oids, scores=scores, slots=slots)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _execute(spec: Query, cols: _Cols, *, use_pallas: bool = False):
    """The one compiled execution path: predicates + score + top-k fused.

    Plan structure (spec aux + presence of optional leaves/columns) keys the
    jit cache; new dynamic values re-run the same executable.
    """
    squeeze = not spec.batched
    spec = _promote(spec)
    cap = cols.active.shape[0]
    k = min(spec.k, cap)
    leaves = jax.tree.leaves(spec)
    Q = int(leaves[0].shape[0]) if leaves else 1
    ok, bonus = _mask_and_bonus(spec, cols)
    ok = jnp.broadcast_to(ok, (Q, cap))

    if use_pallas and spec.embed is not None:
        from repro.kernels import ops as kops
        qs = spec.embed
        if spec.sem_weight is not None:
            qs = qs * spec.sem_weight[:, None]
        bias = jnp.zeros((Q, cap), jnp.float32) if bonus is None \
            else jnp.broadcast_to(bonus, (Q, cap))
        bias = jnp.where(ok, bias, NEG)
        scores, slots = kops.query_topk_bias(qs, cols.embed, bias, k)
    else:
        if spec.embed is not None:
            # fp32 contraction: TPU's default f32 matmul is one bf16
            # pass (~1e-3 score error), which reorders near ties
            sim = jnp.matmul(spec.embed, cols.embed.T,
                             precision=jax.lax.Precision.HIGHEST)  # [Q, cap]
            if spec.sem_weight is not None:
                sim = sim * spec.sem_weight[:, None]
        else:
            sim = jnp.zeros(ok.shape, jnp.float32)
        if bonus is not None:
            sim = sim + bonus
        sim = jnp.where(ok, sim, -jnp.inf)
        scores, slots = jax.lax.top_k(sim, k)

    res = _finalize(cols.ids, scores, slots)
    if k < spec.k:                 # honor k > capacity with padded ranks
        pad = spec.k - k
        res = QueryResult(
            oids=jnp.pad(res.oids, ((0, 0), (0, pad))),
            scores=jnp.pad(res.scores, ((0, 0), (0, pad)),
                           constant_values=-jnp.inf),
            slots=jnp.pad(res.slots, ((0, 0), (0, pad)),
                          constant_values=-1))
    if squeeze:
        res = QueryResult(*(x[0] for x in res))
    return res


@functools.partial(jax.jit, static_argnames=("capz",))
def _merge_shards(oids, scores, slots, zone_ids, capz: int):
    """Fold S per-shard top-k results ([S, Q, k] each) into one [Q, k].

    Shard-local slots globalize to ``zone * zone_capacity + slot`` so a
    sharded result is addressable like a flat one."""
    gslot = jnp.where(slots >= 0,
                      zone_ids[:, None, None] * capz + slots, -1)
    cat = lambda x: jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1)
    sc, oid, sl = cat(scores), cat(oids), cat(gslot)       # [Q, S*k]
    k = scores.shape[-1]
    top, sel = jax.lax.top_k(sc, k)
    take = lambda x: jnp.take_along_axis(x, sel, axis=1)
    return QueryResult(oids=take(oid), scores=top, slots=take(sl))


# ---------------------------------------------------------------------------
# compile + execute API
# ---------------------------------------------------------------------------
def _is_sharded(target) -> bool:
    return hasattr(target, "zones") and hasattr(target, "grid")


def _select_shards(spec: Query, target) -> list:
    """Zone predicates prune shards BEFORE dispatch (host-side, using the
    spec's concrete values at compile time)."""
    Z = target.grid.n_zones
    if spec.zones is not None:
        return [z for z in sorted(set(spec.zones)) if 0 <= z < Z]
    if spec.near is not None:
        center, radius = spec.near
        c = np.atleast_2d(np.asarray(center))
        r = np.atleast_1d(np.asarray(radius))
        sel = np.zeros((Z,), bool)
        for i in range(c.shape[0]):
            sel |= target.grid.overlaps(c[i], float(r[min(i, len(r) - 1)]))
        return [z for z in range(Z) if sel[z]]
    return list(range(Z))


@dataclass
class CompiledQuery:
    """A (spec, target)-shaped executable plan.

    Calling it re-runs the fused dispatch; pass a new same-structure ``spec``
    (and/or an updated target) to re-execute without retracing.  For sharded
    targets the shard selection is fixed at compile time from the spec's
    concrete zone/near values.

    ``index`` (a ``repro.index.ClusterIndex``, or a ``{zone: ClusterIndex}``
    dict for sharded targets) switches the plan to the coarse-to-fine
    two-stage path when the target is large enough (``index.engaged()``);
    below that threshold the flat sweep runs unchanged.  When no index is
    passed the plan discovers one on the target itself
    (``target.cluster_index`` / ``target.indexes``).  ``level="cluster"``
    specs require an index and return a ``repro.index.ClusterResult``.
    """
    spec: Query
    use_pallas: bool = False
    shards: tuple | None = None        # zone ids (sharded targets only)
    index: Any = None                  # ClusterIndex | {zone: ClusterIndex}

    def __call__(self, target, spec: Query | None = None) -> QueryResult:
        with obs_span("query.dispatch", cat="query",
                      sharded=_is_sharded(target)) as sp:
            res = self._run(target, spec)
            sp.fence(res.scores)
        return res

    def _run(self, target, spec: Query | None = None) -> QueryResult:
        spec = self.spec if spec is None else spec
        if not _is_sharded(target):
            idx = self.index if self.index is not None \
                else getattr(target, "cluster_index", None)
            if spec.level == "cluster":
                if idx is None:
                    raise ValueError(
                        "Query(level='cluster') needs a ClusterIndex: pass "
                        "index= to compile_query or attach one as "
                        "target.cluster_index")
                from repro.index.search import cluster_query
                return cluster_query(spec, [(None, idx, target)])
            if idx is not None and idx.engaged():
                from repro.index.search import two_stage_query
                return two_stage_query(spec, target, idx,
                                       use_pallas=self.use_pallas)
            return _execute(spec, _columns(target),
                            use_pallas=self.use_pallas)
        return self._run_sharded(target, spec)

    def _run_sharded(self, target, spec: Query) -> QueryResult:
        shards = self.shards if self.shards is not None \
            else tuple(_select_shards(spec, target))
        idxs = self.index if self.index is not None \
            else getattr(target, "indexes", None)
        if not idxs:                   # {} (index never enabled) == None
            idxs = None
        k = spec.k
        Q = None
        if spec.batched:
            lead = jax.tree.leaves(spec)
            Q = int(lead[0].shape[0]) if lead else 1
        if spec.level == "cluster":
            from repro.index.search import ClusterResult, cluster_query
            items = [] if idxs is None else \
                [(z, idxs[z], target.zones[z]) for z in shards
                 if idxs.get(z) is not None]
            if not items:
                if idxs is None:
                    raise ValueError(
                        "Query(level='cluster') on a sharded target needs "
                        "zone indexes: pass index= to compile_query or call "
                        "enable_index() on the store")
                shape = (k,) if Q is None else (Q, k)
                return ClusterResult(
                    zones=jnp.full(shape, -1, jnp.int32),
                    cells=jnp.full(shape, -1, jnp.int32),
                    scores=jnp.full(shape, -jnp.inf),
                    counts=jnp.zeros(shape, jnp.int32),
                    centroids=jnp.zeros(shape + (3,), jnp.float32))
            return cluster_query(spec, items)
        if not shards:
            shape = (k,) if Q is None else (Q, k)
            return QueryResult(oids=jnp.zeros(shape, jnp.int32),
                               scores=jnp.full(shape, -jnp.inf),
                               slots=jnp.full(shape, -1, jnp.int32))
        # the same fused plan per selected shard (shards share shapes, so
        # this compiles once), then a [k]-sized merge; shards with an
        # engaged index take the two-stage path, the rest stay flat
        bspec = spec if spec.batched else _promote(spec)
        parts = []
        for z in shards:
            zt = target.zones[z]
            zidx = None if idxs is None else idxs.get(z)
            if zidx is not None and zidx.engaged():
                from repro.index.search import two_stage_query
                parts.append(two_stage_query(bspec, zt, zidx,
                                             use_pallas=self.use_pallas))
            else:
                parts.append(_execute(bspec, _columns(zt),
                                      use_pallas=self.use_pallas))
        res = _merge_shards(jnp.stack([p.oids for p in parts]),
                            jnp.stack([p.scores for p in parts]),
                            jnp.stack([p.slots for p in parts]),
                            jnp.asarray(shards, jnp.int32),
                            capz=int(target.zones[0].ids.shape[0]))
        if not spec.batched:
            res = QueryResult(*(x[0] for x in res))
        return res


def compile_query(spec: Query, target, *, use_pallas: bool = False,
                  index: Any = None) -> CompiledQuery:
    """Lower ``spec`` against ``target``'s kind into one executable plan.

    ``target`` is a LocalMap, ObjectStore, or ZoneShardedStore (duck-typed).
    The returned plan is reusable: call it with updated targets/specs of the
    same structure without recompiling.  ``index`` (or an index discovered
    on the target) makes the plan coarse-to-fine — see ``CompiledQuery``.
    """
    shards = tuple(_select_shards(spec, target)) if _is_sharded(target) \
        else None
    return CompiledQuery(spec=spec, use_pallas=use_pallas, shards=shards,
                         index=index)


def execute_query(target, spec: Query, *, use_pallas: bool = False,
                  index: Any = None) -> QueryResult:
    """One-shot convenience: compile (cached by structure) + run."""
    return CompiledQuery(spec=spec, use_pallas=use_pallas,
                         index=index)(target)


# ---------------------------------------------------------------------------
# deprecated embedding-only wrappers (the seed API)
# ---------------------------------------------------------------------------
def _warn_deprecated(name: str):
    warnings.warn(
        f"repro.core.query.{name} is deprecated: build a repro.core.query."
        "Query spec and run it through compile_query/execute_query (which "
        "adds spatial/attribute predicates and score combination on the "
        "same fused dispatch).", DeprecationWarning, stacklevel=3)


def query_server(store: ObjectStore, query_embed: jax.Array, *, k: int = 5,
                 use_pallas: bool = False) -> QueryResult:
    """Deprecated: ``execute_query(store, Query(embed=..., k=k))``."""
    _warn_deprecated("query_server")
    return execute_query(store, Query(embed=query_embed, k=k),
                         use_pallas=use_pallas)


def query_local(m: LocalMap, query_embed: jax.Array, *, k: int = 5,
                use_pallas: bool = False) -> QueryResult:
    """Deprecated: ``execute_query(m, Query(embed=..., k=k))``."""
    _warn_deprecated("query_local")
    return execute_query(m, Query(embed=query_embed, k=k),
                         use_pallas=use_pallas)


def batched_query_local(m: LocalMap, query_embeds: jax.Array, *, k: int = 5,
                        use_pallas: bool = False) -> QueryResult:
    """Deprecated: ``execute_query`` with a batched Query spec."""
    _warn_deprecated("batched_query_local")
    return execute_query(m, Query(embed=query_embeds, k=k, batched=True),
                         use_pallas=use_pallas)


def batched_query_server(store: ObjectStore, query_embeds: jax.Array, *,
                         k: int = 5, use_pallas: bool = False) -> QueryResult:
    """Deprecated: ``execute_query`` with a batched Query spec."""
    _warn_deprecated("batched_query_server")
    return execute_query(store, Query(embed=query_embeds, k=k, batched=True),
                         use_pallas=use_pallas)
