"""Declarative query engine vs a pure-numpy reference oracle.

The oracle recomputes the whole plan — predicate masks, score combination,
top-k — in numpy, for every target kind (LocalMap, ObjectStore,
ZoneShardedStore).  A hypothesis property sweeps randomized stores,
predicate combinations, and k values; deterministic subsets always run.
Also covers: padded-rank masking (the stale-slot-id regression), legacy
wrapper equivalence + DeprecationWarning, Pallas-path parity, batched
stacking, and the serving step-fn carrying Query specs.
"""
import warnings
from dataclasses import replace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.knobs import Knobs
from repro.core.local_map import init_local_map
from repro.core.query import (Query, QueryResult, compile_query,
                              execute_query, stack_queries)
from repro.core.store import synthetic_store
from repro.server.zones import ZoneGrid, ZoneShardedStore

E, P = 32, 16
ROOM = 8.0


def _store(n, seed, cap=None):
    return synthetic_store(n, cap or n, E, P, seed=seed,
                           centroid_low=(-ROOM / 2, 0.0, -ROOM / 2),
                           centroid_high=(ROOM / 2, 2.0, ROOM / 2))


def _local_map(n, seed):
    """LocalMap with the same columns as _store(n, seed) (no obs/last_seen)."""
    st = _store(n, seed)
    cap = st.ids.shape[0]
    m = init_local_map(Knobs(client_capacity=cap,
                             max_object_points_client=P), E)
    return m._replace(ids=st.ids, active=st.active, embed=st.embed,
                      label=st.label, n_points=st.n_points,
                      centroid=st.centroid)


def _zoned(n, seed, grid=None):
    grid = grid or ZoneGrid.for_room(ROOM, nx=2, nz=2)
    st = _store(n, seed)
    zs = ZoneShardedStore(knobs=Knobs(server_capacity=4 * n,
                                      max_object_points_server=P),
                          embed_dim=E, grid=grid, zone_capacity=n,
                          max_points=P)
    zs.refresh_from(st)
    assert zs.dropped == 0
    return zs, st


# ---------------------------------------------------------------------------
# the numpy oracle: full plan (predicates + scoring + top-k) re-derived
# ---------------------------------------------------------------------------
def _np_scores(spec: Query, target, *, has_obs: bool) -> np.ndarray:
    """[cap] f32 combined score, -inf where any predicate fails."""
    act = np.asarray(target.active)
    ok = act.copy()
    cent = np.asarray(target.centroid, np.float32)
    if spec.labels is not None:
        ok &= np.isin(np.asarray(target.label), np.asarray(spec.labels))
    if spec.zones is not None:
        x0, z0, zs_, nx, nz = spec.grid
        ix = np.clip(np.floor((cent[:, 0] - x0) / zs_), 0, nx - 1)
        iz = np.clip(np.floor((cent[:, 2] - z0) / zs_), 0, nz - 1)
        ok &= np.isin((ix * nz + iz).astype(np.int64),
                      np.asarray(spec.zones))
    if spec.min_points is not None:
        ok &= np.asarray(target.n_points) >= int(spec.min_points)
    if spec.min_obs is not None and has_obs:
        ok &= np.asarray(target.obs_count) >= int(spec.min_obs)
    if spec.since is not None and has_obs:
        ok &= np.asarray(target.last_seen) >= int(spec.since)
    if spec.aabb is not None:
        lo, hi = (np.asarray(x, np.float32) for x in spec.aabb)
        ok &= ((cent >= lo) & (cent <= hi)).all(-1)
    score = np.zeros(act.shape, np.float32)
    if spec.embed is not None:
        score = np.asarray(target.embed, np.float32) @ \
            np.asarray(spec.embed, np.float32)
        if spec.sem_weight is not None:
            score = score * np.float32(spec.sem_weight)
    d = None
    if spec.near is not None:
        c, r = spec.near
        d = np.linalg.norm(cent - np.asarray(c, np.float32), axis=-1)
        ok &= d <= np.float32(r)
        if spec.prox_weight is not None:
            score = score + np.float32(spec.prox_weight) / (1.0 + d)
    return np.where(ok, score, -np.inf).astype(np.float32)


def _check_against_oracle(res: QueryResult, oracle: np.ndarray, k: int,
                          ids: np.ndarray, slots_are_oids: bool = False):
    """res must be exactly the oracle's masked top-k (membership checked on
    oids; scores allclose; padded ranks fully masked)."""
    oids = np.asarray(res.oids)
    scores = np.asarray(res.scores)
    slots = np.asarray(res.slots)
    n_pass = int(np.isfinite(oracle).sum())
    nv = min(k, n_pass)
    # exactly nv live ranks, then fully-masked padding
    assert (slots[:nv] >= 0).all() and (oids[:nv] > 0).all()
    assert (slots[nv:] == -1).all(), "stale slot id surfaced in padding"
    assert (oids[nv:] == 0).all(), "stale object id surfaced in padding"
    assert np.isneginf(scores[nv:]).all()
    # scores at every live rank match the oracle's sorted top-k
    want = np.sort(oracle[np.isfinite(oracle)])[::-1][:nv]
    np.testing.assert_allclose(scores[:nv], want, rtol=1e-5, atol=1e-6)
    # membership: bit-exact on oids when the k-boundary is unambiguous
    fin = np.sort(oracle[np.isfinite(oracle)])[::-1]
    unambiguous = nv == 0 or len(fin) == nv \
        or fin[nv - 1] - fin[nv] > 1e-5
    if unambiguous and nv:
        thresh = fin[nv - 1]
        want_oids = set(ids[np.where(oracle >= thresh)[0]].tolist())
        assert set(oids[:nv].tolist()) == want_oids


def _rand_spec(rng, st, k) -> Query:
    """Random predicate combination (dynamic values drawn from the store so
    predicates pass for a non-trivial subset)."""
    kw = {}
    if rng.random() < 0.8:
        kw["embed"] = st.embed[int(rng.integers(st.ids.shape[0]))]
        if rng.random() < 0.3:
            kw["sem_weight"] = jnp.asarray(rng.uniform(0.5, 2.0),
                                           jnp.float32)
    if rng.random() < 0.5:
        c = st.centroid[int(rng.integers(st.ids.shape[0]))]
        kw["near"] = (c, jnp.asarray(rng.uniform(1.0, 6.0), jnp.float32))
        if rng.random() < 0.5:
            kw["prox_weight"] = jnp.asarray(rng.uniform(0.1, 1.0),
                                            jnp.float32)
    if rng.random() < 0.3:
        kw["aabb"] = (jnp.asarray([-2.0, 0.0, -2.0]),
                      jnp.asarray([3.0, 2.0, 3.0]))
    if rng.random() < 0.4:
        kw["labels"] = tuple(int(x) for x in rng.choice(20, 8, replace=False))
    if rng.random() < 0.3:
        kw["min_points"] = jnp.asarray(int(rng.integers(1, P)), jnp.int32)
    if rng.random() < 0.3:
        kw["min_obs"] = jnp.asarray(int(rng.integers(0, 5)), jnp.int32)
    if rng.random() < 0.2:
        kw["since"] = jnp.asarray(0, jnp.int32)
    if rng.random() < 0.25:
        g = ZoneGrid.for_room(ROOM, nx=2, nz=2)
        kw["zones"] = tuple(int(z) for z in
                            rng.choice(4, int(rng.integers(1, 4)),
                                       replace=False))
        kw["grid"] = Query.grid_of(g)
    if not kw:
        kw["embed"] = st.embed[0]
    return Query(k=k, **kw)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,k,n", [(0, 5, 40), (1, 8, 40), (2, 3, 25),
                                      (3, 12, 30)])
def test_engine_matches_oracle_deterministic(seed, k, n):
    """Always-run oracle sweep over random predicate combos × 3 targets."""
    rng = np.random.default_rng(seed)
    st = _store(n, seed)
    lm = _local_map(n, seed)
    zoned, zst = _zoned(n, seed)
    for trial in range(6):
        spec = _rand_spec(rng, st, k)
        # ObjectStore
        res = execute_query(st, spec)
        _check_against_oracle(res, _np_scores(spec, st, has_obs=True), k,
                              np.asarray(st.ids))
        # LocalMap (obs/recency vacuous)
        res = execute_query(lm, spec)
        _check_against_oracle(res, _np_scores(spec, lm, has_obs=False), k,
                              np.asarray(lm.ids))
        # ZoneShardedStore (oracle over the mirrored flat store)
        res = compile_query(spec, zoned)(zoned)
        _check_against_oracle(res, _np_scores(spec, zst, has_obs=True), k,
                              np.asarray(zst.ids))


def test_engine_matches_oracle_property():
    hyp = pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings, strategies as hst

    targets = {}          # cache stores across examples (jit reuse)

    def _get(n, seed):
        if (n, seed) not in targets:
            targets[(n, seed)] = (_store(n, seed), _local_map(n, seed),
                                  _zoned(n, seed))
        return targets[(n, seed)]

    @settings(max_examples=25, deadline=None)
    @given(hst.integers(0, 3), hst.integers(0, 10**6),
           hst.sampled_from([8, 33]), hst.integers(1, 12))
    def prop(seed, spec_seed, n, k):
        st, lm, (zoned, zst) = _get(n, seed)
        spec = _rand_spec(np.random.default_rng(spec_seed), st, k)
        res = execute_query(st, spec)
        _check_against_oracle(res, _np_scores(spec, st, has_obs=True), k,
                              np.asarray(st.ids))
        res = execute_query(lm, spec)
        _check_against_oracle(res, _np_scores(spec, lm, has_obs=False), k,
                              np.asarray(lm.ids))
        res = compile_query(spec, zoned)(zoned)
        _check_against_oracle(res, _np_scores(spec, zst, has_obs=True), k,
                              np.asarray(zst.ids))

    prop()


# ---------------------------------------------------------------------------
def test_padded_ranks_masked_regression():
    """k > matching-object count: padded ranks are score=-inf, oid=0,
    slot=-1 — the seed surfaced stale slot ids there."""
    st = _store(3, 0, cap=16)
    res = execute_query(st, Query(embed=st.embed[0], k=8))
    assert (np.asarray(res.slots)[3:] == -1).all()
    assert (np.asarray(res.oids)[3:] == 0).all()
    assert np.isneginf(np.asarray(res.scores)[3:]).all()
    # the live prefix is intact
    assert (np.asarray(res.slots)[:3] >= 0).all()
    assert (np.asarray(res.oids)[:3] > 0).all()
    # k beyond capacity also pads instead of erroring
    res = execute_query(st, Query(embed=st.embed[0], k=24))
    assert res.slots.shape == (24,) and (np.asarray(res.slots)[3:] == -1).all()
    # and an all-predicates-fail query is fully masked
    res = execute_query(st, Query(embed=st.embed[0], labels=(999,), k=4))
    assert (np.asarray(res.slots) == -1).all()
    assert (np.asarray(res.oids) == 0).all()


def test_legacy_wrappers_deprecated_and_equivalent():
    from repro.core.query import (batched_query_server, query_local,
                                  query_server)
    st = _store(30, 1)
    lm = _local_map(30, 1)
    qe = st.embed[4]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r1 = query_server(st, qe, k=5)
        r2 = query_local(lm, qe, k=5)
        r3 = batched_query_server(st, jnp.stack([qe, st.embed[7]]), k=5)
    assert sum(issubclass(x.category, DeprecationWarning) for x in w) == 3
    e1 = execute_query(st, Query(embed=qe, k=5))
    for a, b in zip(r1, e1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    e2 = execute_query(lm, Query(embed=qe, k=5))
    np.testing.assert_array_equal(np.asarray(r2.slots), np.asarray(e2.slots))
    np.testing.assert_array_equal(np.asarray(r3.slots[0]),
                                  np.asarray(r1.slots))


def test_pallas_path_matches_jnp():
    st = _store(40, 2)
    specs = [
        Query(embed=st.embed[3], k=6),
        Query(embed=st.embed[3], near=(st.centroid[3], jnp.asarray(4.0)),
              prox_weight=jnp.asarray(0.3), labels=tuple(range(12)),
              min_points=jnp.asarray(2), k=6),
    ]
    for spec in specs:
        rj = execute_query(st, spec)
        rp = execute_query(st, spec, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(rj.slots),
                                      np.asarray(rp.slots))
        np.testing.assert_array_equal(np.asarray(rj.oids),
                                      np.asarray(rp.oids))
        valid = np.asarray(rj.slots) >= 0
        np.testing.assert_allclose(np.asarray(rj.scores)[valid],
                                   np.asarray(rp.scores)[valid], rtol=1e-5)
        assert np.isneginf(np.asarray(rp.scores)[~valid]).all()


def test_stacked_batch_equals_singles():
    st = _store(40, 3)
    specs = [Query(embed=st.embed[i],
                   near=(st.centroid[i], jnp.asarray(5.0)), k=4)
             for i in range(6)]
    batched = stack_queries(specs, pad_to=8)
    rb = execute_query(st, batched)
    assert rb.slots.shape == (8, 4)
    for i, s in enumerate(specs):
        ri = execute_query(st, s)
        np.testing.assert_array_equal(np.asarray(rb.slots[i]),
                                      np.asarray(ri.slots))
        np.testing.assert_allclose(np.asarray(rb.scores[i]),
                                   np.asarray(ri.scores), rtol=1e-6)
    with pytest.raises(ValueError):
        stack_queries([Query(embed=st.embed[0], k=3),
                       Query(embed=st.embed[1], k=4)])


def _eager_stack(specs, pad_to):
    """The reference: one eager ``jnp.stack`` per leaf over the specs,
    padded by repeating the first."""
    specs = specs + [specs[0]] * (max(pad_to or 0, len(specs)) - len(specs))
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x)
                                               for x in xs]), *specs)


_LEAF_KINDS = {
    # each builds spec i of a batch from leaves of one kind
    "device": lambda st, i: Query(embed=st.embed[i], k=4),
    "numpy": lambda st, i: Query(embed=np.asarray(st.embed[i], np.float64),
                                 min_points=np.int64(2 + i % 3), k=4),
    "python": lambda st, i: Query(embed=st.embed[i], sem_weight=1.0 + i,
                                  min_obs=i % 2, since=0, k=4),
    "near": lambda st, i: Query(embed=st.embed[i],
                                near=(st.centroid[i], 3.0 + i),
                                prox_weight=jnp.asarray(0.25), k=4),
    "thresholds": lambda st, i: Query(
        embed=st.embed[i], labels=(0, 1, 2, 3), min_points=jnp.asarray(i),
        min_obs=jnp.asarray(1), since=jnp.asarray(i % 2),
        aabb=(np.full(3, -3.0, np.float32), np.full(3, 3.0, np.float32)),
        k=4),
}


@pytest.mark.parametrize("pad_to", [8, 5, None], ids=["pad-above-q",
                                                      "pad-at-q", "no-pad"])
@pytest.mark.parametrize("kind", sorted(_LEAF_KINDS))
def test_compiled_stack_equals_eager_stack(kind, pad_to):
    """``stack_queries`` (one compiled dispatch) gives the eager stack's
    batched spec leaf for leaf: values, dtype, weak type and shape, with
    the same static plan and ``batched`` set; a batch that needs no new
    shape reuses the executable."""
    from repro.core.query import _stack
    st = _store(40, 7)
    specs = [_LEAF_KINDS[kind](st, i) for i in range(5)]
    got = stack_queries(specs, pad_to=pad_to)
    want = _eager_stack(specs, pad_to)
    assert got.batched and not want.batched
    assert got.tree_flatten()[1] == \
        replace(want, batched=True).tree_flatten()[1]
    assert jax.tree.structure(got) == \
        jax.tree.structure(replace(want, batched=True))
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl) > 0
    for g, w in zip(gl, wl):
        assert g.shape == w.shape and g.shape[0] == (pad_to or 5)
        assert g.dtype == w.dtype and g.weak_type == w.weak_type
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
    n = _stack._cache_size()
    stack_queries([_LEAF_KINDS[kind](st, 5 + i) for i in range(3)],
                  pad_to=pad_to or 5)
    assert _stack._cache_size() == n


@pytest.mark.parametrize("case", ["empty", "batched", "all-static",
                                  "mismatched-plan"])
def test_stack_queries_refusals(case):
    st = _store(40, 3)
    specs = {
        "empty": [],
        "batched": [Query(embed=st.embed[:2], batched=True, k=3)],
        "all-static": [Query(labels=(1,), k=3), Query(labels=(1,), k=3)],
        "mismatched-plan": [Query(embed=st.embed[0], k=3),
                            Query(embed=st.embed[1], k=4)],
    }[case]
    with pytest.raises(ValueError):
        stack_queries(specs, pad_to=4)


def test_zone_pruning_before_dispatch():
    grid = ZoneGrid.for_room(ROOM, nx=2, nz=2)
    zoned, zst = _zoned(40, 4, grid)
    spec = Query(embed=zst.embed[0], zones=(1, 2),
                 grid=Query.grid_of(grid), k=5)
    plan = compile_query(spec, zoned)
    assert plan.shards == (1, 2)          # pruned before dispatch
    res = plan(zoned)
    _check_against_oracle(res, _np_scores(spec, zst, has_obs=True), 5,
                          np.asarray(zst.ids))
    # near-predicate pruning: only shards overlapping the circle run
    spec = Query(embed=zst.embed[0],
                 near=(jnp.asarray([-3.0, 1.0, -3.0]), jnp.asarray(1.0)),
                 k=5)
    plan = compile_query(spec, zoned)
    assert len(plan.shards) < grid.n_zones
    _check_against_oracle(plan(zoned), _np_scores(spec, zst, has_obs=True),
                          5, np.asarray(zst.ids))


def test_serving_step_fn_carries_query_specs():
    from repro.serving.batching import BatchScheduler, make_query_step_fn
    st = _store(30, 5)
    step_fn = make_query_step_fn(lambda: st, k=4, pad_to=4)
    sched = BatchScheduler(batch_size=4, step_fn=step_fn)
    spec = Query(embed=st.embed[2], near=(st.centroid[2], jnp.asarray(3.0)),
                 k=4)
    r_spec = sched.submit(spec)
    r_legacy = sched.submit(st.embed[9])          # raw embedding payload
    done = sched.drain()
    res = done[r_spec]
    assert isinstance(res, QueryResult)
    want = execute_query(st, spec)
    np.testing.assert_array_equal(res.slots, np.asarray(want.slots))
    oid, score = done[r_legacy]
    want = execute_query(st, Query(embed=st.embed[9], k=4))
    assert oid == int(want.oids[0])
    assert score == pytest.approx(float(want.scores[0]), rel=1e-6)


def test_compiled_plan_reruns_without_structure_change():
    """A compiled plan re-executes with new dynamic values (radius sweep,
    new embedding) — same structure, same executable."""
    st = _store(30, 6)
    spec = Query(embed=st.embed[1], near=(st.centroid[1], jnp.asarray(2.0)),
                 k=5)
    plan = compile_query(spec, st)
    r1 = plan(st)
    spec2 = Query(embed=st.embed[8], near=(st.centroid[8], jnp.asarray(5.0)),
                  k=5)
    r2 = plan(st, spec2)
    _check_against_oracle(r2, _np_scores(spec2, st, has_obs=True), 5,
                          np.asarray(st.ids))
    assert not np.array_equal(np.asarray(r1.slots), np.asarray(r2.slots)) \
        or True          # values may coincide; the oracle check is the test
