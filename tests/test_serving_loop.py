"""Async pipelined serving loop: double-buffer consistency, donation
byte-identity, and sync-vs-overlapped output equality.

The overlap is only legal because it is UNOBSERVABLE: every test here pins
some facet of that — a query racing a donated in-place ingest must see
exactly the pre- or post-tick snapshot (never a torn mix), donated jits
must produce byte-identical outputs to their copying twins, and the whole
loop (and the scenario engine under ``async_loop=True``) must replay
bit-identically against the synchronous schedule.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.knobs import Knobs
from repro.core.query import Query, execute_query
from repro.core.store import (SnapshotStore, copy_store, synthetic_store)
from repro.serving.loadgen import LoadGenerator, LoadSpec
from repro.serving.loop import (IngestStream, ServingLoop, apply_delta,
                                _apply_delta_donated, _apply_delta2_donated)
from repro.server.fleet import FleetServer
from repro.server.zones import ZoneGrid

E, P, CAP, NLIVE = 32, 16, 128, 96

KN = Knobs(server_capacity=CAP, client_capacity=64,
           max_object_points_server=P, max_object_points_client=8,
           min_obs_before_sync=1)


def _store(seed=1):
    return synthetic_store(NLIVE, CAP, E, P, seed=seed)


def _stream(n_ticks=6, seed=3, **kw):
    kw.setdefault("churn", 24)
    return IngestStream(n_ticks=n_ticks, n_live=NLIVE, embed_dim=E,
                        max_points=P, seed=seed, **kw)


def _oracle_topk(store, q, k):
    """Numpy flat-sweep oracle over a host snapshot: active slots only,
    cosine score, descending."""
    act = np.asarray(store.active)
    sim = np.asarray(store.embed) @ np.asarray(q)
    sim[~act] = -np.inf
    order = np.argsort(-sim)[:k]
    return np.asarray(store.ids)[order], sim[order]


def _stores_equal(a, b):
    return all(
        (x is None and y is None)
        or np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# double-buffer consistency: a query racing the donated ingest sees a
# consistent snapshot
# ---------------------------------------------------------------------------
def test_mid_ingest_query_is_exactly_pre_tick_snapshot():
    snap = SnapshotStore.of(_store())
    stream = _stream()
    d = stream.delta_at(0)
    pre_host = jax.tree.map(np.asarray, snap.front)       # pre-tick oracle
    post = apply_delta(copy_store(snap.front), d)         # post-tick oracle
    post_host = jax.tree.map(np.asarray, post)

    # aim the query at a slot this delta re-embeds, so pre and post top-k
    # actually differ — a torn read could not pass both arms below
    slot = int(np.asarray(d.slots)[np.argmin(np.asarray(d.tomb))])
    q = np.asarray(d.embed)[np.argmin(np.asarray(d.tomb))]
    pre_ids, pre_sc = _oracle_topk(pre_host, q, 5)
    post_ids, post_sc = _oracle_topk(post_host, q, 5)
    assert int(post_host.ids[slot]) == int(post_ids[0])
    assert not np.array_equal(pre_sc, post_sc)

    # in-flight donated ingest: the back buffer is being overwritten NOW
    back = snap.take_back()
    new = _apply_delta_donated(back, d)
    mid = execute_query(snap.front, Query(embed=jnp.asarray(q), k=5))
    assert np.array_equal(np.asarray(mid.oids), pre_ids)
    np.testing.assert_allclose(np.asarray(mid.scores), pre_sc, atol=1e-5)

    snap.publish(new, pending=d)
    after = execute_query(snap.front, Query(embed=jnp.asarray(q), k=5))
    assert np.array_equal(np.asarray(after.oids), post_ids)
    np.testing.assert_allclose(np.asarray(after.scores), post_sc,
                               atol=1e-5)


def test_tombstone_during_query_pre_or_post_never_mixed():
    snap = SnapshotStore.of(_store())
    # hand-built delta: tombstone the store's best match for q
    q = np.asarray(snap.front.embed[7])
    pre_host = jax.tree.map(np.asarray, snap.front)
    pre_ids, _ = _oracle_topk(pre_host, q, 3)
    victim_slot = 7
    assert int(pre_host.ids[victim_slot]) == int(pre_ids[0])
    U = _stream().delta_at(0).slots.shape[0]
    d = _stream().delta_at(0)._replace(
        slots=jnp.zeros((U,), jnp.int32).at[0].set(victim_slot),
        tomb=jnp.zeros((U,), bool).at[0].set(True),
        valid=jnp.zeros((U,), bool).at[0].set(True))

    back = snap.take_back()
    new = _apply_delta_donated(back, d)
    mid = execute_query(snap.front, Query(embed=jnp.asarray(q), k=3))
    # mid-removal: the victim is still the top hit of the published snap
    assert int(np.asarray(mid.oids)[0]) == int(pre_ids[0])

    snap.publish(new, pending=d)
    post = execute_query(snap.front, Query(embed=jnp.asarray(q), k=3))
    post_host = jax.tree.map(np.asarray, snap.front)
    post_ids, _ = _oracle_topk(post_host, q, 3)
    assert int(pre_ids[0]) not in np.asarray(post.oids)
    assert np.array_equal(np.asarray(post.oids), post_ids)


def test_snapshot_store_protocol_guards():
    snap = SnapshotStore.of(_store())
    b = snap.take_back()
    with pytest.raises(AssertionError):
        snap.take_back()
    snap.publish(b)
    assert snap.version == 1
    with pytest.raises(AssertionError):
        snap.publish(b)


# ---------------------------------------------------------------------------
# donation byte-identity: donated jits are scheduling-only changes
# ---------------------------------------------------------------------------
def test_donated_ingest_chain_matches_copying_chain():
    stream = _stream(n_ticks=5)
    ref = _store()
    for t in range(5):
        ref = apply_delta(ref, stream.delta_at(t))
    ref = jax.tree.map(np.asarray, ref)

    # double-buffered donated chain with the pending-delta catch-up: the
    # two-tick-old buffer replays (pending, current) each tick
    snap = SnapshotStore.of(_store())
    for t in range(5):
        d = stream.delta_at(t)
        back = snap.take_back()
        new = _apply_delta_donated(back, d) if snap.pending is None \
            else _apply_delta2_donated(back, snap.pending, d)
        snap.publish(new, pending=d)
    assert _stores_equal(ref, jax.tree.map(np.asarray, snap.front))


def test_collect_donation_byte_identity():
    from repro.server.session import SessionManager
    store = _store()
    sub = np.ones((4,), bool)
    a = SessionManager(knobs=KN, n_clients=4, capacity=CAP, budget=16,
                      subscribed=sub.copy())
    b = SessionManager(knobs=KN, n_clients=4, capacity=CAP, budget=16,
                      donate=True, subscribed=sub.copy())
    for tick in range(3):
        pa = a.collect(store)
        pb = b.collect_finish(b.collect_start(store))
        assert np.array_equal(pa.nbytes, pb.nbytes)
        assert np.array_equal(pa.counts, pb.counts)
        assert np.array_equal(np.asarray(pa.batch.oid),
                              np.asarray(pb.batch.oid))
        assert np.array_equal(np.asarray(pa.batch.valid),
                              np.asarray(pb.batch.valid))
    assert np.array_equal(np.asarray(a.sync.synced_version),
                          np.asarray(b.sync.synced_version))


def test_device_client_donated_ingest_identity():
    from repro.core.runtime import CloudService, DeviceClient
    from repro.core import MappingServer
    from repro.data.scenes import make_scene, scene_stream
    from repro.perception.embedder import OracleEmbedder
    kn = Knobs(server_capacity=CAP, client_capacity=64,
               max_object_points_server=64, max_object_points_client=16,
               max_detections_per_frame=16, min_obs_before_sync=1)
    scene = make_scene(n_objects=10, seed=3)
    classes = {o.oid: o.class_id for o in scene.objects}
    srv = MappingServer(knobs=kn, embedder=OracleEmbedder(embed_dim=E),
                        mode="semanticxr")
    key = jax.random.key(0)
    for i, fr in enumerate(scene_stream(scene, n_frames=12,
                                        keyframe_interval=4, h=60, w=80)):
        srv.process_frame(fr, classes, jax.random.fold_in(key, i))

    out = []
    for donate in (False, True):
        cloud = CloudService(knobs=kn, store_ref=srv)
        dev = DeviceClient(knobs=kn, embed_dim=E, donate=donate)
        pkt = cloud.update_tick(network_up=True)
        dev.ingest(pkt, user_pos=jnp.zeros(3))
        out.append(jax.tree.map(np.asarray, dev.local))
    assert _stores_equal(out[0], out[1])


def test_mapping_server_donated_ingest_identity():
    from repro.core import MappingServer
    from repro.data.scenes import make_scene, scene_stream
    from repro.perception.embedder import OracleEmbedder
    kn = Knobs(server_capacity=CAP, client_capacity=64,
               max_object_points_server=64, max_object_points_client=16,
               max_detections_per_frame=16, min_obs_before_sync=1)
    scene = make_scene(n_objects=8, seed=5)
    classes = {o.oid: o.class_id for o in scene.objects}
    stores = []
    for donate in (False, True):
        srv = MappingServer(knobs=kn, embedder=OracleEmbedder(embed_dim=E),
                            mode="semanticxr", donate=donate)
        key = jax.random.key(0)
        for i, fr in enumerate(scene_stream(scene, n_frames=10,
                                            keyframe_interval=4,
                                            h=60, w=80)):
            srv.process_frame(fr, classes, jax.random.fold_in(key, i))
        stores.append(jax.tree.map(np.asarray, srv.store))
    assert _stores_equal(stores[0], stores[1])


# ---------------------------------------------------------------------------
# whole-loop equality: overlapped schedule is unobservable end to end
# ---------------------------------------------------------------------------
def _loop(overlap, n_ticks=10, C=6):
    store = _store()
    srv = FleetServer(knobs=KN, embed_dim=E, n_clients=C,
                      grid=ZoneGrid.for_room(16.0, 2, 2), budget=16,
                      donate=overlap)
    lg = LoadGenerator(LoadSpec(n_clients=C, n_ticks=n_ticks, base_hz=3.0,
                                burst_hz=30.0, burst_prob=0.1),
                       embed_dim=E)
    ing = _stream(n_ticks=n_ticks)
    snap = SnapshotStore.of(store) if overlap \
        else SnapshotStore(front=store)
    for c in range(C):
        srv.join(c, lg.pose_at(c, 0), 6.0)
    loop = ServingLoop(server=srv, store=snap, ingest=ing, loadgen=lg,
                       overlap=overlap, batch_size=8,
                       max_batches_per_tick=2)
    stats = loop.run(n_ticks)
    return loop, stats


def test_serving_loop_sync_vs_overlapped_byte_identical():
    a, sa = _loop(False)
    b, sb = _loop(True)
    assert sa["n_queries_served"] == sb["n_queries_served"] > 0
    assert sa["sent_bytes_total"] == sb["sent_bytes_total"] > 0
    assert set(a.results) == set(b.results)
    for rid in a.results:
        assert np.array_equal(a.results[rid].oids, b.results[rid].oids)
        assert np.array_equal(a.results[rid].scores, b.results[rid].scores)
    assert _stores_equal(jax.tree.map(np.asarray, a.store.front),
                         jax.tree.map(np.asarray, b.store.front))


def test_fleet_tick_overlap_byte_identity():
    """server.tick(overlap=True) must emit byte-identical packets to the
    sequential per-zone path, across refreshes and pose churn."""
    def run(overlap):
        rng = np.random.default_rng(0)
        store = _store()
        srv = FleetServer(knobs=KN, embed_dim=E, n_clients=5,
                          grid=ZoneGrid.for_room(16.0, 2, 2), budget=16,
                          donate=overlap)
        for c in range(5):
            srv.join(c, rng.uniform(-6, 6, 3).astype(np.float32), 7.0)
        stream = _stream(n_ticks=4, seed=9)
        out = []
        deliverable = np.ones((5,), bool)
        for t in range(4):
            store = apply_delta(store, stream.delta_at(t))
            srv.refresh(store)
            for z, pkt in srv.tick(deliverable, tick=t, overlap=overlap):
                out.append((z, np.asarray(pkt.nbytes).copy(),
                            np.asarray(pkt.batch.oid).copy(),
                            np.asarray(pkt.seqs).copy()))
        return out

    seq, ovl = run(False), run(True)
    assert len(seq) == len(ovl) > 0
    for (za, na, oa, sa), (zb, nb, ob, sb) in zip(seq, ovl):
        assert za == zb
        assert np.array_equal(na, nb)
        assert np.array_equal(oa, ob)
        assert np.array_equal(sa, sb)


def test_engine_async_loop_replay_bit_identical():
    from repro.sim import churn_scenario, run_scenario
    sc = churn_scenario(seed=11, n_objects=12, n_ticks=12, n_clients=2,
                        remove_frac=0.25, drain_ticks=4)
    a = run_scenario(sc)
    b = run_scenario(sc, async_loop=True)
    assert a.equals(b), f"drift in fields: {a.diff(b)}"


# ---------------------------------------------------------------------------
# load generator: seeded, open-loop, deterministic
# ---------------------------------------------------------------------------
def test_loadgen_deterministic_and_open_loop():
    spec = LoadSpec(n_clients=16, n_ticks=40, base_hz=1.0, burst_hz=20.0,
                    burst_prob=0.05, seed=4)
    a, b = LoadGenerator(spec, embed_dim=E), LoadGenerator(spec,
                                                           embed_dim=E)
    assert a.n_arrivals == b.n_arrivals > 0
    for ta, tb in zip(a.arrivals, b.arrivals):
        assert len(ta) == len(tb)
        for (ca, qa), (cb, qb) in zip(ta, tb):
            assert ca == cb
            assert np.array_equal(np.asarray(qa.embed),
                                  np.asarray(qb.embed))
            assert np.array_equal(np.asarray(qa.near[0]),
                                  np.asarray(qb.near[0]))
    # bursty: some tick carries >1 arrival; open loop: schedule exists
    # regardless of any server serving it
    assert max(len(t) for t in a.arrivals) > 1
    # poses follow the cadence and the parametric track
    p0 = a.poses(0)
    assert p0.shape == (16, 3)
    np.testing.assert_allclose(p0[3], a.pose_at(3, 0), atol=1e-6)


def test_batched_pose_update_matches_per_client_path():
    """overlaps_batch == per-client overlaps, and FleetServer.set_poses
    leaves identical session state to C set_client_pose calls."""
    grid = ZoneGrid.for_room(16.0, 3, 2)
    rng = np.random.default_rng(2)
    poses = rng.uniform(-10, 10, size=(32, 3)).astype(np.float32)
    batch = grid.overlaps_batch(poses, 5.0)
    for c in range(32):
        assert np.array_equal(batch[c], grid.overlaps(poses[c], 5.0))

    def mk():
        srv = FleetServer(knobs=KN, embed_dim=E, n_clients=6,
                          grid=ZoneGrid.for_room(16.0, 2, 2), budget=16)
        for c in range(6):
            srv.join(c, poses[c], 6.0)
        return srv
    a, b = mk(), mk()
    for t in range(3):
        step = poses[t * 6:(t + 1) * 6] * (0.5 + 0.2 * t)
        for c in range(6):
            a.set_client_pose(c, step[c], 6.0)
        b.set_poses(step, 6.0)
        assert np.array_equal(a.subscribed, b.subscribed)
        for sa, sb in zip(a.sessions, b.sessions):
            assert sa.dirty == sb.dirty
            assert np.array_equal(sa.subscribed, sb.subscribed)
            assert np.array_equal(sa.user_pos, sb.user_pos)
            assert np.array_equal(np.asarray(sa.sync.synced_version),
                                  np.asarray(sb.sync.synced_version))
            assert np.array_equal(sa.next_seq, sb.next_seq)


def test_loadgen_latency_accounting():
    lg = LoadGenerator(LoadSpec(n_clients=2, n_ticks=4, seed=0),
                       embed_dim=E)
    lg.note_submit(0, 1.0)
    lg.note_served(0, 1.010)
    lg.note_resolved(0, 1.025)
    assert lg.wait_ms == [pytest.approx(10.0)]
    assert lg.e2e_ms == [pytest.approx(25.0)]
    rep = lg.record("test")
    assert rep["e2e_ms"]["p99"] == pytest.approx(25.0)


def test_donate_auto_policy_resolution():
    """donate=None resolves through the one backend-aware policy helper
    (kernels.ops.donate_default): OFF on CPU — where a donated dispatch
    blocks on the donated buffer's producer and serializes the overlap —
    ON for TPU/GPU.  Explicit True/False are untouched."""
    from repro.kernels.ops import donate_default
    from repro.server.session import SessionManager
    want = donate_default()
    assert want == (jax.default_backend() not in ("cpu",))
    sm = SessionManager(knobs=KN, n_clients=2, capacity=CAP, donate=None)
    assert sm.donate == want
    assert SessionManager(knobs=KN, n_clients=2, capacity=CAP,
                          donate=True).donate is True
    assert SessionManager(knobs=KN, n_clients=2, capacity=CAP,
                          donate=False).donate is False
    # FleetServer passes the auto policy through to every zone session
    srv = FleetServer(knobs=KN, embed_dim=E, n_clients=2,
                      grid=ZoneGrid.for_room(8.0, 2, 1), donate=None)
    assert all(s.donate == want for s in srv.sessions)
    # the engine's overlapped mode asks for auto (bug was donate=True
    # unconditionally: the async loop lost its overlap win on CPU)
    from repro.sim.engine import ScenarioEngine
    from repro.sim.scenario import (ClientSpec, GridSpec, NetTrace,
                                    PoseTrack, Scenario)
    sc = Scenario(seed=0, n_ticks=1, embed_dim=E, knobs=KN,
                  grid=GridSpec(room=8.0, nx=1, nz=1),
                  clients=(ClientSpec(cid=0, net=NetTrace(), 
                                      track=PoseTrack()),))
    eng = ScenarioEngine(sc, async_loop=True)
    assert all(s.donate == want for s in eng.server.sessions)
    eng2 = ScenarioEngine(sc, async_loop=False)
    assert all(s.donate is False for s in eng2.server.sessions)


def test_serving_loop_sharded_session_tier_byte_identity():
    """The sharded session tier threads through the serving loop's
    tick_start/tick_finish schedule unchanged: same per-tick sent bytes and
    identical fleet sync state as the single-device tier, in both the
    fenced and overlapped schedules."""
    def run(shards, overlap):
        store = _store()
        srv = FleetServer(knobs=KN, embed_dim=E, n_clients=6,
                          grid=ZoneGrid.for_room(16.0, 2, 2), budget=16,
                          n_session_shards=shards, donate=None)
        rng = np.random.default_rng(5)
        for c in range(6):
            srv.join(c, rng.uniform(-6, 6, 3).astype(np.float32), 6.0)
        snap = SnapshotStore.of(store) if overlap \
            else SnapshotStore(front=store)
        loop = ServingLoop(server=srv, store=snap, ingest=_stream(seed=11),
                           overlap=overlap)
        loop.run(6)
        return loop.sent_bytes, srv

    for overlap in (False, True):
        s1, srv1 = run(1, overlap)
        s3, srv3 = run(3, overlap)
        assert s1 == s3, (overlap, s1, s3)
        # per-zone sync state identical after reassembly
        for z, (a, b) in enumerate(zip(srv1.sessions, srv3.sessions)):
            va = np.asarray(a.sync.synced_version)
            vb = np.zeros_like(va)
            for s, part in enumerate(b.parts):
                if part is not None:
                    vb[b.roster.members[s]] = np.asarray(
                        part.sync.synced_version)
            np.testing.assert_array_equal(va, vb, err_msg=f"zone {z}")


# ---------------------------------------------------------------------------
# tracing the served tick: request ids, queue waits, backlog, owed rows
# ---------------------------------------------------------------------------
def _traced_loop(tracer, n_ticks=8, C=6):
    """The overlapped tiny loop with ``tracer`` installed (None: off);
    returns (loop, Request objects by rid, per-tick backlog, packets,
    per-tick (clock before, clock after, queued ``enqueued_at`` in heap
    order))."""
    from repro.obs.trace import set_tracer
    store = _store()
    srv = FleetServer(knobs=KN, embed_dim=E, n_clients=C,
                      grid=ZoneGrid.for_room(16.0, 2, 2), budget=4,
                      donate=True)
    lg = LoadGenerator(LoadSpec(n_clients=C, n_ticks=n_ticks, base_hz=6.0,
                                burst_hz=60.0, burst_prob=0.2, seed=2),
                       embed_dim=E)
    for c in range(C):
        srv.join(c, lg.pose_at(c, 0), 6.0)
    loop = ServingLoop(server=srv, store=SnapshotStore.of(store),
                       ingest=_stream(n_ticks=n_ticks), loadgen=lg,
                       overlap=True, batch_size=4, max_batches_per_tick=1)
    sched = loop.scheduler
    reqs, backlog, packets, queued = {}, [], [], []
    submit, query_tick = sched.submit, loop._query_tick
    finish = srv.tick_finish

    def submit_rec(payload, **kw):
        rid = submit(payload, **kw)
        reqs[rid] = next(r for r in sched.waiting if r.rid == rid)
        return rid

    def query_tick_rec(t):
        before = time.perf_counter()
        out = query_tick(t)
        queued.append((before, time.perf_counter(),
                       [r.enqueued_at for r in sched.waiting]))
        backlog.append(len(sched.waiting))
        return out

    def finish_rec(started):
        out = finish(started)
        packets.extend((z, np.asarray(p.nbytes).copy(),
                        np.asarray(p.batch.oid).copy(),
                        np.asarray(p.batch.valid).copy()) for z, p in out)
        return out

    sched.submit, loop._query_tick = submit_rec, query_tick_rec
    srv.tick_finish = finish_rec
    prev = set_tracer(tracer)
    try:
        loop.run(n_ticks)
    finally:
        set_tracer(prev)
    return loop, reqs, backlog, packets, queued


def _args(tracer, name):
    return [e[5] or {} for e in tracer.events if e[0] == name]


@pytest.fixture(scope="module")
def traced_run():
    from repro.obs import Tracer
    tr = Tracer()
    return (tr,) + _traced_loop(tr)


def test_each_query_in_one_batch_and_one_resolve(traced_run):
    tr, loop, reqs, _, _, _ = traced_run
    assert len(reqs) > 0 and set(loop.results) == set(reqs)
    for name in ("query.batch", "serving.resolve"):
        rids = [r for a in _args(tr, name) for r in a.get("rids", ())]
        assert sorted(rids) == sorted(reqs), name


def test_one_result_fetch_per_served_batch(traced_run):
    """Each ``query.batch`` that served rows is resolved by one
    ``host.fetch`` read of its whole result, inside ``serving.resolve``:
    ``rows`` counts the batch's real rows, ``ready`` says whether the read
    found the copy landed."""
    tr, loop, _, _, _, _ = traced_run
    batches = [a for a in _args(tr, "query.batch") if "rids" in a]
    fetches = [e for e in tr.events
               if e[0] == "host.fetch" and e[5].get("what") == "result"]
    assert len(fetches) == len(batches) > 0
    # batches resolve in dispatch order, a tick after it
    assert [e[5]["rows"] for e in fetches] == \
        [len(a["rids"]) for a in batches]
    assert sum(e[5]["rows"] for e in fetches) == len(loop.results)
    assert all(type(e[5]["ready"]) is bool for e in fetches)
    resolves = [(e[2], e[3]) for e in tr.events if e[0] == "serving.resolve"]
    for e in fetches:
        assert any(t0 <= e[2] and e[3] <= t1 for t0, t1 in resolves)


def _step_payloads(store, legacy: bool) -> list:
    """Ten payloads in two plan groups (legacy: ten raw embeddings, one)."""
    rng = np.random.default_rng(11)
    emb = rng.normal(size=(10, E)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
    if legacy:
        return [jnp.asarray(e) for e in emb]
    cent = np.asarray(store.centroid)
    return [Query(embed=jnp.asarray(e), k=5) if i % 3 else
            Query(embed=jnp.asarray(e),
                  near=(jnp.asarray(cent[i]), jnp.asarray(4.0)), k=5)
            for i, e in enumerate(emb)]


@pytest.mark.parametrize("legacy", [False, True], ids=["query", "legacy"])
def test_pending_rows_equal_blocking_rows(legacy):
    """``PendingResult`` rows of one step, resolved in any order and twice
    each, equal the blocking step's rows bit for bit: same values, dtypes,
    shapes and Python types."""
    from repro.serving.batching import (PendingResult, make_query_step_fn,
                                        resolve_results)
    store = _store()
    payloads = _step_payloads(store, legacy)
    want = make_query_step_fn(lambda: store, pad_to=8)(payloads)
    pending = make_query_step_fn(lambda: store, pad_to=8,
                                 block=False)(payloads)
    assert all(isinstance(p, PendingResult) for p in pending)
    order = np.random.default_rng(3).permutation(len(pending))
    for i in list(order) + list(order[::-1]):
        got, ref = pending[i].resolve(), want[i]
        if legacy:
            assert got == ref and type(got[0]) is int \
                and type(got[1]) is float
            continue
        for x, y in zip(got, ref):
            assert x.dtype == y.dtype and x.shape == y.shape == (5,)
            assert x.tobytes() == y.tobytes()
    assert pending[0].resolve() is pending[0].resolve()
    done = resolve_results({i: p for i, p in enumerate(pending)})
    for i, ref in enumerate(want):
        got = done[i]
        assert got == ref if legacy else all(
            x.tobytes() == y.tobytes() for x, y in zip(got, ref))


@pytest.mark.parametrize("block", [True, False], ids=["blocking", "pending"])
def test_scheduler_batches_stack_in_one_compile(block):
    """Twenty scheduler steps of fills 1-16 at ``pad_to`` 16 compile the
    stack once; each ``query.batch`` span says how many groups the step
    dispatched (one plan: 1; a step of two plans: 2); every row is byte
    for byte the per-spec ``execute_query`` answer."""
    from repro.core.query import _stack
    from repro.obs import Tracer
    from repro.obs.trace import set_tracer
    from repro.serving.batching import (BatchScheduler, PendingResult,
                                        make_query_step_fn)
    store = _store()
    cent = np.asarray(store.centroid)
    rng = np.random.default_rng(5)

    def spec(i, near=True):
        e = rng.normal(size=E).astype(np.float32)
        e /= np.linalg.norm(e)
        if not near:
            return Query(embed=jnp.asarray(e), k=6)
        return Query(embed=jnp.asarray(e),
                     near=(jnp.asarray(cent[i % NLIVE]),
                           jnp.asarray(3.0 + i % 4, jnp.float32)),
                     min_points=jnp.asarray(2), k=6)

    sched = BatchScheduler(batch_size=16, step_fn=make_query_step_fn(
        lambda: store, pad_to=16, block=block))
    fills = [1, 16] + list(rng.integers(1, 17, size=18))
    tr = Tracer()
    _stack.clear_cache()
    prev = set_tracer(tr)
    try:
        specs, served = {}, {}
        for f in fills:
            for i in range(int(f)):
                s_ = spec(len(specs))
                specs[sched.submit(s_)] = s_
            served.update(sched.step())
        assert _stack._cache_size() == 1
        mixed = [spec(0), spec(1, near=False), spec(2)]
        for s_ in mixed:
            specs[sched.submit(s_)] = s_
        served.update(sched.step())
    finally:
        set_tracer(prev)
    groups = [a["groups"] for a in _args(tr, "query.batch")]
    assert groups == [1] * len(fills) + [2]
    assert set(served) == set(specs)
    for rid, res in served.items():
        assert isinstance(res, PendingResult) != block
        got = res.resolve() if not block else res
        want = execute_query(store, specs[rid])
        for x, y in zip(got, want):
            y = np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape == (6,)
            assert x.tobytes() == y.tobytes()


def test_query_wait_is_step_start_minus_enqueue(traced_run):
    tr, _, reqs, _, _, _ = traced_run
    batches = [a for a in _args(tr, "query.batch") if "rids" in a]
    assert batches
    for a in batches:
        assert len(a["wait_ms"]) == len(a["rids"])
        for rid, w in zip(a["rids"], a["wait_ms"]):
            r = reqs[rid]
            assert w == (r.started_at - r.enqueued_at) * 1e3
    # one request per batch slot and one batch per tick: a queue forms
    assert max(w for a in batches for w in a["wait_ms"]) > 0


def test_query_backlog_matches_the_scheduler(traced_run):
    tr, _, _, backlog, _, _ = traced_run
    waiting = [a["waiting"] for a in _args(tr, "serving.query")]
    # the drain after the last tick steps the scheduler outside any tick
    assert waiting == backlog and max(waiting) > 0


def test_queued_ages_are_the_schedulers_requests(traced_run):
    """``waiting_ms`` of ``serving.query`` holds, in heap order, the age of
    every request still queued when the span closes (0.1 ms rounding)."""
    tr, _, _, _, _, queued = traced_run
    spans = _args(tr, "serving.query")
    assert len(spans) == len(queued)
    for a, (before, after, enq) in zip(spans, queued):
        assert len(a["waiting_ms"]) == a["waiting"] == len(enq)
        for age, t in zip(a["waiting_ms"], enq):
            assert (before - t) * 1e3 - 0.05 <= age <= (after - t) * 1e3 + 0.05
    assert max(max(a["waiting_ms"], default=0) for a in spans) > 0


def test_tracing_leaves_results_and_packets_unchanged(traced_run):
    _, on, _, _, pk_on, _ = traced_run
    off, _, _, pk_off, _ = _traced_loop(None)
    assert set(on.results) == set(off.results)
    for rid in on.results:
        assert np.array_equal(on.results[rid].oids, off.results[rid].oids)
        assert np.array_equal(on.results[rid].scores,
                              off.results[rid].scores)
    assert on.sent_bytes == off.sent_bytes > 0
    assert len(pk_on) == len(pk_off) > 0
    for a, b in zip(pk_on, pk_off):
        assert a[0] == b[0]
        for x, y in zip(a[1:], b[1:]):
            assert np.array_equal(x, y)


def _owed_oracle(store, synced, ever_sent, mask, min_obs):
    """Numpy count of the rows a collect must consider: live rows past the
    client's synced version and tombstones of rows it was ever sent."""
    act = np.asarray(store.active)
    ver = np.asarray(store.version)
    obs = np.asarray(store.obs_count)
    dele = np.asarray(store.deleted)
    newer = ver[None] > synced
    live = act[None] & (obs[None] >= min_obs[:, None]) & newer
    tomb = dele[None] & ever_sent & newer
    return ((live | tomb) & mask[:, None]).sum(axis=1)


@pytest.mark.parametrize("budget", [4, 128])
def test_rows_owed_counts_changed_rows_left_unshipped(budget):
    """``rows_owed`` of ``session.collect_finish`` is the numpy count of
    changed rows minus the rows shipped: positive while the budget binds,
    zero once it does not."""
    from repro.obs import Tracer
    from repro.obs.trace import set_tracer
    from repro.server.session import SessionManager
    store = _store()
    C = 5
    sub = np.array([True, True, False, True, True])
    sm = SessionManager(knobs=KN, n_clients=C, capacity=CAP, budget=budget,
                        subscribed=sub.copy())
    stream = _stream(n_ticks=4, seed=7)
    tr = Tracer()
    prev = set_tracer(tr)
    want = []
    try:
        for t in range(4):
            if t:
                store = apply_delta(store, stream.delta_at(t))
            synced = np.asarray(sm.sync.synced_version)
            ever = np.asarray(sm.sync.ever_sent)
            changed = _owed_oracle(store, synced, ever, sub, sm.min_obs)
            pkt = sm.collect_finish(sm.collect_start(store, now=t))
            shipped = np.asarray(pkt.counts)
            assert np.array_equal(shipped, np.minimum(changed, sm.budget))
            want.append(dict(rows_owed=int((changed - shipped).sum()),
                             rows_shipped=int(shipped.sum()),
                             clients=int(sub.sum()), issue_tick=t,
                             bytes=int(pkt.nbytes.sum()), zone=0))
    finally:
        set_tracer(prev)
    got = _args(tr, "session.collect_finish")
    assert got == want
    owed = [w["rows_owed"] for w in want]
    assert (owed[0] > 0) == (budget < NLIVE)
