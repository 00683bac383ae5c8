"""Multi-tenant fleet server: vmapped sync correctness, zone isolation,
convergence under interleaved ticks/outages/joins, and the smoke-scale
benchmark suite."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.knobs import Knobs
from repro.core.local_map import compute_priority
from repro.core.runtime import ClientSession, DeviceClient, NetworkModel
from repro.core.store import (release_tombstones, remove_objects,
                              synthetic_store)
from repro.core.updates import collect_updates, init_sync, update_nbytes
from repro.server import (FleetServer, SessionManager, ZoneGrid,
                          ZoneShardedStore)
from repro.sim import FleetSimulator

E = 32
KN = Knobs(server_capacity=64, client_capacity=64,
           max_object_points_server=64, max_object_points_client=16,
           min_obs_before_sync=1)


def synth_store(n, *, cap=64, P=64, seed=0, x_range=(-4, 4)):
    return synthetic_store(
        n, cap, E, P, seed=seed, n_labels=10,
        centroid_low=(x_range[0], 0.0, -4.0),
        centroid_high=(x_range[1], 2.0, 4.0))


def bump_versions(store, slots):
    """Mutate objects in-place: version advance (new geometry angle)."""
    slots = jnp.asarray(np.asarray(slots, np.int64))
    return store._replace(version=store.version.at[slots].add(1))


# ---------------------------------------------------------------------------
def test_fleet_collect_matches_single_client():
    """One vmapped dispatch for C clients == C single-client collect_updates
    calls: same object sets, same exact wire bytes, per client."""
    store = synth_store(30)
    C, budget = 5, 16
    rng = np.random.default_rng(1)
    poses = rng.uniform(-3, 3, size=(C, 3)).astype(np.float32)
    sm = SessionManager(knobs=KN, n_clients=C, capacity=KN.server_capacity,
                        budget=budget, user_pos=poses.copy())
    # desync some rows so clients differ: client c already has objects c..c+4
    synced = np.zeros((C, KN.server_capacity), np.int32)
    for c in range(C):
        synced[c, c:c + 5] = 1
    sm.sync = sm.sync._replace(synced_version=jnp.asarray(synced))

    pkt = sm.collect(store)
    for c in range(C):
        pri = np.asarray(compute_priority(
            store.embed, store.label, store.centroid,
            user_pos=jnp.asarray(poses[c]), knobs=KN))
        single, _ = collect_updates(
            store, init_sync(KN.server_capacity)._replace(
                synced_version=synced[c].copy()),
            KN, tick=0, priorities=pri, max_updates=budget)
        assert single.nbytes == int(pkt.nbytes[c])
        assert single.count == int(pkt.counts[c])
        got = set(np.asarray(pkt.batch.oid[c])[:pkt.counts[c]].tolist())
        assert got == {int(u.oid) for u in single.updates}
        # byte-for-byte payload equality: every field of every row matches
        # the single-client packet (match rows by oid — ordering may
        # differ only among equal priorities)
        cnt = int(pkt.counts[c])
        fleet_row = {int(o): i for i, o in
                     enumerate(np.asarray(pkt.batch.oid[c])[:cnt])}
        for u in single.updates:
            i = fleet_row[int(u.oid)]
            for field in ("embed", "label", "points", "n_points",
                          "centroid", "version"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(pkt.batch, field)[c, i]),
                    np.asarray(getattr(u, field)), err_msg=field)
    # budget-limited catch-up: later ticks drain the remainder, then the
    # fleet quiesces to zero bytes
    shipped = [set(np.asarray(pkt.batch.oid[c])[:pkt.counts[c]].tolist())
               for c in range(C)]
    for _ in range(5):
        nxt = sm.collect(store)
        if (nxt.counts == 0).all():
            break
        for c in range(C):
            shipped[c] |= set(
                np.asarray(nxt.batch.oid[c])[:nxt.counts[c]].tolist())
    pkt2 = sm.collect(store)
    assert (pkt2.nbytes == 0).all() and (pkt2.counts == 0).all()
    for c in range(C):                     # every changed object arrived
        expect = {int(o) for s, o in enumerate(np.asarray(store.ids)[:30])
                  if synced[c][s] < 1}
        assert shipped[c] == expect


def test_fleet_collect_honors_class_point_overrides():
    """Per-class point budgets (Knobs.class_point_overrides) apply inside
    the vmapped fleet gather exactly as in the single-client path: every
    row is clipped to its class budget and the fleet's wire-byte
    accounting matches update_nbytes row for row."""
    kn = Knobs(server_capacity=64, client_capacity=64,
               max_object_points_server=64, max_object_points_client=16,
               min_obs_before_sync=1,
               class_point_overrides=((0, 4), (1, 8), (2, 999)))
    store = synth_store(24, seed=13)
    C, budget = 3, 64
    rng = np.random.default_rng(2)
    poses = rng.uniform(-3, 3, size=(C, 3)).astype(np.float32)
    sm = SessionManager(knobs=kn, n_clients=C, capacity=kn.server_capacity,
                        budget=budget, user_pos=poses.copy())
    pkt = sm.collect(store)
    labels = np.asarray(store.label)
    n_src = np.asarray(store.n_points)
    assert (pkt.counts == 24).all()
    for c in range(C):
        cnt = int(pkt.counts[c])
        oids = np.asarray(pkt.batch.oid[c])[:cnt]
        npts = np.asarray(pkt.batch.n_points[c])[:cnt]
        slot = {int(np.asarray(store.ids)[s]): s for s in range(24)}
        expect_bytes = 0
        saw_override = 0
        for o, n in zip(oids, npts):
            s = slot[int(o)]
            cap = kn.client_points_for(int(labels[s]))
            cap = min(cap, kn.max_object_points_client)
            want = min(int(n_src[s]), cap)
            assert int(n) == want, f"oid {o} class {labels[s]}"
            expect_bytes += update_nbytes(E, want)
            saw_override += int(labels[s]) in (0, 1)
        assert saw_override > 0             # the override classes occurred
        assert int(pkt.nbytes[c]) == expect_bytes
        # byte-for-byte vs the single-client collector under the same knobs
        pri = np.asarray(compute_priority(
            store.embed, store.label, store.centroid,
            user_pos=jnp.asarray(poses[c]), knobs=kn))
        single, _ = collect_updates(
            store, init_sync(kn.server_capacity), kn, tick=0,
            priorities=pri, max_updates=budget)
        assert single.nbytes == int(pkt.nbytes[c])
        for u in single.updates:
            i = int(np.nonzero(oids == int(u.oid))[0][0])
            assert int(npts[i]) == int(u.n_points)
            np.testing.assert_array_equal(
                np.asarray(pkt.batch.points[c, i, :int(u.n_points)]),
                np.asarray(u.points[:int(u.n_points)]))


def test_fleet_sync_advances_only_when_deliverable():
    """A client in outage keeps its sync row; reconnection coalesces every
    missed change into one packet (flush_buffer semantics, fleet-wide)."""
    store = synth_store(10)
    sm = SessionManager(knobs=KN, n_clients=2, capacity=KN.server_capacity,
                        budget=16)
    p0 = sm.collect(store, deliverable=np.array([True, False]))
    assert p0.counts[0] == 10 and p0.counts[1] == 0
    store = bump_versions(store, [0, 1])
    p1 = sm.collect(store, deliverable=np.array([True, True]))
    assert p1.counts[0] == 2          # only the delta
    assert p1.counts[1] == 10         # full coalesced catch-up
    assert int(p1.nbytes[1]) > int(p1.nbytes[0])


def test_zone_isolation_exact_bytes():
    """Acceptance: a client whose pose stays in one zone receives NO bytes
    for objects mutated only in other zones — exact update_nbytes
    accounting."""
    grid = ZoneGrid.for_room(8.0, nx=2, nz=1)   # zone 0: x<0, zone 1: x>=0
    store = synth_store(20, seed=3)
    fs = FleetServer(knobs=KN, embed_dim=E, n_clients=2, grid=grid,
                     budget=32)
    fs.refresh(store)
    fs.join(0, np.array([-2.0, 1.5, 0.0]), 1.0)   # client 0: zone 0 only
    fs.join(1, np.array([2.0, 1.5, 0.0]), 1.0)    # client 1: zone 1 only
    assert fs.subscribed[0].tolist() == [True, False]
    assert fs.subscribed[1].tolist() == [False, True]
    both = np.array([True, True])
    fs.tick(both)                                  # initial sync

    # mutate ONLY zone-1 objects (centroid x >= 0)
    cents = np.asarray(store.centroid)
    act = np.asarray(store.active)
    z1_slots = np.nonzero(act & (cents[:, 0] >= 0))[0]
    assert len(z1_slots) > 0
    store = bump_versions(store, z1_slots)
    fs.refresh(store)
    packets = fs.tick(both)
    per = fs.per_client_nbytes(packets)
    assert per[0] == 0                             # zone-0 client: zero bytes
    # zone-1 client: exactly the mutated objects at exact wire size
    n_pts = np.asarray(store.n_points)[z1_slots]
    expect = sum(update_nbytes(E, min(int(n), KN.max_object_points_client))
                 for n in n_pts)
    assert per[1] == expect


def test_zone_slot_reuse_resets_sync():
    """A freed shard slot must not hide its next occupant behind the old
    occupant's synced version."""
    grid = ZoneGrid(origin=(-4.0, -4.0), zone_size=8.0, nx=1, nz=1)
    store = synth_store(3, seed=5)
    zoned = ZoneShardedStore(knobs=KN, embed_dim=E, grid=grid,
                             zone_capacity=4)
    fs = FleetServer(knobs=KN, embed_dim=E, n_clients=1, grid=grid,
                     budget=8, zoned=zoned)
    fs.refresh(store)
    fs.join(0, np.zeros(3), 1.0)
    fs.tick(np.array([True]))
    # retire object at slot 0, then add a NEW object with a LOWER version
    store = store._replace(active=store.active.at[0].set(False))
    fs.refresh(store)                               # frees the shard slot
    store = store._replace(
        active=store.active.at[0].set(True),
        ids=store.ids.at[0].set(99),
        version=store.version.at[0].set(1))         # version 1 <= synced 1
    fs.refresh(store)
    packets = fs.tick(np.array([True]))
    oids = set()
    for _, pkt in packets:
        p = pkt.packet_for(0)
        if p.count:
            oids |= {int(u.oid) for u in p.updates}
    assert 99 in oids


def test_quiesced_zones_skip_collect():
    """Once a zone's subscribers are fully synced, idle ticks dispatch
    nothing for it; a refresh with changes makes it collect again."""
    grid = ZoneGrid.for_room(8.0, nx=2, nz=1)
    store = synth_store(12, seed=9)
    fs = FleetServer(knobs=KN, embed_dim=E, n_clients=2, grid=grid,
                     budget=32)
    fs.refresh(store)
    fs.join(0, np.array([-2.0, 1.5, 0.0]), 1.0)
    fs.join(1, np.array([2.0, 1.5, 0.0]), 1.0)
    both = np.array([True, True])
    assert len(fs.tick(both)) == 2                 # initial catch-up
    assert len(fs.tick(both)) == 2                 # quiescing tick (0 bytes)
    assert fs.tick(both) == []                     # quiesced: no dispatches
    cents = np.asarray(store.centroid)
    z1 = np.nonzero(np.asarray(store.active) & (cents[:, 0] >= 0))[0]
    store = bump_versions(store, z1[:1])
    fs.refresh(store)
    ticked = fs.tick(both)
    assert [z for z, _ in ticked] == [1]           # only the dirty zone
    # zone-1's subscriber in outage: skipped this tick but still dirty
    assert fs.tick(np.array([True, False])) == []
    assert [z for z, _ in fs.tick(both)] == [1]    # quiescing tick
    assert fs.tick(both) == []


# ---------------------------------------------------------------------------
def _expected_visible(fs, min_obs):
    """Oracle: (oid -> version) of the server store restricted to a zone
    subscription, transient-filtered — what a synced client must hold."""
    out = {}
    for z, zone in enumerate(fs.zoned.zones):
        act = np.asarray(zone.active)
        obs = np.asarray(zone.obs_count)
        ids = np.asarray(zone.ids)
        ver = np.asarray(zone.version)
        for s in np.nonzero(act & (obs >= min_obs))[0]:
            out.setdefault(z, {})[int(ids[s])] = int(ver[s])
    return out


def test_multi_client_convergence_under_interleaving():
    """After an arbitrary interleaving of ticks, outages, joins, and store
    mutations, every client's local map converges to the server store
    restricted to its subscribed zones (settle ticks with the network up)."""
    rng = np.random.default_rng(11)
    grid = ZoneGrid.for_room(8.0, nx=2, nz=1)
    kn = Knobs(server_capacity=64, client_capacity=64,
               max_object_points_server=32, max_object_points_client=16,
               min_obs_before_sync=1)
    C = 4
    store = synth_store(12, P=32, seed=7)
    n_next = 12
    fs = FleetServer(knobs=kn, embed_dim=E, n_clients=C, grid=grid,
                     budget=16)
    # fixed per-client poses -> static zone subscriptions (no removals, no
    # zone moves in this scenario, so set equality is exact)
    poses = np.array([[-2.5, 1.5, 0.0], [2.5, 1.5, 0.0],
                      [-1.0, 1.5, 1.0], [1.5, 1.5, -1.0]], np.float32)
    sessions = [ClientSession(
        dev=DeviceClient(knobs=kn, embed_dim=E),
        net=NetworkModel(), knobs=kn, user_pos=jnp.asarray(poses[c]))
        for c in range(C)]
    joined = np.zeros(C, bool)
    fs.refresh(store)

    def run_tick(t, deliverable):
        packets = fs.tick(deliverable & joined)
        total = 0
        for c in range(C):
            if not joined[c]:
                continue
            for _, pkt in packets:
                sessions[c].step(t, pkt.packet_for(c))
            total += sum(int(pkt.nbytes[c]) for _, pkt in packets)
        return total

    fs.join(0, poses[0], 1.2)
    joined[0] = True
    for t in range(24):
        ev = rng.random()
        if ev < 0.3:                      # mutate some existing objects
            slots = rng.choice(np.nonzero(np.asarray(store.active))[0],
                               size=3, replace=False)
            store = bump_versions(store, slots)
        elif ev < 0.5 and n_next < 40:    # new object appears
            s = n_next
            n_next += 1
            emb = rng.normal(size=(E,)).astype(np.float32)
            store = store._replace(
                ids=store.ids.at[s].set(s + 1),
                active=store.active.at[s].set(True),
                embed=store.embed.at[s].set(emb / np.linalg.norm(emb)),
                centroid=store.centroid.at[s].set(
                    rng.uniform(-3, 3, 3).astype(np.float32)),
                n_points=store.n_points.at[s].set(8),
                obs_count=store.obs_count.at[s].set(2),
                version=store.version.at[s].set(1))
        elif ev < 0.7:                    # a client joins mid-session
            c = int(rng.integers(0, C))
            if not joined[c]:
                fs.join(c, poses[c], 1.2)
                joined[c] = True
        fs.refresh(store)
        deliverable = rng.random(C) > 0.35          # random outages
        run_tick(float(t), deliverable)

    for c in range(C):                    # everyone in by settle time
        if not joined[c]:
            fs.join(c, poses[c], 1.2)
            joined[c] = True
    up = np.ones(C, bool)
    t = 24.0
    for _ in range(10):                   # settle: all links up, no changes
        if run_tick(t, up) == 0:
            break
        t += 1.0
    assert run_tick(t + 1.0, up) == 0     # quiesced

    by_zone = _expected_visible(fs, kn.min_obs_before_sync)
    for c in range(C):
        subs = np.nonzero(fs.subscribed[c])[0]
        assert len(subs) > 0
        expect = {}
        for z in subs:
            expect.update(by_zone.get(int(z), {}))
        m = sessions[c].dev.local
        act = np.asarray(m.active)
        got = {int(i): int(v) for i, v in
               zip(np.asarray(m.ids)[act], np.asarray(m.version)[act])}
        assert got == expect, f"client {c}: {got} != {expect}"


# ---------------------------------------------------------------------------
def test_fleet_simulator_smoke():
    """The full driver runs: churn + outages + zone routing + batched
    queries; per-client byte accounting is consistent."""
    kn = Knobs(server_capacity=64, client_capacity=32,
               max_object_points_server=64, max_object_points_client=16,
               max_detections_per_frame=8, min_obs_before_sync=1)
    from repro.core import MappingServer
    from repro.data.scenes import make_scene, scene_stream
    from repro.perception.embedder import OracleEmbedder
    emb = OracleEmbedder(embed_dim=E)
    scene = make_scene(n_objects=10, seed=2)
    classes = {o.oid: o.class_id for o in scene.objects}
    mapper = MappingServer(knobs=kn, embedder=emb)
    frames = list(scene_stream(scene, n_frames=40, keyframe_interval=5,
                               h=60, w=80))
    sim = FleetSimulator(knobs=kn, embed_dim=E, n_clients=6, seed=3,
                         grid=ZoneGrid.for_room(scene.room_size, 2, 2))
    stats = sim.run(n_ticks=8, mapper=mapper, frames=frames, embedder=emb,
                    classes=classes)
    assert stats["down_bytes_total"] >= 0
    per = sum(c.session.down_bytes for c in sim.clients)
    assert per <= stats["down_bytes_total"]   # in-flight may lag delivery
    assert stats["served"] == stats["sq_queries"]   # full drain: no backlog
    assert stats["unserved"] == 0
    assert stats["dropped_by_full_zone"] == 0


@pytest.mark.slow
def test_bench_fleet_scale_smoke():
    """tier-1-adjacent smoke of the fleet_scale suite (C=2, tiny shapes)."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks import fleet_scale
    res = fleet_scale.run(smoke=True)
    assert set(res["sweep"]) == {"1", "2"}
    for r in res["sweep"].values():
        assert r["tick_ms"] > 0 and r["per_client_bytes"] > 0
    # both clients receive identical bytes (same subscription, same map)
    b = [r["per_client_bytes"] for r in res["sweep"].values()]
    assert b[0] == b[1]


def test_ack_tick_parity_with_per_client_acks():
    """The serving loop's batched same-tick ack (FleetServer.ack_tick) must
    leave the server in exactly the state of routing each framed client's
    ack through the per-client path (FleetServer.ack) — acked vectors,
    drained inflight queues, epoch freshness, and lease bookkeeping."""
    def build():
        srv = FleetServer(knobs=KN, embed_dim=E, n_clients=4,
                          grid=ZoneGrid.for_room(8.0, 2, 1), budget=8)
        rng = np.random.default_rng(3)
        for c in range(4):
            srv.join(c, rng.uniform(-3, 3, size=3).astype(np.float32), 6.0)
        srv.refresh(synth_store(24))
        return srv

    deliverable = np.ones((4,), bool)
    a, b = build(), build()
    for t in range(3):
        pk_a = a.tick(deliverable, tick=t)
        pk_b = b.tick(deliverable, tick=t)
        a.ack_tick(pk_a, tick=t)
        for z, pkt in pk_b:
            for c in np.nonzero(pkt.seqs >= 0)[0]:
                b.ack(int(c), int(z), int(pkt.epoch[c]), int(pkt.seqs[c]),
                      tick=t)
    for sa, sb in zip(a.sessions, b.sessions):
        assert np.array_equal(sa.acked, sb.acked)
        assert all(len(q) == 0 for q in sa.inflight)
        assert all(len(q) == 0 for q in sb.inflight)
    assert np.array_equal(a.epoch_fresh, b.epoch_fresh)
    assert np.array_equal(a.last_ack_tick, b.last_ack_tick)


# ---------------------------------------------------------------------------
# the zone mirror's client-cloud column (server/zones.py, session.py)
KN_OVR = Knobs(server_capacity=64, client_capacity=64,
               max_object_points_server=64, max_object_points_client=16,
               min_obs_before_sync=1,
               class_point_overrides=((0, 4), (1, 8), (2, 999)))
GRID2 = ZoneGrid.for_room(8.0, nx=2, nz=1)      # zone 0: x<0, zone 1: x>=0


def _ref_client_clouds(zone, kn):
    """Each shard row stride-downsampled to its class budget the way the
    collect once did it per (client, row): index i of the output reads
    floor(i * n / b) when the row has more than b points.  Returns the
    float16 points [R, Pc, 3], n [R] and the float32 downsample's
    centroid [R, 3]."""
    pts = np.asarray(zone.points)
    n_src, labels = np.asarray(zone.n_points), np.asarray(zone.label)
    R, P = pts.shape[:2]
    Pc = kn.max_object_points_client
    ar = np.arange(Pc)
    out = np.zeros((R, Pc, 3), np.float32)
    n_out = np.zeros((R,), np.int32)
    cent = np.zeros((R, 3), np.float32)
    for r in range(R):
        n = max(int(n_src[r]), 1)
        b = min(max(kn.client_points_for(int(labels[r])), 1), Pc)
        sub = np.minimum(np.where(n > b, (ar * n) // b, ar), P - 1)
        k = min(n, b)
        out[r, :k] = pts[r, sub[:k]]
        n_out[r] = k
        cent[r] = out[r, :k].astype(np.float64).mean(axis=0)
    return out.astype(np.float16), n_out, cent


def _churn_stores():
    """Global stores in turn: inserts, version bumps that change geometry
    and class, tombstones, their release (shard slots freed), new objects
    in the released slots (shard slots reused) and a zone crossing."""
    rng = np.random.default_rng(31)
    st = synth_store(20, cap=32, seed=21)
    yield st
    s = jnp.asarray([1, 4, 7, 12])
    st = st._replace(points=st.points.at[s].multiply(-0.5),
                     n_points=st.n_points.at[s].set(
                         jnp.asarray([3, 64, 17, 40], jnp.int32)),
                     label=st.label.at[s].set(
                         jnp.asarray([0, 1, 2, 5], jnp.int32)),
                     version=st.version.at[s].add(1))
    yield st
    ids = np.asarray(st.ids)
    st = remove_objects(st, ids[[2, 5, 9]])
    yield st
    st = release_tombstones(st)
    yield st
    s = np.array([2, 5, 9, 20, 21])
    m = len(s)
    st = st._replace(
        ids=st.ids.at[s].set(jnp.arange(101, 101 + m, dtype=jnp.int32)),
        active=st.active.at[s].set(True),
        label=st.label.at[s].set(jnp.asarray([0, 1, 3, 2, 0], jnp.int32)),
        points=st.points.at[s].set(
            rng.normal(size=(m, 64, 3)).astype(np.float32)),
        n_points=st.n_points.at[s].set(
            jnp.asarray(rng.integers(2, 64, size=m), jnp.int32)),
        centroid=st.centroid.at[s].set(
            rng.uniform((-4, 0, -4), (4, 2, 4), size=(m, 3))
            .astype(np.float32)),
        obs_count=st.obs_count.at[s].set(3),
        version=st.version.at[s].set(1))
    yield st
    c = np.asarray(st.centroid)
    g = int(np.nonzero(np.asarray(st.active) & (c[:, 0] >= 0.5))[0][0])
    st = st._replace(centroid=st.centroid.at[g, 0].set(-c[g, 0]),
                     points=st.points.at[g].add(0.25),
                     version=st.version.at[g].add(1))
    yield st


def _assert_reference_packet(pkt, idx, zone, kn):
    """Every row of the packet, padding included, is what the per-row
    reference downsample of the shard gives at the packet's slots."""
    ref_pts, ref_n, ref_cent = _ref_client_clouds(zone, kn)
    b = jax.tree.map(np.asarray, pkt.batch)
    dele = b.deleted
    assert not (dele & ~b.valid).any()
    np.testing.assert_array_equal(b.oid, np.asarray(zone.ids)[idx])
    np.testing.assert_array_equal(b.version, np.asarray(zone.version)[idx])
    np.testing.assert_array_equal(
        dele, np.asarray(zone.deleted)[idx] & b.valid)
    np.testing.assert_array_equal(b.n_points, np.where(dele, 0, ref_n[idx]))
    want = np.where(dele[..., None, None], np.float16(0), ref_pts[idx])
    assert b.points.dtype == np.float16
    np.testing.assert_array_equal(b.points.view(np.uint16),
                                  want.view(np.uint16))
    want_c = np.where(dele[..., None], np.asarray(zone.centroid)[idx],
                      ref_cent[idx])
    np.testing.assert_allclose(b.centroid, want_c, rtol=0, atol=1e-6)
    nb = [sum(update_nbytes(E, int(n), deleted=bool(d))
              for n, d, v in zip(b.n_points[c], dele[c], b.valid[c]) if v)
          for c in range(len(pkt.nbytes))]
    np.testing.assert_array_equal(pkt.nbytes, nb)
    return int(dele.sum()), int((b.valid & (b.n_points < 16)
                                 & ~dele).sum())


@pytest.mark.parametrize("column", ["maintained", "built"])
def test_collect_from_client_cloud_column_matches_reference(column):
    """The collect ships what the per-(client, row) stride downsample
    shipped, whether it gathers the zone mirror's maintained column or,
    given none, builds the column itself: oid, version, valid, deleted,
    n_points, float16 points and bytes bit for bit, centroids within
    1e-6 m, over inserts, geometry and class changes, tombstones, freed
    and reused slots and a zone crossing, under class point overrides."""
    kn = KN_OVR
    zoned = ZoneShardedStore(knobs=kn, embed_dim=E, grid=GRID2,
                             zone_capacity=24)
    poses = np.array([[-2, 1, 0], [2, 1, 1], [0, 1, -3]], np.float32)
    sessions = [SessionManager(knobs=kn, n_clients=3, capacity=24, budget=8,
                               user_pos=poses.copy()) for _ in range(2)]
    tombs = clipped = freed_total = 0
    for st in _churn_stores():
        freed, _ = zoned.refresh_from(st)
        for z, sess in enumerate(sessions):
            freed_total += len(freed[z])
            sess.reset_slots(freed[z])
            for _ in range(2):
                clouds = zoned.clouds[z] if column == "maintained" else None
                p = sess.collect_start(zoned.zones[z], zone=z,
                                       clouds=clouds)
                pkt = sess.collect_finish(p)
                t, k = _assert_reference_packet(pkt, np.asarray(p.idx),
                                                zoned.zones[z], kn)
                tombs += t
                clipped += k
    # the sequence did what it is for: tombstones shipped, slots freed
    # (release and the zone crossing), rows cut below the buffer width
    assert tombs > 0 and freed_total >= 4 and clipped > 0


@pytest.mark.parametrize("stage", ["refreshes", "prepopulated", "place_on"])
def test_client_cloud_column_matches_its_shard(stage):
    """The maintained column equals the column rebuilt from scratch from
    the shard: after every refresh, for a store built from pre-populated
    shards, and after place_on and a refresh there."""
    kn = KN_OVR

    def check(zoned):
        for zone, cl in zip(zoned.zones, zoned.clouds):
            pts, n, cent = _ref_client_clouds(zone, kn)
            np.testing.assert_array_equal(
                np.asarray(cl.points).reshape(pts.shape).view(np.uint16),
                pts.view(np.uint16))
            np.testing.assert_array_equal(np.asarray(cl.n_points), n)
            np.testing.assert_allclose(np.asarray(cl.centroid), cent,
                                       rtol=0, atol=1e-6)

    zoned = ZoneShardedStore(knobs=kn, embed_dim=E, grid=GRID2,
                             zone_capacity=24)
    for st in _churn_stores():
        zoned.refresh_from(st)
        if stage == "refreshes":
            check(zoned)
    if stage == "prepopulated":
        zoned = ZoneShardedStore(knobs=kn, embed_dim=E, grid=GRID2,
                                 zones=list(zoned.zones))
        assert zoned.n_active() > 0
    elif stage == "place_on":
        from jax.sharding import Mesh
        zoned.place_on(Mesh(np.array(jax.devices()[:1]), ("d",)))
        check(zoned)
        act = np.nonzero(np.asarray(st.active))[0][:3]
        zoned.refresh_from(st._replace(
            points=st.points.at[act].multiply(2.0),
            version=st.version.at[act].add(1)))
    check(zoned)


# ---------------------------------------------------------------------------
# mesh-sharded session tier (server/mesh.py)
def test_mesh_tier_byte_identity_vs_unsharded():
    """MeshSessionTier (client axis split over S session shards) must be
    byte-identical to the single-device SessionManager: every per-client
    packet field, the seq streams, and the host bookkeeping (acked /
    inflight / deletion debt) — across ticks with interleaved mutations,
    acks, rollbacks, resets, and slot reuse."""
    from repro.server.mesh import ClientRoster, MeshSessionTier
    C, N = 12, KN.server_capacity
    store = synth_store(28, cap=N, seed=5)
    rng = np.random.default_rng(2)
    poses = rng.uniform(-3, 3, (C, 3)).astype(np.float32)
    subs = rng.random(C) < 0.85

    ref = SessionManager(knobs=KN, n_clients=C, capacity=N, budget=8,
                         subscribed=subs.copy(), user_pos=poses.copy())
    tier = MeshSessionTier(knobs=KN, capacity=N, budget=8,
                           roster=ClientRoster.round_robin(C, 4))
    tier.set_all(subscribed=subs, user_pos=poses)

    epoch = np.arange(C, dtype=np.int64)
    for t in range(5):
        deliv = rng.random(C) < 0.9
        pa = ref.collect(store, deliverable=deliv, zone=1, epoch=epoch,
                         now=t)
        pb = tier.collect(store, deliverable=deliv, zone=1, epoch=epoch,
                          now=t)
        np.testing.assert_array_equal(pa.counts, pb.counts)
        np.testing.assert_array_equal(pa.nbytes, pb.nbytes)
        np.testing.assert_array_equal(pa.seqs, pb.seqs)
        np.testing.assert_array_equal(pa.tomb_counts(), pb.tomb_counts())
        assert pa.total_nbytes == pb.total_nbytes
        for c in range(C):
            ua, ub = pa.packet_for(c), pb.packet_for(c)
            assert (ua.count, ua.nbytes, ua.tick) \
                == (ub.count, ub.nbytes, ub.tick)
            if ua.count:
                assert (ua.zone, ua.seq, ua.epoch) \
                    == (ub.zone, ub.seq, ub.epoch)
                for f in ("oid", "embed", "label", "points", "n_points",
                          "centroid", "version", "valid"):
                    np.testing.assert_array_equal(
                        np.asarray(getattr(ua.batch, f)),
                        np.asarray(getattr(ub.batch, f)), err_msg=f)
        # interleave the control plane identically on both
        for c in range(C):
            if int(pa.seqs[c]) >= 0 and rng.random() < 0.6:
                ref.ack(c, int(pa.seqs[c]))
                tier.ack(c, int(pb.seqs[c]))
        if t == 1:
            ref.rollback(3), tier.rollback(3)
        if t == 2:
            ref.reset_client(5, keep_seq=True)
            tier.reset_client(5, keep_seq=True)
            ref.reset_slots([0, 7]), tier.reset_slots([0, 7])
        if t == 3:
            store = bump_versions(store, [1, 4, 9])
        assert ref.dirty == tier.dirty
        np.testing.assert_array_equal(ref.acked, tier_acked(tier))
        np.testing.assert_array_equal(ref.deletion_debt(store),
                                      tier.deletion_debt(store))
        for c in range(C):
            assert ref.oldest_unacked_tick(c) == tier.oldest_unacked_tick(c)


def tier_acked(tier):
    """Assemble a sharded tier's [C, N] acked mirror for comparison."""
    out = np.zeros((tier.n_clients, tier.capacity), np.int32)
    for s, part in enumerate(tier.parts):
        if part is not None:
            out[tier.roster.members[s]] = part.acked
    return out


def test_mesh_fleet_server_end_to_end_byte_identity():
    """FleetServer(n_session_shards=S) vs the default single-device tier:
    identical wire packets through joins, pose churn (zone crossings), and
    the batched-ack tick loop."""
    def build(shards):
        srv = FleetServer(knobs=KN, embed_dim=E, n_clients=6,
                          grid=ZoneGrid.for_room(8.0, 2, 2), budget=8,
                          n_session_shards=shards)
        rng = np.random.default_rng(4)
        for c in range(6):
            srv.join(c, rng.uniform(-3, 3, 3).astype(np.float32), 2.0)
        return srv

    a, b = build(1), build(3)
    store = synth_store(24, cap=a.zoned.zone_capacity)
    rng = np.random.default_rng(9)
    deliverable = np.ones((6,), bool)
    for t in range(4):
        a.refresh(store), b.refresh(store)
        poses = rng.uniform(-3.5, 3.5, (6, 3)).astype(np.float32)
        a.set_poses(poses, 2.0), b.set_poses(poses, 2.0)
        np.testing.assert_array_equal(a.subscribed, b.subscribed)
        pa = a.tick(deliverable, tick=t)
        pb = b.tick(deliverable, tick=t)
        assert [z for z, _ in pa] == [z for z, _ in pb]
        for (z, qa), (_, qb) in zip(pa, pb):
            np.testing.assert_array_equal(qa.nbytes, qb.nbytes)
            np.testing.assert_array_equal(qa.seqs, qb.seqs)
            for c in range(6):
                ua, ub = qa.packet_for(c), qb.packet_for(c)
                assert (ua.count, ua.nbytes) == (ub.count, ub.nbytes)
                if ua.count:
                    np.testing.assert_array_equal(
                        np.asarray(ua.batch.points),
                        np.asarray(ub.batch.points))
        a.ack_tick(pa, tick=t), b.ack_tick(pb, tick=t)
        store = bump_versions(store, [t, t + 3])
    np.testing.assert_array_equal(a.epoch, b.epoch)
    assert a.blocked_tombstone_oids(tick=5) == b.blocked_tombstone_oids(tick=5)


def test_client_shard_affinity():
    """Zone-affinity partition: a client lands on the shard holding the
    majority of its subscribed zones; unsubscribed clients round-robin."""
    from repro.server.mesh import client_shard_affinity
    subs = np.zeros((4, 8), bool)
    subs[0, [0, 2, 4]] = True          # zones 0,2,4 -> shard 0 under z%2
    subs[1, [1, 3]] = True             # -> shard 1
    subs[2, [0, 1, 3]] = True          # majority odd -> shard 1
    # client 3 subscribes nothing -> 3 % 2 = 1
    a = client_shard_affinity(subs, 2)
    assert a.tolist() == [0, 1, 1, 1]
    # explicit zone->shard map overrides the z % S default
    a2 = client_shard_affinity(subs, 2, zone_shards=np.zeros(8, np.int64))
    assert a2.tolist() == [0, 0, 0, 1]


# ---------------------------------------------------------------------------
# satellite bugfix: zone-crossing mid-flight staleness
def _framed_server(n_clients=1):
    srv = FleetServer(knobs=KN, embed_dim=E, n_clients=n_clients,
                      grid=ZoneGrid.for_room(8.0, 2, 1), budget=8)
    return srv


def test_zone_crossing_midflight_never_applies_stale_row():
    """A packet in the air when its client leaves the zone must be DROPPED
    at the device on arrival — never ingested then pruned a tick later.
    The seq stream still advances and the cumulative ack still goes out,
    so re-entry packets (seq continues: the server kept the stream via
    reset_client(keep_seq=True)) are not mistaken for a gap."""
    srv = _framed_server()
    # client in zone 0 (left half of the 2x1 grid)
    srv.join(0, np.array([-2.0, 1.5, 0.0], np.float32), 1.0)
    store = synth_store(20, x_range=(-4, -1))   # all objects in zone 0
    srv.refresh(store)
    sess = ClientSession(dev=DeviceClient(knobs=KN, embed_dim=E),
                         net=NetworkModel(rtt_ms=20.0, bandwidth_mbps=100.0),
                         knobs=KN, cid=0)
    sess.zone_subs = srv.subscribed[0].copy()

    packets = srv.tick(np.ones(1, bool), tick=0)
    assert packets and int(packets[0][1].counts[0]) > 0
    in_air = packets[0][1].packet_for(0)

    # the client crosses to zone 1 BEFORE the packet lands
    srv.set_client_pose(0, np.array([2.0, 1.5, 0.0], np.float32), 1.0)
    sess.zone_subs = srv.subscribed[0].copy()
    assert not sess.zone_subs[0] and sess.zone_subs[1]

    live0 = int(np.asarray(sess.dev.local.active).sum())
    sess._receive(0.0, in_air)
    # dropped at delivery: nothing ingested, no stale-zone row in the map
    assert int(np.asarray(sess.dev.local.active).sum()) == live0 == 0
    assert sess.delivered == 0 and sess.down_bytes == 0
    assert sess.stale_drops == 1
    # ...but the protocol position advanced: ack emitted, seq consumed
    acks = sess.drain_acks()
    assert acks == [(0, int(in_air.epoch), int(in_air.seq))]
    assert sess._expect[0] == in_air.seq + 1

    # re-entry: the client returns to zone 0 — the catch-up re-ships on the
    # SAME seq stream (keep_seq survived the round trip) and applies
    # cleanly, no gap, no resync
    srv.set_client_pose(0, np.array([-2.0, 1.5, 0.0], np.float32), 1.0)
    sess.zone_subs = srv.subscribed[0].copy()
    pk2 = srv.tick(np.ones(1, bool), tick=1)
    delivered_any = False
    for z, pkt in pk2:
        u = pkt.packet_for(0)
        if u.count:
            assert u.seq == in_air.seq + 1   # stream continued, not reset
            sess._receive(1.0, u)
            delivered_any = True
    assert delivered_any
    assert sess.stale_drops == 1            # no further drops
    assert sess.resyncs == 0 and not sess._gap_since
    assert int(np.asarray(sess.dev.local.active).sum()) > 0


def test_zone_gate_off_by_default():
    """Legacy callers that never set zone_subs keep the old behavior:
    framed packets from any zone apply (the gate arms only when the
    subscription view is wired)."""
    srv = _framed_server()
    srv.join(0, np.array([-2.0, 1.5, 0.0], np.float32), 1.0)
    srv.refresh(synth_store(12, x_range=(-4, -1)))
    sess = ClientSession(dev=DeviceClient(knobs=KN, embed_dim=E),
                         net=NetworkModel(rtt_ms=20.0, bandwidth_mbps=100.0),
                         knobs=KN, cid=0)
    assert sess.zone_subs is None
    packets = srv.tick(np.ones(1, bool), tick=0)
    sess._receive(0.0, packets[0][1].packet_for(0))
    assert sess.delivered == 1 and sess.stale_drops == 0
