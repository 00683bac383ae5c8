"""Keyframes through the serving loop's ingest seam.

The seam yields due ``Keyframe``s instead of ``IngestDelta`` rows, and the
loop maps them with the mapper's one fused ``ingest_frame`` dispatch.  At a
small size (120x160 frames, a 12-object room, 3 mappers, 4 viewers) these
tests pin what makes that path sound: both schedules serve the same bytes,
the loop maps exactly what ``MappingServer.process_frame`` maps keyframe by
keyframe, the donated back buffer catches up to the published front from
the keyframes' records, and the association cosine is taken at full f32.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import association as assoc
from repro.core.knobs import Knobs
from repro.core.pipeline import MappingServer
from repro.core.store import SnapshotStore, store_from_knobs
from repro.data.scenes import make_scene, render_frame
from repro.perception.embedder import OracleEmbedder
from repro.server.fleet import FleetServer
from repro.server.zones import ZoneGrid
from repro.serving.loop import Keyframe, ServingLoop

E, P, D, CAP = 16, 64, 8, 128
MAPPERS, VIEWERS, ORBIT = 3, 4, 60
KN = Knobs(server_capacity=CAP, client_capacity=64,
           max_object_points_server=P, max_object_points_client=8,
           max_detections_per_frame=D)


@pytest.fixture(scope="module")
def keyframes():
    """Per tick, the keyframes due: the three mappers' phase-offset orbits
    of one room, one or two keyframes a tick (and a tick with none)."""
    scene = make_scene(n_objects=12, seed=4)
    classes = {o.oid: o.class_id for o in scene.objects}
    key = jax.random.key(2)
    kfs = []
    for j in range(6):
        for m in range(MAPPERS):
            idx = (5 * j + m * ORBIT // MAPPERS) % ORBIT
            fr = render_frame(scene, idx, h=120, w=160, n_frames=ORBIT)
            kfs.append(Keyframe(frame=fr, classes=classes,
                                key=jax.random.fold_in(key, len(kfs)),
                                mapper=m))
    per_tick, i = [], 0
    for n in (2, 1, 0, 2, 3, 1, 2, 1, 2, 2, 2):
        per_tick.append(kfs[i:i + n])
        i += n
    assert i == len(kfs)
    return per_tick


class Seam:
    """The loop's ``ingest``: this tick's due keyframes."""

    def __init__(self, per_tick):
        self.per_tick = per_tick
        self.mapped = []

    def delta_at(self, t):
        return self.per_tick[t] if t < len(self.per_tick) else []

    def note_mapped(self, t, out):
        self.mapped.append((t, out))


def _run(per_tick, overlap, extra_ticks=2, donate=None):
    grid = ZoneGrid.for_room(8.0, 1, 1)
    srv = FleetServer(knobs=KN, embed_dim=E, n_clients=VIEWERS, grid=grid,
                      budget=4, index=False)
    packets = []
    ack_real = srv.ack_tick

    def ack_tick(pkts, *, tick):
        for z, p in pkts:
            packets.append((tick, z, jax.tree.map(np.asarray, p.batch),
                            np.asarray(p.nbytes)))
        return ack_real(pkts, tick=tick)

    srv.ack_tick = ack_tick
    rng = np.random.default_rng(0)
    for c in range(VIEWERS):
        srv.join(c, rng.uniform(-3, 3, size=3).astype(np.float32), 6.0)
    mapper = MappingServer(knobs=KN, embedder=OracleEmbedder(embed_dim=E),
                           donate=donate)
    seam = Seam(per_tick)
    loop = ServingLoop(server=srv,
                       store=SnapshotStore.of(store_from_knobs(KN, E)),
                       ingest=seam, mapper=mapper, overlap=overlap)
    loop.run(len(per_tick) + extra_ticks)
    return loop, seam, packets


def _host(store):
    return jax.tree.map(np.asarray, store)


def _equal(a, b):
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_overlapped_and_sync_serve_identical_bytes(keyframes):
    lo, so, po = _run(keyframes, overlap=True)
    # the sync schedule maps onto a copy of the front it still reads, so a
    # donating mapper (a TPU's default) serves the same bytes
    ls, ss, ps = _run(keyframes, overlap=False, donate=True)
    assert _equal(_host(lo.store.front), _host(ls.store.front))
    assert int(np.asarray(lo.store.front.active).sum()) > 0
    assert lo.sent_bytes == ls.sent_bytes > 0
    assert len(po) == len(ps) > 0
    for a, b in zip(po, ps):
        assert a[:2] == b[:2] and _equal(a[2:], b[2:])
    # both schedules hand the seam the same records, keyframe by keyframe
    flat = [[(i, kf.mapper, r) for t, out in s.mapped for i, kf, r in out]
            for s in (so, ss)]
    assert [x[:2] for x in flat[0]] == [x[:2] for x in flat[1]]
    assert [x[0] for x in flat[0]] == list(range(sum(map(len, keyframes))))
    for (_, _, a), (_, _, b) in zip(*flat):
        assert (a is None) == (b is None)
        assert a is None or _equal(a, b)


def test_loop_maps_what_process_frame_maps(keyframes):
    loop, seam, _ = _run(keyframes, overlap=True)
    ref = MappingServer(knobs=KN, embedder=OracleEmbedder(embed_dim=E),
                        donate=False)
    for kfs in keyframes:
        for kf in kfs:
            ref.process_frame(kf.frame, kf.classes, kf.key)
    assert ref.frame_count == loop.mapper.frame_count
    assert _equal(_host(loop.store.front), _host(ref.store))
    # each record names the rows its keyframe wrote, as they now stand
    st = _host(ref.store)
    last = {}
    for _, out in seam.mapped:
        for _, _, r in out:
            if r is None:
                continue
            for k in np.nonzero(r.slot < CAP)[0]:
                last[int(r.oid[k])] = (int(r.slot[k]), int(r.version[k]))
    live = {int(st.ids[s]): int(st.version[s])
            for s in np.nonzero(st.active)[0]}
    assert live and all(last[o][1] == v for o, v in live.items())


def _back_catches_up(keyframes):
    loop, _, _ = _run(keyframes, overlap=True, extra_ticks=0)
    assert loop.store.pending        # the last tick's keyframe records
    front = _host(loop.store.front)
    back = loop._catch_up(loop.store.take_back())
    assert _equal(_host(back), front)


def test_back_buffer_catches_up_to_front(keyframes):
    _back_catches_up(keyframes)


def test_back_buffer_catches_up_one_record_a_dispatch(keyframes,
                                                      monkeypatch):
    """With one record a row-copy dispatch, a tick's records take
    several dispatches, and the back buffer still ends as the front."""
    from repro.serving import loop as loop_mod
    monkeypatch.setattr(loop_mod, "CATCH_UP_RECORDS", 1)
    _back_catches_up(keyframes)


def test_association_cosine_at_highest_precision():
    st = store_from_knobs(KN, E)
    det = assoc.Detections(embed=jnp.zeros((D, E)),
                           label=jnp.zeros((D,), jnp.int32),
                           points=jnp.zeros((D, P, 3)),
                           n_points=jnp.zeros((D,), jnp.int32),
                           valid=jnp.ones((D,), bool))
    jaxpr = jax.make_jaxpr(
        lambda s, d: assoc.association_scores(s, d))(st, det)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        prec = e.params["precision"]
        precs = prec if isinstance(prec, tuple) else (prec,)
        assert all(p == jax.lax.Precision.HIGHEST for p in precs), prec


@pytest.mark.parametrize("h, w", [(120, 160), (360, 640)])
def test_detect_matches_per_object_presence(h, w):
    """The detector stand-in's one-pass bbox areas keep and defer exactly
    the objects a [K, H, W] presence test does."""
    from repro.core import depth as depth_mod
    kn = Knobs(max_detections_per_frame=D, skip_mapping_set=(3,))
    scene = make_scene(n_objects=40, seed=6)
    classes = {o.oid: o.class_id for o in scene.objects}
    mapper = MappingServer(knobs=kn, embedder=OracleEmbedder(embed_dim=E),
                           store=store_from_knobs(KN, E))
    deferred = 0
    for idx in (0, 23, 41):
        fr = render_frame(scene, idx, h=h, w=w, n_frames=60)
        oids = np.asarray(fr.visible_ids, np.int32)
        oids = oids[[classes[int(o)] not in kn.skip_mapping_set
                     for o in oids]]
        pres = fr.inst[None] == oids[:, None, None]
        ext = [np.ptp(np.nonzero(p.any(axis=ax))[0]) + 1
               for p in pres for ax in (1, 0)]
        area = np.asarray(ext).reshape(-1, 2).prod(axis=1)
        keep = np.asarray(depth_mod.mapping_gate(area, kn,
                                                 frame_pixels=h * w))
        want = oids[keep][:D]
        cids, masks = mapper._detect(fr, classes)
        assert [classes[int(o)] for o in want] == cids.tolist()
        r = kn.depth_downsampling_ratio
        assert np.array_equal(
            masks, fr.inst[::r, ::r][None] == want[:, None, None])
        deferred += int((~keep).sum())
    assert deferred > 0 and mapper.deferred == deferred
