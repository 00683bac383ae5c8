"""Driver ``map``: the shared room's served tick, keyframes in, under
open-loop wall-clock load.

One ``repro.serving.loop.ServingLoop`` (overlapped schedule) maps and
serves, ticking back to back as the ``serve`` driver does, while three
open-loop streams fall due on the wall clock:

- keyframes: each mapper sends its orbit's keyframes periodically; they
  enter through the loop's ``ingest`` seam as the ``Keyframe``s due since
  the last tick, which the loop detects and maps one by one in due order;
- queries, per-viewer MMPP ("where is <class>?" near the viewer), through
  the ``loadgen`` seam, as in the ``serve`` driver;
- poses: every tick reports each viewer's pose at the current time.

A (viewer, object version) pair is timed from the keyframe's due time to
the framed packet that first gives the viewer that version or a newer
one; a query from its due time to its resolved result on the host.

Set-up draws the room from the run's seed, renders every mapper's orbit
at the configuration's resolution, maps one warm orbit of every mapper
(so the room starts mapped and every shape the window uses is compiled),
warms full query batches, and ticks until every viewer has caught up.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import generators as tg, stats
from bench.checks import map as check
from bench.drivers import serve as venue

from repro.core.knobs import Knobs
from repro.core.pipeline import MappingServer
from repro.core.query import Query
from repro.core.store import SnapshotStore, copy_store, store_from_knobs
from repro.data.scenes import make_scene, render_frame
from repro.perception.embedder import OracleEmbedder
from repro.server.fleet import FleetServer
from repro.server.zones import ZoneGrid, ZoneShardedStore
from repro.serving.loop import Keyframe, ServingLoop

PRE_COLUMNS = ("ids", "active", "deleted", "embed", "centroid", "n_points",
               "obs_count", "version", "last_seen", "label", "next_id")
WARM_GROUPS = (1, 2, 3, 4, 5, 6)   # warm keyframes handed out per tick
N_QUERY_SAMPLE = 64                # queries compared with the reference


@jax.jit
def capture(pre, post, rec):
    """A sampled keyframe's view for the reference: the store columns
    before it (copies), the clouds of the rows it targeted before it, and
    the clouds of the rows it wrote after it."""
    cap = pre.ids.shape[0]
    cols = {k: jnp.copy(getattr(pre, k)) for k in PRE_COLUMNS}
    cols["points"] = pre.points[jnp.minimum(rec.target, cap - 1)]
    return cols, post.points[jnp.minimum(rec.slot, cap - 1)]


# ---------------------------------------------------------------------------
class Traffic:
    """Every timed item of one run, with its due time (s from the start
    of the window); the attributes ``serve``'s recorder and reference read
    are named as there."""

    def __init__(self, cfg: dict, trf: dict, seed: int, seconds: float,
                 basis: np.ndarray):
        C = cfg["clients"]
        shape = np.random.default_rng(trf["shape_seed"])
        anchor = tg.anchors(shape, C, cfg["room_m"])
        phase = shape.uniform(0.0, 2 * np.pi, size=C)
        q = trf["queries"]
        due, who = tg.mmpp_arrivals(shape, C, seconds, q["base_hz"],
                                    q["burst_factor"], q["burst_entry_hz"],
                                    q["burst_dwell_s"])
        kf = trf["keyframes"]
        self.kf_due, self.kf_mapper = tg.periodic_events(
            shape, cfg["mappers"], kf["hz"], seconds)
        # each mapper walks its orbit on: the window goes on from the warm
        # orbit's end, which is the orbit's start
        seen = collections.Counter()
        self.kf_pos = np.zeros(len(self.kf_due), np.int64)
        for j, m in enumerate(self.kf_mapper):
            self.kf_pos[j] = seen[m] % kf["orbit_keyframes"]
            seen[m] += 1
        perm = tg.derive_seed(seed, "clients").permutation(C)
        self.anchor, self.phase = anchor[perm], phase[perm]
        self.q_due = due
        self.q_client = np.argsort(perm)[who]
        self.pose = trf["poses"]
        p = self.pose
        ang = self.phase[self.q_client] \
            + (p["walk_m_per_s"] / p["orbit_m"]) * due
        self.q_center = (self.anchor[self.q_client] + np.stack(
            [p["orbit_m"] * np.cos(ang), np.zeros_like(ang),
             p["orbit_m"] * np.sin(ang)], axis=1)).astype(np.float32)
        # "where is my <class>?": the class's text embedding
        qc = tg.derive_seed(seed, "queries").integers(0, len(basis),
                                                      size=len(due))
        self.q_embed = basis[qc].astype(np.float32)
        self.k, self.radius = q["k"], q["near_radius_m"]

    def poses_at(self, tau: float) -> np.ndarray:
        p = self.pose
        return tg.orbit_poses(self.anchor, self.phase, p["orbit_m"],
                              p["walk_m_per_s"], tau)


class Recorder(venue.Recorder):
    """``serve``'s recorder (queries, poses, packets) with keyframes at
    the ``ingest`` seam in place of rows."""

    def __init__(self, trf: Traffic, orbits: list, classes: dict, keys,
                 warm_specs):
        super().__init__(trf, None, warm_specs)
        self.orbits, self.classes, self.keys = orbits, classes, keys
        self.warm_kf = collections.deque()
        self.kf_next = 0               # next window keyframe not handed out
        self.n_handed = 0              # keyframes handed out: next index
        self.records, self.kf_tick = {}, {}
        self.frame_of = {}             # keyframe index -> (mapper, pos)
        self.sample_at = set()         # keyframe indices the check samples
        self.samples = {}

    def _keyframe(self, m: int, pos: int, due) -> Keyframe:
        i = self.n_handed
        self.n_handed += 1
        self.frame_of[i] = (m, pos)
        return Keyframe(frame=self.orbits[m][pos], classes=self.classes,
                        key=self.keys[i], mapper=m, due=due)

    # -- ServingLoop.ingest -------------------------------------------------
    def delta_at(self, t):
        start = self.n_handed
        if self.warm_kf:
            kfs = [self._keyframe(m, pos, None)
                   for m, pos in self.warm_kf.popleft()]
        elif self.t0 is not None:
            now = time.perf_counter() - self.t0
            stop = int(np.searchsorted(self.trf.kf_due, now, side="right"))
            kfs = [self._keyframe(int(self.trf.kf_mapper[j]),
                                  int(self.trf.kf_pos[j]),
                                  self.t0 + self.trf.kf_due[j])
                   for j in range(self.kf_next, stop)]
            self.kf_next = stop
        else:
            kfs = []
        self._rows = (start, len(kfs))
        return kfs

    def note_mapped(self, t, out):
        for i, _, rec in out:
            self.records[i] = rec
            self.kf_tick[i] = t

    def wrap_mapper(self, mapper):
        """Capture the sampled keyframes' stores around their dispatch."""
        real = mapper.ingest_keyframe

        def ingest_keyframe(store, inputs, key, index):
            if index not in self.sample_at:
                return real(store, inputs, key, index)
            pre = copy_store(store)          # before the dispatch donates it
            out, rec = real(store, inputs, key, index)
            self.samples[index] = capture(pre, out, rec)
            return out, rec

        mapper.ingest_keyframe = ingest_keyframe


# ---------------------------------------------------------------------------
def _knobs(cfg: dict) -> Knobs:
    return Knobs(server_capacity=cfg["capacity"],
                 client_capacity=max(2 * cfg["budget_rows"], 64),
                 max_object_points_server=cfg["server_points"],
                 max_object_points_client=cfg["client_points"],
                 max_detections_per_frame=cfg["max_detections"],
                 min_mapping_bbox_area=cfg["min_bbox_px"],
                 depth_downsampling_ratio=cfg["depth_ratio"],
                 min_obs_before_sync=cfg["min_obs_before_sync"])


def _orbits(cfg: dict, trf: dict, seed: int):
    """The room, drawn from the run's seed, and each mapper's orbit of
    keyframes, rendered at the configuration's resolution: mapper m starts
    m/K of the way round, every ``keyframe_interval``-th frame of a 30 fps
    orbit."""
    kf = trf["keyframes"]
    scene = make_scene(n_objects=cfg["scene_objects"], room=cfg["room_m"],
                       seed=int(tg.derive_seed(seed, "room").integers(
                           2 ** 31)))
    classes = {o.oid: o.class_id for o in scene.objects}
    n = kf["orbit_keyframes"] * cfg["keyframe_interval"]
    K = cfg["mappers"]
    orbits = [[render_frame(scene, (cfg["keyframe_interval"] * j + m * n // K)
                            % n, h=cfg["frame_h"], w=cfg["frame_w"],
                            n_frames=n)
               for j in range(kf["orbit_keyframes"])] for m in range(K)]
    return orbits, classes


def _build(cfg: dict, ctx):
    seed, trf_cfg = ctx.seed, ctx.traffic
    embed_seed = int(tg.derive_seed(seed, "embedder").integers(2 ** 31))
    basis = check.class_basis(cfg, embed_seed)
    trf = Traffic(cfg, trf_cfg, seed, ctx.seconds, basis)
    orbits, classes = _orbits(cfg, trf_cfg, seed)
    E, C = cfg["embed_dim"], cfg["clients"]
    kn = _knobs(cfg)
    mapper = MappingServer(
        knobs=kn, embedder=OracleEmbedder(
            embed_dim=E, noise=cfg["embedder"]["noise"], seed=embed_seed),
        store=store_from_knobs(kn, E))
    n_warm = cfg["mappers"] * trf_cfg["keyframes"]["orbit_keyframes"]
    min_obs = jnp.asarray(cfg["min_obs_before_sync"], jnp.int32)
    key = jax.random.key(int(tg.derive_seed(seed, "keys").integers(2 ** 31)))
    keys = list(jax.random.split(key, n_warm + len(trf.kf_due)))
    grid = ZoneGrid.for_room(cfg["room_m"], *cfg["zones"])
    zoned = ZoneShardedStore(knobs=kn, embed_dim=E, grid=grid,
                             zone_capacity=cfg["zone_capacity"])
    srv = FleetServer(knobs=kn, embed_dim=E, n_clients=C, grid=grid,
                      budget=cfg["budget_rows"], donate=None, index=False,
                      zoned=zoned)
    radius = jnp.asarray(trf.radius, jnp.float32)
    embeds = jax.device_put(list(trf.q_embed))
    centers = jax.device_put(list(trf.q_center))
    # a viewer asks among the objects it can be sent: seen often enough
    specs = [Query(embed=e, near=(c, radius), min_obs=min_obs, k=trf.k)
             for e, c in zip(embeds, centers)]
    warm = [Query(embed=jnp.asarray(basis[0], jnp.float32),
                  near=(jnp.asarray(trf.poses_at(0.0)[0]), radius),
                  min_obs=min_obs, k=trf.k)] * cfg["query_batch"]
    rec = Recorder(trf, orbits, classes, keys, warm)
    rec.specs = specs
    rec.content_sel = jnp.zeros((0,), jnp.int32)
    rec.wrap(srv)
    rec.wrap_mapper(mapper)
    poses0 = trf.poses_at(0.0)
    for c in range(C):
        srv.join(c, poses0[c], cfg["subscribe_radius_m"])
    loop = ServingLoop(server=srv, store=SnapshotStore.of(mapper.store),
                       ingest=rec, loadgen=rec, mapper=mapper, overlap=True,
                       batch_size=cfg["query_batch"],
                       max_batches_per_tick=cfg["query_batches_per_tick"],
                       subscribe_radius=cfg["subscribe_radius_m"])
    rec.loop = loop
    # the warm orbit: every mapper's keyframes in orbit order, handed out
    # in groups of growing size so that every width the window meets
    # compiles here
    order = [(m, j) for j in range(trf_cfg["keyframes"]["orbit_keyframes"])
             for m in range(cfg["mappers"])]
    g = 0
    while order:
        n = WARM_GROUPS[g % len(WARM_GROUPS)]
        rec.warm_kf.append(order[:n])
        order, g = order[n:], g + 1
    return loop, srv, rec, trf, orbits, classes, basis, n_warm


def run(ctx) -> dict:
    cfg, seconds = ctx.config, ctx.seconds
    loop, srv, rec, trf, orbits, classes, basis, n_warm = _build(cfg, ctx)
    cap = ctx.traffic["settle_cap_ticks"]
    # the keyframes the reference checks: the warm orbit's last (so the
    # capture compiles in set-up) and a sample of the window's
    n_kf = len(trf.kf_due)
    pick = tg.derive_seed(ctx.seed, "keyframe-sample")
    rec.sample_at = {n_warm - 1} | set((n_warm + pick.choice(
        n_kf, size=min(ctx.traffic["check_keyframes"], n_kf),
        replace=False)).tolist())
    n_groups = len(rec.warm_kf)
    for _ in range(n_groups):
        venue._tick(loop, srv, rec)
    rec.warm_due = 4
    catchup = venue._settle(loop, srv, rec, cap)
    jax.block_until_ready(loop.store.front.active)
    ctx.log(f"set-up: {n_warm} warm keyframes in {n_groups} ticks, "
            f"{catchup} settle ticks")

    ctx.start_window()
    rec.t0 = ctx.window_t0
    first_window_tick = loop.tick_idx
    trace_from = seconds - ctx.trace_seconds if ctx.trace else None
    while True:
        el = time.perf_counter() - rec.t0
        if el >= seconds:
            break
        if trace_from is not None and el >= trace_from:
            ctx.trace_start()
            trace_from = None
        venue._tick(loop, srv, rec)
    window_ticks = loop.tick_idx - first_window_tick
    ctx.end_window()
    behind = n_kf - sum(1 for i in rec.kf_tick if i >= n_warm)
    rec.end_tau = seconds
    n_q = len(trf.q_due)
    drain_cap = ctx.traffic["drain_cap_s"]
    while time.perf_counter() - rec.t0 < seconds + drain_cap:
        if (rec.q_next == n_q and len(rec.done_at) == n_q
                and rec.kf_next == n_kf
                and len(rec.records) == rec.n_handed and rec.quiet()):
            break
        venue._tick(loop, srv, rec)
    drain_s = time.perf_counter() - rec.t0 - seconds
    peak = ctx.memory_peak()

    # -- everything the reference needs, then free the program's state
    zones = [{k: np.asarray(getattr(zs, k)) for k in (
        "ids", "active", "deleted", "version", "label", "n_points",
        "centroid", "embed")} for zs in srv.zoned.zones]
    for p in rec.packets:
        for k in ("oid", "version", "valid"):
            p[k] = np.asarray(p[k])
    results = {i: loop.results[rid] for i, rid in rec.rid_of.items()
               if rid in loop.results}
    q_lat = [(rec.done_at[rid] - rec.due_of[rid]) * 1e3
             for rid in rec.due_of if rid in rec.done_at]
    serve_tick = {i: rec.serve_tick.get(rid) for i, rid in rec.rid_of.items()}
    samples = jax.device_get(rec.samples)
    D, E = cfg["max_detections"], cfg["embed_dim"]
    noise = {i: np.asarray(jax.random.normal(rec.keys[i], (D, E)),
                           np.float64) for i in samples}
    records = [rec.records.get(i) for i in range(rec.n_handed)]
    subs, ticks = np.asarray(rec.subs), np.asarray(rec.ticks)
    kf_tick, packets, zones_started = rec.kf_tick, rec.packets, \
        rec.zones_started
    frame_of = rec.frame_of
    mapped_window = sum(1 for i in kf_tick if i >= n_warm)
    del loop, srv, rec
    gc.collect()

    # -- the reference
    picked = []
    served = [i for i in sorted(results) if serve_tick.get(i) is not None]
    if served:
        picked = sorted(tg.derive_seed(ctx.seed, "query-sample").choice(
            served, size=min(N_QUERY_SAMPLE, len(served)), replace=False))
    kf_due = {n_warm + j: trf.kf_due[j] for j in range(n_kf)}
    n_fail_q = n_q - len(q_lat)

    def reference(control: bool):
        assoc = {"score_gap": 0.0, "lift_gap": 0.0, "cent_gap": 0.0,
                 "faults": {}, "checked": 0, "unclear": 0}
        for i, (pre, post_pts) in sorted(samples.items()):
            if records[i] is None:
                continue
            m, pos = frame_of[i]
            got = check.check_keyframe(
                i, orbits[m][pos], classes, noise[i], basis, cfg, pre,
                post_pts, records[i], control=control)
            if not got["clear"]:
                assoc["unclear"] += 1
                continue
            assoc["checked"] += 1
            for k in ("score_gap", "lift_gap", "cent_gap"):
                assoc[k] = max(assoc[k], got[k])
            for k, v in got["faults"].items():
                assoc["faults"][k] = assoc["faults"].get(k, 0) + v
        ref = check.Reference(cfg, trf, records, np.arange(cfg["clients"]),
                              pre={i: s[0] for i, s in samples.items()})
        values = ref.replay(ticks=ticks, subs=subs,
                            zones_started=zones_started, packets=packets,
                            zones=zones, control=control,
                            queries={i: (serve_tick[i], results[i])
                                     for i in picked})
        upd = check.update_latencies(ref, kf_due, kf_tick, subs, packets,
                                     window_t0=ctx.window_t0)
        checks = {
            "assoc_score_gap": assoc["score_gap"],
            "lift_gap_m": assoc["lift_gap"],
            "centroid_gap_m": assoc["cent_gap"],
            "query_score_gap": values["query_score_gap"],
            "assoc_faults": sum(assoc["faults"].values())
            + sum(ref.record_faults.values()),
            "packet_faults": values["packet_faults"],
            "mirror_faults": values["mirror_faults"],
            "query_faults": values["query_faults"],
            "unmapped_keyframes": n_kf - mapped_window,
            "unanswered_queries": n_fail_q,
            "undelivered_pairs": upd["failed"]}
        return checks, ref, assoc, upd

    checks, ref, assoc, upd = reference(False)
    if ctx.control:
        ctx.control_checks = check.verdict(reference(True)[0])
    ctx.log(f"reference: {ref.counts}; packet faults {ref.faults}; "
            f"record faults {ref.record_faults}; sampled keyframes "
            f"{assoc['checked']} checked, {assoc['unclear']} unclear, "
            f"faults {assoc['faults']}")
    # a queue of keyframes shows as a tail that grows across the window
    half = [stats.percentile([x for x, d in zip(upd["latency_ms"],
                                                 upd["due_s"])
                              if (d >= seconds / 2) == h], 95)
            for h in (False, True)]
    ctx.info = {"settle_ticks": catchup, "window_ticks": window_ticks,
                "keyframes_behind_at_end": behind,
                "update_p95_ms_by_half": half,
                "drain_s": drain_s, "queries_due": n_q,
                "queries_resolved": len(q_lat), "keyframes_due": n_kf,
                "update_pairs": upd["n_pairs"],
                "pairs_dropped": upd["dropped"], "reference": ref.counts,
                "keyframes_checked": assoc["checked"],
                "keyframes_unclear": assoc["unclear"]}
    ctx.log(f"window: {window_ticks} ticks in {seconds} s "
            f"({window_ticks / seconds:.2f} ticks/s), drain {drain_s:.2f} s; "
            f"keyframes due {n_kf} (offered {n_kf / seconds:.2f}/s), "
            f"mapped {mapped_window}; queries due {n_q}, resolved "
            f"{len(q_lat)}; update pairs {upd['n_pairs']} delivered "
            f"{len(upd['latency_ms'])}, dropped {upd['dropped']}")
    return {
        "samples": {"query": q_lat, "update": upd["latency_ms"]},
        "attempted": n_q + upd["n_pairs"],
        "failed": n_fail_q + upd["failed"],
        "checks": check.verdict(checks),
        "memory_peak_bytes": peak,
        "window_ticks": window_ticks,
        "shapes": {"n_slots": cfg["capacity"], "embed_dim": E,
                   "max_detections": D, "server_points": cfg["server_points"],
                   "depth_hw": [cfg["frame_h"] // cfg["depth_ratio"],
                                cfg["frame_w"] // cfg["depth_ratio"]]},
    }
