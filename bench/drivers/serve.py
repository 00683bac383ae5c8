"""Driver ``serve``: the venue's served tick under open-loop wall-clock load.

One ``repro.serving.loop.ServingLoop`` (overlapped schedule) runs as fast
as it can while three open-loop streams fall due on the wall clock:

- queries, per-client MMPP, enter through the loop's ``loadgen`` seam: the
  adapter below hands the loop only the arrivals already due and records
  their due times in place of the loop's own submit times;
- ingest rows, in keyframe-sized groups, enter through the ``ingest``
  seam as one fixed-width ``IngestDelta`` whose ``valid`` mask holds the
  rows due since the last tick (a row that does not fit waits a tick);
- poses: every tick reports each client's pose at the current time.

Delivered rows are read where ``FleetServer.tick_finish`` returns its
packets.  A query is timed from its due time to its resolved result on
the host; a (client, object version) pair from the row's due time to the
framed packet that first gives the client that version or a newer one.

Set-up makes the store and the row stream on the device from the seed,
joins every client and ticks, with no timed traffic, until every client
has caught up on its zones; then it warms every shape the window uses and
ticks until quiet again.
"""
from __future__ import annotations

import collections
import gc
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from bench import generators as tg
from bench.checks import serve as check

from repro.core.knobs import Knobs
from repro.core.query import Query
from repro.core.store import ObjectStore, SnapshotStore
from repro.obs.trace import span as obs_span
from repro.server.fleet import FleetServer
from repro.server.zones import ZoneGrid, ZoneShardedStore
from repro.serving.loop import IngestDelta, ServingLoop

CONTENT_EVERY = 16     # sampled clients' packet rows are kept every n ticks
N_CONTENT_CLIENTS = 8  # clients whose packet rows are kept
N_REPLAY_CLIENTS = 16  # clients whose sync state the reference replays
N_QUERY_SAMPLE = 64    # queries compared with the reference


# ---------------------------------------------------------------------------
# data made on the device from the seed
# ---------------------------------------------------------------------------
def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def row_points(key, rows, P: int):
    """[len(rows), P, 3] point clouds; row ``i`` of a table is always
    drawn from ``fold_in(key, i)``, so any row can be drawn again alone."""
    return jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                                (P, 3)))(rows)


@partial(jax.jit, static_argnames=("n_live", "cap", "E", "P", "n_labels",
                                   "half_m", "height_m"))
def make_store(key, *, n_live, cap, E, P, n_labels, half_m, height_m):
    ke, kp, kc, kl, kn = jax.random.split(key, 5)
    rows = jnp.arange(cap)
    live = rows < n_live
    lo = jnp.asarray([-half_m, 0.0, -half_m], jnp.float32)
    hi = jnp.asarray([half_m, height_m, half_m], jnp.float32)
    z = jnp.zeros((cap,), jnp.int32)
    return ObjectStore(
        ids=jnp.where(live, rows + 1, 0).astype(jnp.int32),
        active=live,
        embed=jnp.where(live[:, None], _unit(jax.random.normal(ke, (cap, E))),
                        0.0),
        label=jnp.where(live, jax.random.randint(kl, (cap,), 0, n_labels), 0),
        points=jnp.where(live[:, None, None], row_points(kp, rows, P), 0.0),
        n_points=jnp.where(live, jax.random.randint(kn, (cap,), 4, P), 0),
        centroid=jnp.where(live[:, None],
                           jax.random.uniform(kc, (cap, 3), minval=lo,
                                              maxval=hi), 0.0),
        bbox_min=jnp.zeros((cap, 3), jnp.float32),
        bbox_max=jnp.zeros((cap, 3), jnp.float32),
        obs_count=jnp.where(live, 3, 0).astype(jnp.int32),
        version=jnp.where(live, 1, 0).astype(jnp.int32),
        last_seen=z, next_id=jnp.asarray(n_live + 1, jnp.int32),
        deleted=jnp.zeros((cap,), bool))


@partial(jax.jit, static_argnames=("E", "P", "n_labels", "width"))
def make_stream(key, slots, tomb, home, drift_m, *, E, P, n_labels, width):
    """Row columns of the ingest stream (one row per object update, then
    ``width`` rows of padding so that every slice of ``width`` rows
    exists) and a pool of ``width`` point clouds that row ``i`` takes
    ``i % width`` from."""
    ke, kc, kn, kl, kp = jax.random.split(key, 5)
    R = slots.shape[0]

    def padded(x):
        return jnp.concatenate([x, jnp.zeros((width,) + x.shape[1:],
                                             x.dtype)])
    return dict(
        slots=padded(slots), tomb=padded(tomb),
        embed=padded(_unit(jax.random.normal(ke, (R, E)))),
        centroid=padded(home[slots]
                        + drift_m * jax.random.normal(kc, (R, 3))),
        n_points=padded(jax.random.randint(kn, (R,), 4, P)),
        label=padded(jax.random.randint(kl, (R,), 0, n_labels)),
        pool=row_points(kp, jnp.arange(width), P))


@partial(jax.jit, static_argnames=("width",))
def take_rows(stream, start, n, *, width):
    """Rows [start, start + n) of the stream as one ``IngestDelta`` of
    ``width`` rows (the rest masked off by ``valid``)."""
    def cut(x):
        return jax.lax.dynamic_slice_in_dim(x, start, width)
    idx = start + jnp.arange(width)
    pool = stream["pool"]
    return IngestDelta(
        slots=cut(stream["slots"]), embed=cut(stream["embed"]),
        centroid=cut(stream["centroid"]),
        points=pool[idx % pool.shape[0]],
        n_points=cut(stream["n_points"]), label=cut(stream["label"]),
        tomb=cut(stream["tomb"]), valid=jnp.arange(width) < n)


@jax.jit
def packet_rows(batch, sel):
    """Rows of the sampled clients ``sel`` of one zone's packets."""
    return jax.tree.map(lambda x: x[sel], batch)


# ---------------------------------------------------------------------------
# traffic drawn on the host from the seed
# ---------------------------------------------------------------------------
class Traffic:
    """Every timed item of one run, with its due time (s from the start
    of the window)."""

    def __init__(self, cfg: dict, trf: dict, seed: int, seconds: float):
        C = cfg["clients"]
        shape = np.random.default_rng(trf["shape_seed"])
        anchor = tg.anchors(shape, C, cfg["room_m"])
        phase = shape.uniform(0.0, 2 * np.pi, size=C)
        q = trf["queries"]
        due, who = tg.mmpp_arrivals(shape, C, seconds, q["base_hz"],
                                    q["burst_factor"], q["burst_entry_hz"],
                                    q["burst_dwell_s"])
        ing = trf["ingest"]
        kf_due, _ = tg.periodic_events(shape, ing["mappers"],
                                       ing["keyframe_hz"], seconds)
        # the seed decides who plays which timeline, and the content
        perm = tg.derive_seed(seed, "clients").permutation(C)
        self.anchor, self.phase = anchor[perm], phase[perm]
        self.q_due = due
        self.q_client = np.argsort(perm)[who]
        self.pose = trf["poses"]
        # a query is asked around where its client stands when it is due
        p = self.pose
        ang = self.phase[self.q_client] \
            + (p["walk_m_per_s"] / p["orbit_m"]) * due
        self.q_center = (self.anchor[self.q_client] + np.stack(
            [p["orbit_m"] * np.cos(ang), np.zeros_like(ang),
             p["orbit_m"] * np.sin(ang)], axis=1)).astype(np.float32)
        qr = tg.derive_seed(seed, "queries")
        e = qr.normal(size=(len(due), cfg["embed_dim"])).astype(np.float32)
        self.q_embed = e / np.linalg.norm(e, axis=1, keepdims=True)
        self.k, self.radius = q["k"], q["near_radius_m"]
        # ingest rows: warm-up rows first, then the window's keyframes
        self.width = ing["max_rows_per_tick"]
        self.drift_m = ing["drift_m"]
        self.warm_counts = [self.width >> i for i in range(5)]
        n_warm = sum(self.warm_counts)
        self.row_due = np.concatenate([
            np.full(n_warm, -np.inf),
            np.repeat(kf_due, ing["rows_per_keyframe"])])
        rr = tg.derive_seed(seed, "rows")
        order = rr.permutation(cfg["live_objects"])
        self.row_slot = order[np.arange(len(self.row_due))
                              % cfg["live_objects"]].astype(np.int32)
        self.row_tomb = rr.random(len(self.row_due)) < ing["tombstone_share"]
        self.n_warm = n_warm

    def poses_at(self, tau: float) -> np.ndarray:
        p = self.pose
        return tg.orbit_poses(self.anchor, self.phase, p["orbit_m"],
                              p["walk_m_per_s"], tau)


# ---------------------------------------------------------------------------
# the seams: what the loop sees of the traffic, and what it delivered
# ---------------------------------------------------------------------------
class Recorder:
    """The loop's ``loadgen`` and ``ingest``, plus the record of every
    tick that the reference replays."""

    def __init__(self, trf: Traffic, stream, warm_specs):
        self.trf, self.stream = trf, stream
        self.loop = None
        self.t0 = None                 # perf_counter of the window start
        self.end_tau = None            # poses freeze here in the drain
        self.warm_specs = warm_specs
        self.warm_due = 0              # warm batches still to hand out
        self.warm_rows = collections.deque()
        self.specs = None
        self.q_next = 0                # next query not yet handed out
        self.fifo = collections.deque()
        self.due_of, self.serve_tick, self.done_at = {}, {}, {}
        self.rid_of = {}               # query index -> rid
        self.row_next = 0              # next stream row not yet handed out
        self.ticks = []                # per tick: [tau, row_start, n_rows]
        self.subs = []                 # per tick: [C, Z] subscriptions
        self.zones_started = {}        # issue tick -> [zones]
        self.issue_fifo = collections.deque()
        self.packets = []              # framed packets, in framing order
        self.content_sel = None

    # -- ServingLoop.loadgen ----------------------------------------------
    @property
    def arrivals(self):
        return self

    def __getitem__(self, t):
        out = []
        if self.warm_due:
            self.warm_due -= 1
            for spec in self.warm_specs:
                self.fifo.append(None)
                out.append((0, spec))
        if self.t0 is not None:
            now = time.perf_counter() - self.t0
            stop = np.searchsorted(self.trf.q_due, now, side="right")
            for i in range(self.q_next, stop):
                self.fifo.append(i)
                out.append((int(self.trf.q_client[i]), self.specs[i]))
            self.q_next = stop
        return out

    def poses(self, t):
        if self.t0 is None:
            tau = 0.0
        elif self.end_tau is not None:
            tau = self.end_tau
        else:
            tau = time.perf_counter() - self.t0
        self.ticks.append([tau, 0, 0])
        return self.trf.poses_at(tau)

    def note_submit(self, rid, wall):
        i = self.fifo.popleft()
        if i is not None:
            self.due_of[rid] = self.t0 + self.trf.q_due[i]
            self.rid_of[i] = rid

    def note_served(self, rid, wall):
        if rid in self.due_of:
            self.serve_tick[rid] = self.loop.tick_idx

    def note_resolved(self, rid, wall):
        if rid in self.due_of:
            self.done_at[rid] = wall

    # -- ServingLoop.ingest -------------------------------------------------
    def delta_at(self, t):
        if self.warm_rows:
            n = self.warm_rows.popleft()
        elif self.t0 is not None:
            now = time.perf_counter() - self.t0
            due = np.searchsorted(self.trf.row_due, now, side="right")
            n = min(due - self.row_next, self.trf.width)
        else:
            n = 0
        n, start = int(n), self.row_next
        self.row_next += n
        self._rows = (start, n)
        return take_rows(self.stream, start, n, width=self.trf.width)

    # -- FleetServer.tick_start / tick_finish ------------------------------
    def wrap(self, srv):
        start_real, finish_real = srv.tick_start, srv.tick_finish

        def tick_start(deliverable, *, tick=None):
            started = start_real(deliverable, tick=tick)
            t = self.loop.tick_idx
            self.zones_started[t] = [z for z, _ in started]
            self.issue_fifo.append(t)
            return started

        def tick_finish(started):
            t = self.issue_fifo.popleft()
            out = finish_real(started)
            wall = time.perf_counter()
            keep = t % CONTENT_EVERY == 0
            for z, pkt in out:
                b = pkt.batch
                self.packets.append(dict(
                    tick=t, zone=z, wall=wall, counts=pkt.counts,
                    nbytes=pkt.nbytes, oid=b.oid, version=b.version,
                    valid=b.valid,
                    content=packet_rows(b, self.content_sel) if keep
                    else None))
            return out

        ack_real = srv.ack_tick

        def ack_tick(packets, *, tick):
            # the acks get a span of their own: the loop calls them
            # outside every span the program has
            with obs_span("fleet.ack_tick", cat="sync"):
                return ack_real(packets, tick=tick)

        srv.tick_start, srv.tick_finish = tick_start, tick_finish
        srv.ack_tick = ack_tick

    def after_tick(self, srv):
        self.ticks[-1][1:] = self._rows
        self.subs.append(srv.subscribed.copy())

    def quiet(self, n: int = 2) -> bool:
        """No zone collected in the last ``n`` ticks."""
        t = self.loop.tick_idx
        return t >= n and all(not self.zones_started.get(t - 1 - i)
                              for i in range(n))


# ---------------------------------------------------------------------------
def _build(cfg: dict, trf: Traffic, seed: int):
    C, E, P = cfg["clients"], cfg["embed_dim"], cfg["server_points"]
    key = jax.random.key(int(np.random.SeedSequence(
        int(seed) % (2 ** 63)).generate_state(1)[0]))
    k_store, k_stream = jax.random.split(key)
    store = make_store(k_store, n_live=cfg["live_objects"],
                       cap=cfg["capacity"], E=E, P=P,
                       n_labels=cfg["labels"], half_m=cfg["object_half_m"],
                       height_m=cfg["object_height_m"])
    stream = make_stream(k_stream, jnp.asarray(trf.row_slot),
                         jnp.asarray(trf.row_tomb), store.centroid,
                         jnp.float32(trf.drift_m), E=E, P=P,
                         n_labels=cfg["labels"], width=trf.width)
    kn = Knobs(server_capacity=cfg["capacity"],
               client_capacity=max(2 * cfg["budget_rows"], 64),
               max_object_points_server=P,
               max_object_points_client=cfg["client_points"],
               min_obs_before_sync=1)
    nx, nz = cfg["zones"]
    grid = ZoneGrid.for_room(cfg["room_m"], nx, nz)
    zoned = ZoneShardedStore(knobs=kn, embed_dim=E, grid=grid,
                             zone_capacity=cfg["zone_capacity"])
    srv = FleetServer(knobs=kn, embed_dim=E, n_clients=C, grid=grid,
                      budget=cfg["budget_rows"], donate=None, index=False,
                      zoned=zoned)
    # queries exactly as the program's own load generator builds them
    radius = jnp.asarray(trf.radius, jnp.float32)
    embeds = jax.device_put(list(trf.q_embed))
    centers = jax.device_put(list(trf.q_center))
    specs = [Query(embed=e, near=(c, radius), k=trf.k)
             for e, c in zip(embeds, centers)]
    warm = [Query(embed=jnp.asarray(trf.q_embed[0] if len(trf.q_embed)
                                    else np.eye(E, dtype=np.float32)[0]),
                  near=(jnp.asarray(trf.poses_at(0.0)[0]), radius), k=trf.k)
            ] * cfg["query_batch"]
    rec = Recorder(trf, stream, warm)
    rec.specs = specs
    sel = tg.derive_seed(seed, "sample").choice(
        C, size=min(N_REPLAY_CLIENTS, C), replace=False)
    rec.replay_clients = np.sort(sel)
    rec.content_clients = rec.replay_clients[:N_CONTENT_CLIENTS]
    rec.content_sel = jnp.asarray(rec.content_clients, jnp.int32)
    rec.wrap(srv)
    poses0 = trf.poses_at(0.0)
    for c in range(C):
        srv.join(c, poses0[c], cfg["subscribe_radius_m"])
    loop = ServingLoop(server=srv, store=SnapshotStore.of(store),
                       ingest=rec, loadgen=rec, overlap=True,
                       batch_size=cfg["query_batch"],
                       max_batches_per_tick=cfg["query_batches_per_tick"],
                       subscribe_radius=cfg["subscribe_radius_m"])
    rec.loop = loop
    return loop, srv, rec, k_store


def _tick(loop, srv, rec):
    # in a traced run, host time inside a tick that no span of the program
    # covers is named after this span
    with obs_span("loop.tick", cat="bench"):
        loop.tick()
    rec.after_tick(srv)


def _settle(loop, srv, rec, cap_ticks: int) -> int:
    n = 0
    while not rec.quiet() and n < cap_ticks:
        _tick(loop, srv, rec)
        n += 1
    if not rec.quiet():
        raise RuntimeError(f"the venue did not settle in {cap_ticks} ticks")
    return n


def run(ctx) -> dict:
    cfg, seconds = ctx.config, ctx.seconds
    trf = Traffic(cfg, ctx.traffic, ctx.seed, seconds)
    loop, srv, rec, k_store = _build(cfg, trf, ctx.seed)
    cap = ctx.traffic["settle_cap_ticks"]
    catchup = _settle(loop, srv, rec, cap)
    # warm: every ingest width the window can hand out and full query
    # batches, then quiet again
    rec.warm_rows.extend(trf.warm_counts)
    rec.warm_due = len(trf.warm_counts)
    for _ in trf.warm_counts:
        _tick(loop, srv, rec)
    settle = _settle(loop, srv, rec, cap)
    jax.block_until_ready(loop.store.front.active)
    ctx.log(f"set-up: {catchup} catch-up ticks, {len(trf.warm_counts)} "
            f"warm ticks, {settle} settle ticks")

    tracer = ctx.start_window()
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
        _tick(loop, srv, rec)
    window_ticks = loop.tick_idx - first_window_tick
    ctx.end_window()
    rec.end_tau = seconds
    n_q = len(trf.q_due)
    drain_cap = ctx.traffic["drain_cap_s"]
    while time.perf_counter() - rec.t0 < seconds + drain_cap:
        if (rec.q_next == n_q and len(rec.done_at) == n_q
                and rec.row_next == len(trf.row_due) and rec.quiet()):
            break
        _tick(loop, srv, rec)
    drain_s = time.perf_counter() - rec.t0 - seconds
    peak = ctx.memory_peak()

    # -- everything the reference needs, then free the program's state
    pick = tg.derive_seed(ctx.seed, "zone-sample")
    zones = []
    for zs in srv.zoned.zones:
        host = {k: np.asarray(getattr(zs, k)) for k in (
            "ids", "active", "deleted", "version", "label", "n_points",
            "centroid", "embed")}
        occ = np.nonzero(host["active"])[0]
        rows = np.sort(pick.choice(occ, size=min(16, len(occ)),
                                   replace=False))
        pts = np.asarray(zs.points[jnp.asarray(rows)])
        host["points_sample"] = dict(zip(rows.tolist(), pts))
        zones.append(host)
    for p in rec.packets:
        for k in ("oid", "version", "valid"):
            p[k] = np.asarray(p[k])
        if p["content"] is not None:
            p["content"] = jax.tree.map(np.asarray, p["content"])
    results = {}
    for i, rid in rec.rid_of.items():
        if rid in loop.results:
            results[i] = loop.results[rid]
    q_lat = [(rec.done_at[rid] - rec.due_of[rid]) * 1e3
             for rid in rec.due_of if rid in rec.done_at]
    serve_tick = {i: rec.serve_tick.get(rid) for i, rid in rec.rid_of.items()}
    stream = jax.tree.map(np.asarray, rec.stream)
    subs, ticks = np.asarray(rec.subs), np.asarray(rec.ticks)
    zones_started, packets = rec.zones_started, rec.packets
    replay_clients, content_clients = rec.replay_clients, rec.content_clients
    del loop, srv, rec
    gc.collect()

    # the seed's store once more, for the reference (its geometry row by
    # row, on demand)
    st = make_store(k_store, n_live=cfg["live_objects"], cap=cfg["capacity"],
                    E=cfg["embed_dim"], P=cfg["server_points"],
                    n_labels=cfg["labels"], half_m=cfg["object_half_m"],
                    height_m=cfg["object_height_m"])
    init = {k: np.asarray(getattr(st, k)) for k in (
        "active", "version", "centroid", "embed", "label", "n_points")}
    del st
    k_pts = jax.random.split(k_store, 5)[1]
    P = cfg["server_points"]

    def init_points(slots):
        return np.asarray(row_points(k_pts, jnp.asarray(slots, jnp.int32), P))

    ref = check.Reference(cfg, trf, init, init_points, stream,
                          replay_clients, content_clients)
    qs = tg.derive_seed(ctx.seed, "query-sample")
    served = [i for i in sorted(results) if serve_tick.get(i) is not None]
    picked = sorted(qs.choice(served, size=min(N_QUERY_SAMPLE, len(served)),
                              replace=False)) if served else []
    replay = dict(ticks=ticks, subs=subs, zones_started=zones_started,
                  packets=packets, zones=zones,
                  queries={i: (serve_tick[i], results[i]) for i in picked})
    values = ref.replay(**replay)
    ctx.log(f"reference: {ref.counts}; packet faults {ref.faults}")
    upd = check.update_latencies(ref, trf, ticks, subs, packets,
                                 window_t0=ctx.window_t0)
    n_fail_q = n_q - len(q_lat)
    delivery = {"unanswered_queries": n_fail_q,
                "undelivered_pairs": upd["failed"]}
    if ctx.control:
        ctx.control_checks = check.verdict(dict(check.Reference(
            cfg, trf, init, init_points, stream, replay_clients,
            content_clients).replay(**replay, control=True), **delivery))
    ctx.info = {"catchup_ticks": catchup, "settle_ticks": settle,
                "window_ticks": window_ticks, "drain_s": drain_s,
                "queries_due": n_q, "queries_resolved": len(q_lat),
                "rows_due": len(trf.row_due) - trf.n_warm,
                "update_pairs": upd["n_pairs"], "pairs_dropped":
                upd["dropped"], "reference": ref.counts,
                "faults": ref.faults}
    ctx.log(f"window: {window_ticks} ticks in {seconds} s "
            f"({window_ticks / seconds:.2f} ticks/s), drain {drain_s:.2f} s; "
            f"queries due {n_q} (offered {n_q / seconds:.1f}/s), resolved "
            f"{len(q_lat)}; ingest rows due {len(trf.row_due) - trf.n_warm} "
            f"(offered {(len(trf.row_due) - trf.n_warm) / seconds:.1f}/s); "
            f"update pairs {upd['n_pairs']} delivered "
            f"{len(upd['latency_ms'])}, dropped {upd['dropped']}")
    return {
        "samples": {"query": q_lat, "update": upd["latency_ms"]},
        "attempted": n_q + upd["n_pairs"],
        "failed": n_fail_q + upd["failed"],
        "checks": check.verdict(dict(values, **delivery)),
        "memory_peak_bytes": peak,
        "window_ticks": window_ticks,
        "shapes": {"n_slots": cfg["capacity"], "embed_dim": cfg["embed_dim"],
                   "query_batch": cfg["query_batch"]},
    }
