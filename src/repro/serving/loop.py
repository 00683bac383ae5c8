"""Async pipelined serving loop: overlapped ingest / fleet-sync / query.

Every tick of the repo's drivers used to be strictly synchronous —
ingest scatter, ``block_until_ready``, fleet collect, ``np.asarray`` the
counts, query dispatch, materialize — so the device idled while Python
did bookkeeping and Python idled while the device computed.  This loop
issues all three dispatch families against one consistent snapshot and
lets JAX's async dispatch overlap them:

- **Ingest** writes the NEXT store generation.  The ingest seam yields,
  each tick, either one ``IngestDelta`` of pre-drawn rows or the
  keyframes now due (``Keyframe``), which the loop maps one by one in due
  order: host detect, then the mapper's one fused ``ingest_frame``
  dispatch.  What the seam yields picks the path.  Overlapped mode
  donates the dead back buffer of the ``SnapshotStore`` double buffer
  (``core.store``) and catches it up IN PLACE before this tick's ingest
  runs on it — by replaying the delta that produced the front, or by
  copying from the front the rows the front's keyframes wrote (their
  ``KeyframeRecord``s) — O(changed rows) per tick instead of the
  O(capacity) full-store copy the synchronous functional update pays.
  Queries keep reading the published front buffer, so a request served
  mid-ingest sees exactly the pre-tick store, never a torn mix.
- **Fleet sync** issues every dirty zone's ``_collect_fleet`` dispatch
  before materializing any packet (``SessionManager.collect_start`` /
  ``collect_finish``), with the [C, N] sync state donated.
- **Queries** drain from the ``BatchScheduler`` with a non-blocking step
  fn (``PendingResult`` handles): each batch's host copy starts at
  dispatch, and the loop resolves the results a tick later with one
  blocking read per batch, not one per result.
- **Publish** swaps the double buffer; the loop's cluster index (when
  enabled) is maintained against the publish buffer from the delta's
  touched slots, so a two-stage plan stays exact against the snapshot.

The synchronous mode runs the identical workload — same deltas, same
collect inputs, same query stream — with a fence after every dispatch
and the copying (non-donated) ingest, which is precisely the loop the
drivers run today.  Both modes serve queries against the post-previous-
tick snapshot, so their per-query results are byte-identical; the
benchmark (benchmarks/serving_loop.py) asserts that and measures the
throughput gap.
"""
from __future__ import annotations

import collections
import functools
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.store import (ObjectStore, SnapshotStore, copy_store,
                              deleted_mask)
from repro.obs import metrics as obs_metrics
from repro.obs.trace import get_tracer, span as obs_span
from repro.serving.batching import (BatchScheduler, PendingResult,
                                    make_query_step_fn)


# ---------------------------------------------------------------------------
# The ingest scatter: a seeded stream of per-tick mapping deltas (the
# mapping frontend's output, pre-drawn so two loop variants replay the
# identical workload).
# ---------------------------------------------------------------------------
class IngestDelta(NamedTuple):
    """One tick's store mutations, SoA with a fixed row budget U."""
    slots: jax.Array      # [U] int32 target store slots (unique per tick)
    embed: jax.Array      # [U, E] f32 unit-norm
    centroid: jax.Array   # [U, 3] f32
    points: jax.Array     # [U, P, 3] f32
    n_points: jax.Array   # [U] int32
    label: jax.Array      # [U] int32
    tomb: jax.Array       # [U] bool — row is a removal (tombstone)
    valid: jax.Array      # [U] bool


def _apply_delta_impl(store: ObjectStore, d: IngestDelta) -> ObjectStore:
    """Scatter one delta into the store (padding rows dropped via OOB).

    Upserts refresh geometry/embedding and clear any tombstone (respawn);
    tombstone rows mirror ``_tombstone_slots`` semantics (active off,
    deleted on, geometry zeroed).  Every touched row's version bumps so
    the sync protocol ships it."""
    cap = store.ids.shape[0]
    up = d.valid & ~d.tomb
    tb = d.valid & d.tomb
    tg_all = jnp.where(d.valid, d.slots, cap)
    tg_up = jnp.where(up, d.slots, cap)
    tg_tb = jnp.where(tb, d.slots, cap)
    return store._replace(
        active=store.active.at[tg_up].set(True, mode="drop")
                           .at[tg_tb].set(False, mode="drop"),
        deleted=deleted_mask(store).at[tg_up].set(False, mode="drop")
                                   .at[tg_tb].set(True, mode="drop"),
        embed=store.embed.at[tg_up].set(d.embed, mode="drop"),
        label=store.label.at[tg_up].set(d.label, mode="drop"),
        points=store.points.at[tg_up].set(d.points, mode="drop"),
        n_points=store.n_points.at[tg_up].set(d.n_points, mode="drop")
                               .at[tg_tb].set(0, mode="drop"),
        centroid=store.centroid.at[tg_up].set(d.centroid, mode="drop"),
        obs_count=store.obs_count.at[tg_all].add(1, mode="drop"),
        version=store.version.at[tg_all].add(1, mode="drop"))


# today's path: functional update — XLA must preserve the input store, so
# every [cap, ...] column is copied per tick
apply_delta = jax.jit(_apply_delta_impl)


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply_delta2_donated(back: ObjectStore, pending: IngestDelta,
                          cur: IngestDelta) -> ObjectStore:
    """Catch the donated two-tick-old back buffer up: apply the delta that
    produced the current front, then this tick's — in place."""
    return _apply_delta_impl(_apply_delta_impl(back, pending), cur)


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply_delta_donated(back: ObjectStore, cur: IngestDelta) -> ObjectStore:
    """First overlapped tick: back is still a clone of front (no pending)."""
    return _apply_delta_impl(back, cur)


CATCH_UP_RECORDS = 4     # keyframe records per row-copy dispatch


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_rows_donated(back: ObjectStore, front: ObjectStore,
                       slots: tuple) -> ObjectStore:
    """Catch the donated back buffer up to ``front`` where keyframes of
    one tick wrote (``slots``: their records' [D] slot arrays; cap =
    padding, dropped): the row columns (embedding, points) at those slots,
    every per-slot scalar column whole — the prune can turn off any slot,
    and those columns are a few bytes a slot."""
    slots = jnp.concatenate(slots)
    take = jnp.minimum(slots, front.ids.shape[0] - 1)
    # copies, not the front's own buffers: jit would hand an unchanged
    # input straight back, and the next ingest donates this generation
    return jax.tree.map(jnp.copy, front)._replace(
        embed=back.embed.at[slots].set(front.embed[take], mode="drop"),
        points=back.points.at[slots].set(front.points[take], mode="drop"))


class Keyframe(NamedTuple):
    """One due keyframe, as the ingest seam hands it to the loop."""
    frame: object          # repro.data.scenes.Frame: posed depth + instances
    classes: dict          # object id -> class id (the detector stand-in)
    key: jax.Array         # the embedder's noise key
    mapper: int            # the headset that sent it
    due: float | None = None   # perf_counter due time, when scheduled


@dataclass
class IngestStream:
    """Seeded per-tick delta schedule over a store's live region.

    Each tick touches ``churn`` distinct slots drawn from ``[0, n_live)``:
    mostly upserts (drifted centroid, re-embedded, fresh cloud), a
    ``tomb_prob`` fraction tombstones.  A slot tombstoned at tick t may be
    re-upserted later (respawn) — versions only ever advance, so the sync
    protocol stays monotonic.  All tensors are pre-staged on device as
    [T, U, ...] stacks; ``delta_at`` is a cheap device slice."""
    n_ticks: int
    n_live: int
    embed_dim: int
    max_points: int
    churn: int = 64
    tomb_prob: float = 0.05
    drift: float = 0.15            # per-touch centroid drift (m)
    room: float = 16.0
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        T, U, E, P = self.n_ticks, self.churn, self.embed_dim, \
            self.max_points
        slots = np.stack([rng.choice(self.n_live, size=U, replace=False)
                          for _ in range(T)]).astype(np.int32)
        emb = rng.normal(size=(T, U, E)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=-1, keepdims=True)
        # touched rows drift instead of teleporting: anchor to a per-slot
        # home so zone routing changes occasionally, not constantly
        half = self.room / 2
        home = rng.uniform(-half, half,
                           size=(self.n_live, 3)).astype(np.float32)
        home[:, 1] = rng.uniform(0.0, 2.0, size=self.n_live)
        cent = home[slots] + self.drift * rng.normal(
            size=(T, U, 3)).astype(np.float32)
        pts = rng.normal(size=(T, U, P, 3)).astype(np.float32)
        npts = rng.integers(4, P, size=(T, U)).astype(np.int32)
        lab = rng.integers(0, 20, size=(T, U)).astype(np.int32)
        tomb = rng.random(size=(T, U)) < self.tomb_prob
        self._stack = IngestDelta(
            slots=jnp.asarray(slots), embed=jnp.asarray(emb),
            centroid=jnp.asarray(cent), points=jnp.asarray(pts),
            n_points=jnp.asarray(npts), label=jnp.asarray(lab),
            tomb=jnp.asarray(tomb),
            valid=jnp.ones((T, U), bool))

    def delta_at(self, t: int) -> IngestDelta:
        return IngestDelta(*(x[t] for x in self._stack))


# ---------------------------------------------------------------------------
@dataclass
class ServingLoop:
    """Event-driven serving tick over (SnapshotStore, FleetServer, queries).

    One tick, in both modes, does the same logical work against the same
    snapshot (the store published at the END of the previous tick):

      1. issue the ingest producing the next generation: the seam's
         ``IngestDelta`` scatter, or its due keyframes mapped in order
      2. mirror the snapshot into the fleet zones + collect dirty zones
      3. submit this tick's query arrivals; run scheduler steps
      4. publish the new generation; resolve query results

    ``overlap=False`` fences after every dispatch (today's loop);
    ``overlap=True`` fences only at result resolution.
    """
    server: object                    # FleetServer
    store: SnapshotStore
    ingest: object                    # the seam: delta_at(t) -> an
    #                                   IngestDelta or a list of Keyframes;
    #                                   note_mapped(t, records) where
    #                                   keyframes were mapped
    loadgen: object = None            # LoadGenerator | None
    overlap: bool = True
    batch_size: int = 16
    max_batches_per_tick: int = 2     # service capacity: backlog above this
    subscribe_radius: float = 6.0
    index: object = None              # ClusterIndex over the publish buffer
    mapper: object = None             # MappingServer: maps the keyframes
    #                                   the ingest seam yields
    # measured state
    tick_idx: int = 0
    results: dict = field(default_factory=dict)    # rid -> QueryResult (np)
    tick_ms: list = field(default_factory=list)
    sent_bytes: int = 0
    n_served: int = 0
    scheduler: BatchScheduler = None

    def __post_init__(self):
        self.scheduler = BatchScheduler(
            batch_size=self.batch_size,
            step_fn=make_query_step_fn(
                lambda: self.store.front, pad_to=self.batch_size,
                block=not self.overlap,
                get_index=(lambda: self.index)
                if self.index is not None else None))
        self._mode = "overlapped" if self.overlap else "sync"
        self._deliverable = np.ones((self.server.n_clients,), bool)
        self._carry = {}          # overlap: last tick's unresolved results
        self._sync_started = []   # overlap: issued, unframed fleet collects
        self._mapped = []         # dispatched keyframes, records unread
        self._counts = collections.deque()   # tracing: read records' counts

    def enable_index(self, **kw) -> None:
        """Attach a cluster index maintained against the PUBLISH buffer:
        refreshed from each published delta's touched slots, so two-stage
        plans read the same snapshot flat sweeps do."""
        from repro.index import ClusterIndex
        self.index = ClusterIndex.for_target(self.store.front, **kw)
        self.__post_init__()       # rebuild the step fn with get_index

    # ------------------------------------------------------------------
    def _issue_ingest(self, d) -> tuple:
        """This tick's ingest, from what the seam yielded: an
        ``IngestDelta`` or a list of due ``Keyframe``s.  Returns (the next
        generation, what produced it from the front: the generation's
        ``pending``)."""
        with obs_span("serving.ingest", cat="ingest", mode=self._mode) as sp:
            if isinstance(d, IngestDelta):
                new, pending = self._apply_rows(d), d
            else:
                new, pending = self._map_keyframes(d)
            if not self.overlap:
                jax.block_until_ready(new.active)
            sp.fence(new.active)
        return new, pending

    def _apply_rows(self, d: IngestDelta) -> ObjectStore:
        if not self.overlap:
            return apply_delta(self.store.front, d)
        back = self.store.take_back()
        if isinstance(self.store.pending, IngestDelta):
            return _apply_delta2_donated(back, self.store.pending, d)
        return _apply_delta_donated(self._catch_up(back), d)

    def _catch_up(self, back: ObjectStore) -> ObjectStore:
        """Bring the donated back buffer to the published front where the
        front's keyframes wrote (the generation's ``pending`` holds their
        records): one row copy for a tick of up to ``CATCH_UP_RECORDS``
        keyframes."""
        slots = [rec.slot for _, _, rec in self.store.pending or ()
                 if rec is not None]
        # CATCH_UP_RECORDS records a dispatch, the last repeated (a row
        # copied twice is copied once): one width compiles, whatever a
        # tick maps
        R = CATCH_UP_RECORDS
        for i in range(0, len(slots), R):
            part = slots[i:i + R]
            part += part[-1:] * (R - len(part))
            back = _copy_rows_donated(back, self.store.front, tuple(part))
        return back

    def _map_keyframes(self, kfs: list) -> tuple:
        """Map the due keyframes in order onto the next generation: the
        caught-up back buffer (overlapped), or a copy of the front when
        the mapper donates (sync: the front is read all tick).  The
        transient prune counts keyframes over every mapper: the mapper's
        ``frame_count``, where the single-mapper paper pipeline counts
        one headset's frames."""
        if self.overlap:
            store = self._catch_up(self.store.take_back())
        else:
            store = self.store.front
            if kfs and self.mapper.donate:
                store = copy_store(store)
        mapped = []
        for kf in kfs:
            store, rec = self._map_one(store, kf)
            mapped.append(rec)
        if mapped:
            self._mapped.append((self.tick_idx, mapped))
        return store, mapped

    def _map_one(self, store: ObjectStore, kf: Keyframe) -> tuple:
        """Detect and dispatch one keyframe.  Returns (store, (keyframe
        index, kf, its ``KeyframeRecord`` on the device; None where
        nothing was detected and nothing dispatched))."""
        m = self.mapper
        i = m.frame_count
        m.frame_count += 1
        with obs_span("mapping.detect", cat="ingest") as sp:
            deferred = m.deferred
            inputs, nd = m.prepare(kf.frame, kf.classes)
            if sp.on:
                sp.set(keyframe=i, mapper=kf.mapper, nd=nd,
                       deferred=m.deferred - deferred)
        if inputs is None:
            return store, (i, kf, None)
        with obs_span("mapping.ingest", cat="ingest") as sp:
            if sp.on:
                args = {"keyframe": i}
                if kf.due is not None:
                    args["wait_ms"] = round(
                        (time.perf_counter() - kf.due) * 1e3, 3)
                if self._counts:
                    args.update(self._counts.popleft())
                sp.set(**args)
            store, rec = m.ingest_keyframe(store, inputs, kf.key, i)
            for x in rec:
                x.copy_to_host_async()
        return store, (i, kf, rec)

    def _read_records(self, upto: int) -> None:
        """Bring the records of keyframes mapped at ticks <= ``upto`` to the
        host, all of them with one read on copies started at dispatch, and
        hand them to the ingest seam's ``note_mapped`` (a keyframe with no
        detection, so no dispatch, has the record None)."""
        done = [x for x in self._mapped if x[0] <= upto]
        if not done:
            return
        self._mapped = [x for x in self._mapped if x[0] > upto]
        with obs_span("host.fetch", cat="ingest", what="record") as sp:
            host = jax.device_get([[rec for _, _, rec in mapped]
                                   for _, mapped in done])
            if sp.on:
                sp.set(rows=sum(r is not None for rs in host for r in rs))
        cap = self.store.front.ids.shape[0]
        for (t, mapped), recs in zip(done, host):
            out = [(i, kf, r) for (i, kf, _), r in zip(mapped, recs)]
            self.ingest.note_mapped(t, out)
            if get_tracer() is not None:
                # the counts a later ``mapping.ingest`` span shows
                for i, _, r in out:
                    if r is None:
                        continue
                    ok = r.slot < cap
                    self._counts.append(dict(
                        counts_of=i, matched=int((ok & r.matched).sum()),
                        inserted=int((ok & ~r.matched).sum()),
                        pruned=int(r.n_pruned)))

    def _sync_tick(self, t: int) -> None:
        front = self.store.front
        with obs_span("serving.sync", cat="sync", mode=self._mode):
            if self.loadgen is not None:
                poses = self.loadgen.poses(t)
                if poses is not None:
                    self.server.set_poses(poses, self.subscribe_radius)
            self.server.refresh(front)
            if self.overlap:
                # issue only — framing is deferred a full tick
                # (_finish_sync), giving the collect dispatches the whole
                # tick to complete before any host transfer waits on them.
                # Legal because the sync state chains on-device (FleetSync
                # carries synced_version AND ever_sent).
                self._sync_started.append(
                    (t, self.server.tick_start(self._deliverable, tick=t)))
            else:
                packets = self.server.tick(self._deliverable, tick=t,
                                           overlap=False)
                for _, pkt in packets:
                    # fence via the packet (a mesh-sharded tier fences
                    # every shard's tensors, not one [C, U] batch)
                    pkt.block_until_ready()
                self._account_packets(packets, t)

    def _account_packets(self, packets: list, t: int) -> None:
        self.sent_bytes += sum(p.total_nbytes for _, p in packets)
        # The serving fleet is always-connected: every delivered packet
        # is applied immediately, so ack it the same tick.  This keeps
        # inflight queues O(1) instead of growing over the run (which
        # would make slot-retirement scrubs quadratic in run length).
        self.server.ack_tick(packets, tick=t)

    def _finish_sync(self, upto: int) -> None:
        """Frame every deferred collect issued at tick <= ``upto`` into
        packets (byte-identical to the sequential path: finish runs in
        issue order, and slots freed since issue are scrub-filtered from
        the retirement bookkeeping)."""
        while self._sync_started and self._sync_started[0][0] <= upto:
            t0, started = self._sync_started.pop(0)
            self._account_packets(self.server.tick_finish(started), t0)

    def _query_tick(self, t: int) -> dict:
        out = {}
        with obs_span("serving.query", cat="query", mode=self._mode) as sp:
            now = time.perf_counter()
            if self.loadgen is not None:
                for cid, spec in self.loadgen.arrivals[t]:
                    rid = self.scheduler.submit(spec)
                    self.loadgen.note_submit(rid, now)
            for _ in range(self.max_batches_per_tick):
                if not self.scheduler.waiting:
                    break
                served = self.scheduler.step()
                claim = time.perf_counter()
                if self.loadgen is not None:
                    for rid in served:
                        self.loadgen.note_served(rid, claim)
                out.update(served)
            if sp.on:
                # the queued requests' ages so far: a request the scheduler
                # never picks shows here, not in any ``query.batch``
                end = time.perf_counter()
                queued = self.scheduler.waiting
                sp.set(waiting=len(queued),
                       waiting_ms=[round((end - r.enqueued_at) * 1e3, 1)
                                   for r in queued])
        return out

    def _resolve(self, out: dict) -> None:
        """Materialize this tick's query results — in overlapped mode one
        blocking read per batch, on a copy started at dispatch (it waits
        only on the query dispatches: they read the published front, never
        the in-flight ingest)."""
        with obs_span("serving.resolve", cat="query") as sp:
            if sp.on:
                sp.set(rids=list(out))
            for rid, res in out.items():
                if isinstance(res, PendingResult):
                    res = res.resolve()
                    self.scheduler.done[rid] = res
                self.results[rid] = res
                self.n_served += 1
            if self.loadgen is not None and out:
                done = time.perf_counter()
                for rid in out:
                    self.loadgen.note_resolved(rid, done)

    # ------------------------------------------------------------------
    def tick(self) -> None:
        t = self.tick_idx
        wall0 = time.perf_counter()
        d = self.ingest.delta_at(t)
        new, pending = self._issue_ingest(d)
        self._sync_tick(t)
        out = self._query_tick(t)
        with obs_span("serving.publish", cat="ingest"):
            if self.overlap:
                self.store.publish(new, pending=pending)
            else:
                # synchronous mode never touched the back buffer: swap the
                # front pointer only (the stale clone is never donated)
                self.store.front = new
                self.store.pending = None
                self.store.version += 1
            if self.index is not None:
                # index maintenance rides the publish: update from the
                # delta's touched slots against the NEW publish buffer
                # (keyframes' slots reach the host a tick later: refresh)
                if isinstance(d, IngestDelta):
                    self.index.update_slots(self.store.front,
                                            np.asarray(d.slots))
                else:
                    self.index.refresh(self.store.front)
        if self.overlap:
            # software pipelining: frame LAST tick's packets and resolve
            # LAST tick's queries now, carry this tick's — their device
            # work overlaps the whole next tick's ingest/sync/query
            # dispatch instead of fencing here.
            # Safe vs next tick's donation of the buffer they read: PJRT
            # usage events sequence the donated in-place write after every
            # outstanding read (worst case the runtime copies instead of
            # donating for that tick).  Results are unchanged — the
            # computation captured its inputs at dispatch.
            self._finish_sync(t - 1)
            self._resolve(self._carry)
            self._carry = out
            self._read_records(t - 1)
        else:
            self._resolve(out)
            self._read_records(t)
        self.tick_idx += 1
        self.tick_ms.append((time.perf_counter() - wall0) * 1e3)

    def run(self, n_ticks: int) -> dict:
        for _ in range(n_ticks):
            self.tick()
        # drain: the carried tick, then whatever arrivals are still queued
        self._finish_sync(self.tick_idx)
        self._resolve(self._carry)
        self._carry = {}
        self._read_records(self.tick_idx)
        while self.scheduler.waiting:
            out = self.scheduler.step()
            claim = time.perf_counter()
            if self.loadgen is not None:
                for rid in out:
                    self.loadgen.note_served(rid, claim)
            self._resolve(out)
        jax.block_until_ready(self.store.front.active)
        wall_s = sum(self.tick_ms) / 1e3
        stats = {
            "mode": self._mode,
            "n_ticks": n_ticks,
            "ticks_per_s": n_ticks / max(wall_s, 1e-9),
            "tick_ms": obs_metrics.exact_percentiles(self.tick_ms),
            "n_queries_served": self.n_served,
            "sent_bytes_total": int(self.sent_bytes),
            "store_version": self.store.version,
        }
        if self.loadgen is not None:
            stats.update(self.loadgen.record(self._mode))
        return stats
