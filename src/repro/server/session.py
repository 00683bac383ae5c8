"""Multi-tenant per-client sync: stacked sync vectors, one vmapped collect.

The single-client protocol (core/updates.py) keeps one ``synced_version[N]``
vector per client and builds each client's packet with a host-side pass over
the store.  Serving C clients that way costs C Python-loop iterations and C
dispatches per tick.  Here the fleet's sync state is ONE ``[C, N]`` array
and the whole tick is one jitted dispatch (`_collect_fleet`):

  changed[C, N]  = active & (obs >= min_obs[c]) & (version > synced[c])
                   & subscribed-and-deliverable[c]
  priority[C, N] = vmapped compute_priority over per-client user_pos
  top-k          = per-client budgeted selection (lax.top_k over the
                   priority-masked scores; invalid rows sort last, so live
                   rows form a prefix exactly like the single-client packet)
  gather         = whole-row gather of the selected slots' client clouds
                   (`ClientClouds`: each row's stride downsample, computed
                   once per row write by the zone mirror, not per client)
  sync advance   = vmapped scatter of the shipped versions

Byte accounting matches core/updates.py exactly (same wire format), so the
fleet packets and single-client packets are interchangeable — asserted in
tests/test_fleet.py.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import geometry as geo
from repro.core.knobs import Knobs
from repro.core.local_map import UpdateBatch, compute_priority
from repro.core.store import ObjectStore, deleted_mask
from repro.obs.trace import get_tracer, span as obs_span
from repro.core.updates import (_HEADER_B, PROTO_HEADER_NBYTES,
                                TOMBSTONE_NBYTES, UpdatePacket,
                                class_budget_table)


class FleetSync(NamedTuple):
    """Stacked per-client sync state, all device-resident so consecutive
    collects chain through dispatch order alone — no host round-trip
    between a tick's collect and the next tick's (the overlapped serving
    loop defers packet framing a full tick on the strength of this)."""
    synced_version: jax.Array    # [C, N] int32 — last shipped version
    ever_sent: jax.Array = None  # [C, N] bool — row was EVER shipped


class FleetBatch(NamedTuple):
    """C clients' update packets as one SoA pytree (leading [C, U] dims)."""
    oid: jax.Array        # [C, U] int32
    embed: jax.Array      # [C, U, E] f32
    label: jax.Array      # [C, U] int32
    points: jax.Array     # [C, U, Pc, 3] f16
    n_points: jax.Array   # [C, U] int32
    centroid: jax.Array   # [C, U, 3] f32
    version: jax.Array    # [C, U] int32
    valid: jax.Array      # [C, U] bool — live-row prefix mask per client
    deleted: jax.Array = None   # [C, U] bool — tombstone rows


CLOUD_BATCH_ROWS = 256


class ClientClouds(NamedTuple):
    """Every store row's cloud at client resolution, as the collect ships
    it.  A row's client cloud depends on that row alone (points,
    ``n_points``, label through ``class_budget_table``), so whoever writes
    a row writes its client cloud (``ZoneShardedStore``), and the collect
    gathers whole rows instead of downsampling per (client, row)."""
    points: jax.Array     # [N, Pc * 3] f16 — one whole row per slot
    n_points: jax.Array   # [N] int32
    centroid: jax.Array   # [N, 3] f32 — of the f32 downsample


def client_clouds(points: jax.Array, n_points: jax.Array, label: jax.Array,
                  class_budgets: jax.Array, budget: int) -> ClientClouds:
    """Stride-downsample each of the R rows ``points`` [R, P, 3] to its
    class budget (``class_budgets`` [256], ``budget`` the buffer width and
    hard cap) with geo.downsample_dyn, the centroid taken from the float32
    downsample before the float16 cast."""
    def row(x):
        pts, n, lab = x
        out, n_out = geo.downsample_dyn(
            pts, n, class_budgets[jnp.clip(lab, 0, 255)], budget)
        cent = geo.centroid_bbox(out, n_out)[0]
        return out.astype(jnp.float16).reshape(budget * 3), n_out, cent

    # in batches of rows: the chip's compiler takes ~1 s for one batch's
    # gather, ~20 s for one gather over a whole 4,096-slot shard
    return ClientClouds(*jax.lax.map(row, (points, n_points, label),
                                     batch_size=CLOUD_BATCH_ROWS))


def _changed(store: ObjectStore, synced: jax.Array, ever_sent: jax.Array,
             clear_mask: jax.Array, mask_c: jax.Array, min_obs: jax.Array):
    """The collect's eligibility predicate, shared by the collect and the
    owed-row count: (synced, ever_sent) with the freed slots cleared, and
    the [C, N] ``changed`` and ``tomb`` masks."""
    # slots freed since the last collect (reset_slots) clear INSIDE the
    # dispatch: the [N] mask rides in as 1 KB of host data instead of two
    # eager [C, N] where-ops materializing fresh sync arrays every free —
    # the kernel already streams synced/ever_sent, so the fold is free
    synced = jnp.where(clear_mask[None], 0, synced)
    ever_sent = jnp.where(clear_mask[None], False, ever_sent)
    dele = deleted_mask(store)
    live = (store.active[None]
            & (store.obs_count[None] >= min_obs[:, None])
            & (store.version[None] > synced))
    # a tombstone ships to exactly the clients the object was EVER shipped
    # to; clients that never held it delete nothing.  ever_sent (not
    # synced > 0) is the gate: a resync rollback drops sync to the acked
    # vector, but the deletion must still reach a client whose ack was
    # lost upstream.
    tomb = (dele[None] & ever_sent
            & (store.version[None] > synced))
    changed = (live | tomb) & mask_c[:, None]
    return synced, ever_sent, changed, tomb


@jax.jit
def _changed_counts(store: ObjectStore, synced: jax.Array,
                    ever_sent: jax.Array, clear_mask: jax.Array,
                    mask_c: jax.Array, min_obs: jax.Array) -> jax.Array:
    """[C] rows each client is owed before the collect's budget cut."""
    _, _, changed, _ = _changed(store, synced, ever_sent, clear_mask,
                                mask_c, min_obs)
    return changed.sum(axis=1, dtype=jnp.int32)


def _collect_fleet_impl(store: ObjectStore, synced: jax.Array,
                        ever_sent: jax.Array, clear_mask: jax.Array,
                        mask_c: jax.Array,
                        min_obs: jax.Array, user_pos: jax.Array,
                        interest_embeds, class_budgets: jax.Array,
                        clouds: ClientClouds | None = None, *,
                        budget: int, points_budget: int, knobs: Knobs):
    """One update tick for the whole fleet in a single dispatch.

    ``class_budgets`` [256] is the per-class client point budget table
    (updates.class_budget_table) — the fleet path honors
    ``Knobs.class_point_overrides`` row-by-row exactly like the
    single-client gather.  ``clouds`` is the store's client-cloud column
    (`ClientClouds`, kept by the zone mirror); a caller that holds none
    gets it built here for the whole store, in this dispatch.

    Returns (FleetBatch, new_synced [C, N], new_ever [C, N], nbytes [C],
    counts [C], idx [C, U] — the store slots behind each packet row, for
    the sender's in-flight/ack bookkeeping).
    """
    synced, ever_sent, changed, tomb = _changed(
        store, synced, ever_sent, clear_mask, mask_c, min_obs)
    dele = deleted_mask(store)
    pri = jax.vmap(lambda up: compute_priority(
        store.embed, store.label, store.centroid, user_pos=up, knobs=knobs,
        interest_embeds=interest_embeds))(user_pos)          # [C, N]
    # deletions jump the queue: a freed client slot outranks a refresh
    pri = jnp.where(tomb, jnp.float32(1e30), pri)
    score = jnp.where(changed, pri, -jnp.inf)
    top, idx = jax.lax.top_k(score, budget)                  # [C, U]
    valid = jnp.isfinite(top)
    row_del = jnp.take_along_axis(tomb, idx, axis=1) & valid  # [C, U]

    if clouds is None:
        clouds = client_clouds(store.points, store.n_points, store.label,
                               class_budgets, points_budget)
    pts = clouds.points[idx].reshape(*idx.shape, points_budget, 3)
    n = jnp.where(row_del, 0, clouds.n_points[idx])
    pts = jnp.where(row_del[..., None, None], jnp.float16(0), pts)
    cent = jnp.where(row_del[..., None], store.centroid[idx],
                     clouds.centroid[idx])
    batch = FleetBatch(
        oid=store.ids[idx], embed=store.embed[idx], label=store.label[idx],
        points=pts, n_points=n, centroid=cent,
        version=store.version[idx], valid=valid, deleted=row_del)

    N = synced.shape[1]
    shipped = jnp.where(valid, idx, N)                       # OOB -> dropped
    new_synced = jax.vmap(
        lambda s, i, w: s.at[i].set(w, mode="drop"))(
            synced, shipped, store.version[idx])
    # fully-empty slots must not pin a stale synced version on any client
    new_synced = jnp.where((store.active | dele)[None], new_synced, 0)
    # the sent-gate updates INSIDE the dispatch so consecutive collects
    # chain on-device (no empty-slot clearing here: only reset_slots /
    # reset_client may forget a shipped row, exactly like the host mirror)
    new_ever = jax.vmap(lambda e, i: e.at[i].set(True, mode="drop"))(
        ever_sent, shipped)

    E = store.embed.shape[1]
    n_live = jnp.where(valid, n, 0)
    counts = valid.sum(axis=-1).astype(jnp.int32)
    n_tomb = row_del.sum(axis=-1).astype(jnp.int32)
    nbytes = ((counts - n_tomb) * (_HEADER_B + 2 * E)
              + 6 * n_live.sum(axis=-1) + n_tomb * TOMBSTONE_NBYTES)
    return batch, new_synced, new_ever, nbytes, counts, idx


_COLLECT_STATICS = ("budget", "points_budget", "knobs")
_collect_fleet = functools.partial(
    jax.jit, static_argnames=_COLLECT_STATICS)(_collect_fleet_impl)
# Donating variant: the [C, N] sync-state array is dead the moment the
# dispatch is issued (the session rebinds to new_synced), so XLA may write
# new_synced in place instead of allocating + copying a fresh [C, N] every
# tick.  Byte-identical to the non-donating path (tests/test_serving_loop);
# opt-in via SessionManager(donate=True) because callers that keep their
# own reference to synced_version (oracle tests, benchmarks that reset the
# sync state from a saved array) would read a deleted buffer.
_collect_fleet_donated = jax.jit(_collect_fleet_impl, donate_argnums=(1, 2),
                                 static_argnames=_COLLECT_STATICS)


class _PendingCollect(NamedTuple):
    """An issued-but-unresolved collect dispatch: device handles plus the
    host-side context ``collect_finish`` needs.  Between issue and finish
    the caller is free to dispatch other work (the overlapped loop issues
    every zone's collect, then ingest and queries, before materializing
    any counts) — nothing here forces a device sync."""
    batch: FleetBatch
    nbytes: jax.Array     # [C] device
    counts: jax.Array     # [C] device
    idx: jax.Array        # [C, U] device
    mask: np.ndarray      # [C] bool — subscribed & deliverable at issue
    zone: int
    epoch: np.ndarray
    fresh: np.ndarray
    now: int | None
    scrub: np.ndarray = None   # [N] bool — slots freed AFTER issue; their
    #                            rows must not enter in-flight/ever_sent
    #                            bookkeeping at finish (deferred pipeline)
    changed: jax.Array = None  # [C] device — rows owed before the budget
    #                            cut; counted only while a tracer is on


@dataclass
class FleetPacket:
    """One tick's C packets: the FleetBatch plus host-side accounting.

    When the session assigns sequence numbers (``seqs[c] >= 0``) the
    single-client views carry the hardened-protocol framing: per-(client,
    zone) seq, the client's sync epoch, and — under the fault-injection
    transport (``proto``) — a crc32 checksum.  Framing bytes are counted
    in ``nbytes`` only when ``proto`` is on, so the clean-link byte
    accounting is unchanged."""
    batch: FleetBatch
    counts: np.ndarray       # [C] live rows per client
    nbytes: np.ndarray       # [C] exact wire bytes per client
    tick: int
    zone: int = 0            # zone shard this packet's seq streams belong to
    seqs: np.ndarray = None  # [C] int64 — per-client seq (-1 = unframed)
    epoch: np.ndarray = None  # [C] int64 — per-client sync epoch
    fresh: np.ndarray = None  # [C] bool — epoch restarted from scratch
    proto: bool = False      # fault-injection transport: checksum + header

    @property
    def total_nbytes(self) -> int:
        return int(self.nbytes.sum())

    def block_until_ready(self) -> None:
        """Fence the packet's device tensors (serving-loop sync path)."""
        if self.batch is not None:
            jax.block_until_ready(self.batch.valid)

    def tomb_counts(self) -> np.ndarray:
        """[C] tombstone rows actually shipped per client this tick."""
        if self.batch is None or self.batch.deleted is None:
            return np.zeros_like(self.counts)
        return (np.asarray(self.batch.deleted)
                & np.asarray(self.batch.valid)).sum(axis=1)

    def packet_for(self, c: int) -> UpdatePacket:
        """Single-client UpdatePacket view (leading-dim slice, no copy on
        the live path — `DeviceClient.ingest` consumes the batch as-is)."""
        cnt = int(self.counts[c])
        if cnt == 0:
            return UpdatePacket(batch=None, count=0, nbytes=0, tick=self.tick)
        b = self.batch
        ub = UpdateBatch(oid=b.oid[c], embed=b.embed[c], label=b.label[c],
                         points=b.points[c], n_points=b.n_points[c],
                         centroid=b.centroid[c], version=b.version[c],
                         valid=b.valid[c],
                         deleted=None if b.deleted is None else b.deleted[c])
        pkt = UpdatePacket(batch=ub, count=cnt, nbytes=int(self.nbytes[c]),
                           tick=self.tick)
        if self.seqs is not None and int(self.seqs[c]) >= 0:
            pkt.zone = self.zone
            pkt.seq = int(self.seqs[c])
            pkt.epoch = int(self.epoch[c])
            pkt.fresh = bool(self.fresh[c])
            if self.proto:
                pkt.checksum = pkt.compute_checksum()
        return pkt


@dataclass
class SessionManager:
    """C clients' sync state against one store (or one zone shard).

    Per-client knobs live as stacked host arrays (pose, min-obs,
    subscription); the sync vectors live on device as one [C, N] array.
    ``collect`` is the fleet hot path: one `_collect_fleet` dispatch for all
    C clients.  Unsubscribed / undeliverable clients simply don't advance
    their sync rows, so their next deliverable tick coalesces everything
    they missed (same semantics as CloudService.flush_buffer).
    """
    knobs: Knobs
    n_clients: int
    capacity: int                      # N = slot count of the served store
    budget: int = 64                   # max objects shipped per client/tick
    sync: FleetSync = None
    subscribed: np.ndarray = None      # [C] bool
    user_pos: np.ndarray = None        # [C, 3] f32
    min_obs: np.ndarray = None         # [C] int32
    interest_embeds: object = None     # optional [I, E] shared interests
    tick: int = 0
    dirty: bool = True                 # False only when the last collect
    #                                    covered every subscriber and
    #                                    shipped nothing (fleet quiesced)
    proto: bool = False                # fault-injection transport on: count
    #                                    framing bytes + checksum packets
    donate: bool | None = False        # donate the [C, N] sync state to the
    #                                    collect dispatch (in-place advance;
    #                                    see _collect_fleet_donated).  None =
    #                                    backend-aware auto policy
    #                                    (kernels.ops.donate_default): on for
    #                                    TPU/GPU, OFF on CPU, where a donated
    #                                    dispatch blocks on the donated
    #                                    buffer's producer
    acked: np.ndarray = None           # [C, N] int32 — versions each client
    #                                    has CONFIRMED applying (cumulative
    #                                    acks); trails sync, drives slot
    #                                    retirement
    next_seq: np.ndarray = None        # [C] int64 — next seq per client
    inflight: list = None              # per-client deque of
    #                                    (seq, tick, slots, versions)
    ever_sent: np.ndarray = None       # [C, N] bool — row was EVER shipped
    #                                    to the client; gates tombstones and
    #                                    deletion debt.  Survives rollback
    #                                    (unlike sync, which falls back to
    #                                    acked): a lost upstream ack must
    #                                    not suppress a later deletion.

    def __post_init__(self):
        C, N = self.n_clients, self.capacity
        self.budget = min(self.budget, N)
        if self.donate is None:
            from repro.kernels.ops import donate_default
            self.donate = donate_default()
        if self.sync is None:
            self.sync = FleetSync(jnp.zeros((C, N), jnp.int32),
                                  jnp.zeros((C, N), bool))
        elif self.sync.ever_sent is None:
            self.sync = self.sync._replace(
                ever_sent=jnp.asarray(self.ever_sent)
                if self.ever_sent is not None
                else jnp.zeros((C, N), bool))
        if self.subscribed is None:
            self.subscribed = np.ones((C,), bool)
        if self.user_pos is None:
            self.user_pos = np.zeros((C, 3), np.float32)
        if self.min_obs is None:
            self.min_obs = np.full((C,), self.knobs.min_obs_before_sync,
                                   np.int32)
        if self.acked is None:
            self.acked = np.zeros((C, N), np.int32)
        if self.next_seq is None:
            self.next_seq = np.zeros((C,), np.int64)
        if self.inflight is None:
            self.inflight = [deque() for _ in range(C)]
        if self.ever_sent is None:
            self.ever_sent = np.zeros((C, N), bool)
        self._open_scrubs = []      # scrub masks of issued, unfinished collects
        # [N] bool — slots freed since the last collect; the next collect
        # dispatch zeroes their synced/ever_sent columns in-kernel
        self._pending_clear = np.zeros((N,), bool)
        self._class_budgets = jnp.asarray(class_budget_table(self.knobs))

    # -- per-client knob management (control plane, off the hot path) ------
    def set_client(self, c: int, *, user_pos=None, min_obs=None,
                   subscribed=None):
        if user_pos is not None:
            self.user_pos[c] = np.asarray(user_pos, np.float32)
        if min_obs is not None:
            if int(min_obs) != int(self.min_obs[c]):
                self.dirty = True      # eligibility changed: re-collect
            self.min_obs[c] = int(min_obs)
        if subscribed is not None:
            if bool(subscribed) != bool(self.subscribed[c]):
                self.dirty = True      # membership changed: re-collect
            self.subscribed[c] = bool(subscribed)

    def set_all(self, *, subscribed=None, user_pos=None):
        """Whole-fleet writes of the stacked per-client knob arrays (the
        pose-stream hot path).  Dirty marking stays with the caller —
        FleetServer.set_poses computes membership changes once for every
        zone from the [C, Z] broadcast test."""
        if subscribed is not None:
            self.subscribed[:] = np.asarray(subscribed, bool)
        if user_pos is not None:
            self.user_pos[:] = np.asarray(user_pos, np.float32)

    def reset_client(self, c: int, *, keep_seq: bool = False):
        """Fresh join (or zone re-entry): zero the sync + acked rows so the
        next tick ships a full catch-up of the subscribed store.

        ``keep_seq=True`` preserves the client's sequence stream — used by
        the zone-leave prune, where the client's protocol position must
        survive the subscription gap (only epoch bumps may restart seqs,
        because only they reset the client's expected-seq counters)."""
        self.dirty = True
        self.sync = FleetSync(self.sync.synced_version.at[c].set(0),
                              self.sync.ever_sent.at[c].set(False))
        self.acked[c] = 0
        self.ever_sent[c] = False
        self.inflight[c].clear()
        if not keep_seq:
            self.next_seq[c] = 0

    def reset_slots(self, slots):
        """Store slots were freed/reassigned (zone shard slot reuse): forget
        every client's synced AND acked version there so a future occupant
        ships — and is never falsely 'already acked' by its predecessor's
        confirmations.  In-flight entries scrub the slots too: an ack that
        lands after the reuse must not re-mark them."""
        if len(slots):
            self.dirty = True
            sl = np.asarray(slots)
            # O(1) slot-membership lookup instead of np.isin (a sort) per
            # in-flight entry — this runs per freed zone per tick, over
            # every un-acked packet of every client, and dominated the
            # serving tick at C=256 before the rewrite
            hit = np.zeros((self.capacity,), bool)
            hit[sl] = True
            # the DEVICE clear is deferred: the [N] mask accumulates on the
            # host and the next collect dispatch applies it first thing
            # (see _collect_fleet_impl) — nothing reads the device sync
            # state between here and that collect, and eagerly clearing
            # costs two [C, N] materializations per freed zone per tick
            self._pending_clear |= hit
            self.acked[:, sl] = 0
            self.ever_sent[:, sl] = False
            # collects issued but not yet framed (deferred pipeline) must
            # not resurrect these slots in their finish-time bookkeeping
            for m in self._open_scrubs:
                m[sl] = True
            for q in self.inflight:
                for k, (seq, tk, islots, ivers) in enumerate(q):
                    drop = hit[islots]
                    if drop.any():
                        keep = ~drop
                        q[k] = (seq, tk, islots[keep], ivers[keep])

    # -- ack / resync bookkeeping (hardened protocol control plane) --------
    def ack(self, c: int, seq: int):
        """Cumulative ack: the client has applied every packet up to and
        including ``seq`` — fold those in-flight versions into its acked
        vector (monotonic: a stale duplicate ack can never regress it)."""
        q = self.inflight[c]
        while q and q[0][0] <= seq:
            _, _, islots, ivers = q.popleft()
            if len(islots):
                self.acked[c, islots] = np.maximum(self.acked[c, islots],
                                                   ivers)

    def rollback(self, c: int):
        """Resync: everything sent past the client's last cumulative ack is
        presumed lost.  The sync row falls back to the acked vector, the
        sequence stream restarts, and the next collect re-ships exactly the
        un-acked delta (idempotent on the device: version-guarded).

        ``ever_sent`` deliberately survives the rollback: an UPSTREAM ack
        loss must not erase the fact that a row was ever shipped, or a
        later tombstone would be suppressed (sent-gated) and the client
        kept a ghost object with no deletion debt blocking its slot."""
        self.dirty = True
        self.sync = self.sync._replace(
            synced_version=self.sync.synced_version.at[c].set(
                jnp.asarray(self.acked[c])))
        self.inflight[c].clear()
        self.next_seq[c] = 0

    def oldest_unacked_tick(self, c: int):
        """Collect tick of the client's oldest un-acked packet (None if
        nothing is outstanding) — the server's retransmit-timeout signal."""
        q = self.inflight[c]
        return q[0][1] if q else None

    def deletion_debt(self, store: ObjectStore) -> np.ndarray:
        """[C, N] bool: client c still owes an ack that covers slot n's
        tombstone.  A slot is retirable only when NO subscriber owes it:
        the object was ever shipped to the client (ever_sent) but its
        acked version does not yet cover the deletion (acked < tombstone
        version)."""
        dele = np.asarray(deleted_mask(store))
        ver = np.asarray(store.version)
        return dele[None] & self.ever_sent & (self.acked < ver[None])

    # -- hot path ----------------------------------------------------------
    def collect_start(self, store: ObjectStore, *,
                      deliverable: np.ndarray | None = None, zone: int = 0,
                      epoch: np.ndarray | None = None,
                      fresh: np.ndarray | None = None,
                      now: int | None = None,
                      clouds: ClientClouds | None = None) -> _PendingCollect:
        """Issue the fleet collect dispatch; return device handles.

        This is the async half of ``collect``: the `_collect_fleet` jit is
        dispatched (donating the old sync state when ``donate``), the sync
        vector is rebound to the new device array, and NO host transfer
        happens — the caller overlaps other dispatch families before
        ``collect_finish`` materializes counts and does seq bookkeeping.
        ``clouds`` is ``store``'s client-cloud column where the caller
        keeps one (the zone mirror does); without it the dispatch builds
        the column for the whole store."""
        mask = self.subscribed if deliverable is None \
            else self.subscribed & np.asarray(deliverable, bool)
        fn = _collect_fleet_donated if self.donate else _collect_fleet
        clear = jnp.asarray(self._pending_clear)
        self._pending_clear = np.zeros((self.capacity,), bool)
        mask_d, min_obs = jnp.asarray(mask), jnp.asarray(self.min_obs)
        # the owed-row count reads the sync state the collect donates, so
        # it is issued first
        changed = None if get_tracer() is None else _changed_counts(
            store, self.sync.synced_version, self.sync.ever_sent, clear,
            mask_d, min_obs)
        with obs_span("session.collect_fleet", cat="sync", zone=zone) as sp:
            batch, new_synced, new_ever, nbytes, counts, idx = fn(
                store, self.sync.synced_version, self.sync.ever_sent,
                clear, mask_d, min_obs, jnp.asarray(self.user_pos),
                self.interest_embeds, self._class_budgets, clouds,
                budget=self.budget,
                points_budget=self.knobs.max_object_points_client,
                knobs=self.knobs)
            sp.fence(batch.valid)
        self.sync = FleetSync(new_synced, new_ever)
        # the collect consumes the dirty flag; finish (or any event in
        # between — refresh marks, subscription changes) re-raises it
        self.dirty = False
        scrub = np.zeros((self.capacity,), bool)
        self._open_scrubs.append(scrub)
        return _PendingCollect(batch=batch, nbytes=nbytes, counts=counts,
                               idx=idx, mask=mask, zone=zone, epoch=epoch,
                               fresh=fresh, now=now, scrub=scrub,
                               changed=changed)

    def collect_finish(self, p: _PendingCollect) -> FleetPacket:
        """Materialize an issued collect: host transfer + seq/in-flight
        bookkeeping.  Finishing in issue order keeps the packets
        byte-identical to the sequential ``collect`` path."""
        with obs_span("session.collect_finish", cat="sync") as sp:
            pkt = self._finish(p)
            if sp.on:
                sp.set(zone=p.zone, issue_tick=p.now,
                       clients=int(p.mask.sum()),
                       rows_shipped=int(pkt.counts.sum()),
                       bytes=int(pkt.nbytes.sum()))
                if p.changed is not None:
                    with obs_span("host.fetch", cat="sync", what="owed"):
                        changed = np.asarray(p.changed)
                    sp.set(rows_owed=int((changed - pkt.counts).sum()))
        return pkt

    def _finish(self, p: _PendingCollect) -> FleetPacket:
        batch = p.batch
        with obs_span("host.fetch", cat="sync", what="counts"):
            counts = np.asarray(p.counts)
            nbytes = np.asarray(p.nbytes).astype(np.int64)
        seqs = np.full((self.n_clients,), -1, np.int64)
        if counts.any():
            with obs_span("host.fetch", cat="sync", what="rows"):
                idx_h = np.asarray(p.idx)
                valid_h = np.asarray(batch.valid)
                vers_h = np.asarray(batch.version)
            stamp = self.tick if p.now is None else p.now
            scrubbed = p.scrub is not None and p.scrub.any()
            for c in np.nonzero(counts)[0]:
                seqs[c] = self.next_seq[c]
                self.next_seq[c] += 1
                v = valid_h[c]
                sl, vv = idx_h[c][v], vers_h[c][v]
                if scrubbed:
                    # slots freed after issue (deferred finish): the packet
                    # still ships as computed, but its rows must not enter
                    # retirement bookkeeping — a later occupant of the slot
                    # would inherit the predecessor's send/ack state
                    keep = ~p.scrub[sl]
                    sl, vv = sl[keep], vv[keep]
                self.inflight[c].append((int(seqs[c]), stamp, sl, vv))
                self.ever_sent[c, sl] = True
            if self.proto:
                nbytes[counts > 0] += PROTO_HEADER_NBYTES
        pkt = FleetPacket(batch=batch, counts=counts, nbytes=nbytes,
                          tick=self.tick, zone=p.zone, seqs=seqs,
                          epoch=np.zeros((self.n_clients,), np.int64)
                          if p.epoch is None
                          else np.asarray(p.epoch, np.int64),
                          fresh=np.zeros((self.n_clients,), bool)
                          if p.fresh is None else np.asarray(p.fresh, bool),
                          proto=self.proto)
        self.tick += 1
        if p.scrub is not None:
            self._open_scrubs = [m for m in self._open_scrubs
                                 if m is not p.scrub]
        # quiesced iff every subscriber was covered and nothing shipped (a
        # partial-coverage tick may still owe undeliverable clients); OR —
        # not assign — so marks raised between a deferred issue and this
        # finish (refresh, slot churn, subscription moves) survive
        self.dirty = (self.dirty or bool(pkt.counts.any())
                      or not (p.mask == self.subscribed).all())
        return pkt

    def collect(self, store: ObjectStore, *,
                deliverable: np.ndarray | None = None, zone: int = 0,
                epoch: np.ndarray | None = None,
                fresh: np.ndarray | None = None,
                now: int | None = None,
                clouds: ClientClouds | None = None) -> FleetPacket:
        """One fleet update tick: ONE jitted dispatch for all C clients.

        Every non-empty per-client packet takes the next number on that
        client's sequence stream, and the shipped (slot, version) pairs are
        queued in-flight until the client's cumulative ack lands — the
        sync vector records what was SENT, ``acked`` what was CONFIRMED,
        and slot retirement trusts only the latter."""
        return self.collect_finish(self.collect_start(
            store, deliverable=deliverable, zone=zone, epoch=epoch,
            fresh=fresh, now=now, clouds=clouds))
