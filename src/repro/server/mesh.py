"""Mesh-sharded session tier: the [C, N] fleet sync state partitioned
across S session shards, one per mesh device.

``SessionManager`` keeps the whole fleet's sync state as one [C, N] device
array and the per-client host state (acked / inflight / ever_sent /
next_seq) as C-row host arrays — one host, one device.  Past C≈1k the
single [C, N] dispatch still scales, but the arrays live on one device and
the host bookkeeping on one process; the ROADMAP's C≥4096 tier wants both
partitioned.  ``MeshSessionTier`` shards the CLIENT axis: S plain
SessionManager parts, part s owning rows for the clients a ``ClientRoster``
homes there (subscribed-zone affinity via
``client_shard_affinity``, round-robin before poses
exist).  Each part is placed on its own mesh device (``place_on``), so a
part's vmapped ``_collect_fleet`` gathers run where its clients' zone
stores live.

Correctness rests on a property of ``_collect_fleet_impl``: every
per-client row of the collect is computed independently (vmapped change
detection, per-row priority, per-row ``lax.top_k``, per-row gather), so a
[C_s, N] collect over a subset of clients produces BIT-IDENTICAL rows to
the same clients' rows in the unsharded [C, N] collect.  The tier
therefore never merges tensors: the k-way merge happens only at the wire
boundary — ``MeshFleetPacket`` assembles the per-client byte/seq/count
accounting into [C] arrays and delegates ``packet_for(c)`` to the owning
part's row view, so wire packets are byte-identical to the single-device
path (asserted per client at every C in benchmarks/fleet_scale.py and in
tests/test_fleet.py).

Control-plane routing: acks, resyncs, rollbacks, and per-client resets are
routed to the owning shard through the roster (``parts[assign[c]]``, row
``row[c]``); store-slot events (``reset_slots``) broadcast to every part,
exactly like the unsharded [C, N] column clear.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import jax

from repro.core.knobs import Knobs
from repro.core.store import ObjectStore
from repro.core.updates import UpdatePacket
from repro.server.session import ClientClouds, SessionManager
from repro.server.zones import zone_shard_devices


# ---------------------------------------------------------------------------
@dataclass
class ClientRoster:
    """Static client -> session-shard partition.

    ``assign[c]`` is the shard homing client c; ``row[c]`` its row inside
    that shard's [C_s, N] state (ascending-cid order, so a shard's rows
    are a stable sorted view of its members).  The roster is fixed for the
    tier's lifetime — re-homing a client would have to move its sync/ack/
    in-flight state across hosts mid-protocol (ROADMAP: live migration).
    """
    assign: np.ndarray                 # [C] int32
    n_shards: int
    row: np.ndarray = None             # [C] int32, derived
    members: tuple = None              # per-shard int64[C_s] global cids

    def __post_init__(self):
        self.assign = np.asarray(self.assign, np.int32)
        assert self.assign.ndim == 1
        assert (0 <= self.assign).all() and (self.assign < self.n_shards).all()
        C = len(self.assign)
        self.row = np.zeros((C,), np.int32)
        members = []
        for s in range(self.n_shards):
            cids = np.nonzero(self.assign == s)[0].astype(np.int64)
            members.append(cids)
            self.row[cids] = np.arange(len(cids), dtype=np.int32)
        self.members = tuple(members)

    @property
    def n_clients(self) -> int:
        return len(self.assign)

    def counts(self) -> np.ndarray:
        return np.array([len(m) for m in self.members], np.int64)

    @classmethod
    def round_robin(cls, n_clients: int, n_shards: int) -> "ClientRoster":
        return cls(assign=np.arange(n_clients, dtype=np.int32) % n_shards,
                   n_shards=n_shards)

    @classmethod
    def from_affinity(cls, subscribed: np.ndarray, n_shards: int,
                      zone_shards=None) -> "ClientRoster":
        """Partition by subscribed-zone affinity (majority vote over the
        zones' shard placement; see ``client_shard_affinity``)."""
        return cls(assign=client_shard_affinity(subscribed, n_shards,
                                                zone_shards),
                   n_shards=n_shards)


# ---------------------------------------------------------------------------
@dataclass
class MeshFleetPacket:
    """One tick's C packets from S shard collects, merged ONLY at the wire
    boundary: the per-client accounting ([C] nbytes/counts/seqs/epoch/
    fresh) is assembled from the part packets, while the payload tensors
    stay in their per-part [C_s, U] batches — ``packet_for(c)`` is the
    owning part's row view, so the framed bytes are exactly the
    single-device packet's."""
    parts: list                        # per-shard FleetPacket (None = empty
    #                                    shard: no clients homed there)
    roster: ClientRoster
    counts: np.ndarray                 # [C] assembled
    nbytes: np.ndarray                 # [C] assembled
    seqs: np.ndarray                   # [C] assembled (-1 = unframed)
    epoch: np.ndarray                  # [C] assembled
    fresh: np.ndarray                  # [C] assembled
    tick: int
    zone: int = 0
    proto: bool = False

    @property
    def total_nbytes(self) -> int:
        return int(self.nbytes.sum())

    def block_until_ready(self) -> None:
        """Fence every shard's device tensors (serving-loop sync path)."""
        for pkt in self.parts:
            if pkt is not None:
                pkt.block_until_ready()

    def tomb_counts(self) -> np.ndarray:
        out = np.zeros_like(self.counts)
        for s, pkt in enumerate(self.parts):
            if pkt is not None:
                out[self.roster.members[s]] = pkt.tomb_counts()
        return out

    def packet_for(self, c: int) -> UpdatePacket:
        pkt = self.parts[int(self.roster.assign[c])]
        if pkt is None:
            return UpdatePacket(batch=None, count=0, nbytes=0, tick=self.tick)
        return pkt.packet_for(int(self.roster.row[c]))


class _MeshPending:
    """Issued-but-unfinished collects of every part, in shard order."""
    __slots__ = ("pending",)

    def __init__(self, pending):
        self.pending = pending         # per-shard _PendingCollect | None


# ---------------------------------------------------------------------------
@dataclass
class MeshSessionTier:
    """S SessionManager parts behind the SessionManager facade FleetServer
    drives: same control-plane methods (global client ids, routed to the
    owning shard) and the same collect_start/collect_finish hot path (every
    part dispatched per tier collect, so part ticks stay in lockstep with
    the tier tick and quiescence semantics match the unsharded session:
    tier dirty == OR over part dirty == unsharded dirty)."""
    knobs: Knobs
    capacity: int                      # N = slot count of the served store
    roster: ClientRoster = None
    n_clients: int = 0                 # used only when roster is None
    n_shards: int = 2                  # used only when roster is None
    budget: int = 64
    proto: bool = False
    donate: bool | None = False        # None = backend-aware auto policy
    parts: list = field(default_factory=list)
    devices: list = None               # per-shard jax device (None entries =
    #                                    default device; 1-device container:
    #                                    every part on the same device)
    tick: int = 0

    def __post_init__(self):
        if self.roster is None:
            self.roster = ClientRoster.round_robin(self.n_clients,
                                                   self.n_shards)
        self.n_clients = self.roster.n_clients
        self.n_shards = self.roster.n_shards
        if self.devices is None:
            self.devices = [None] * self.n_shards
        if not self.parts:
            self.parts = [
                SessionManager(knobs=self.knobs, n_clients=len(m),
                               capacity=self.capacity, budget=self.budget,
                               proto=self.proto, donate=self.donate,
                               subscribed=np.zeros((len(m),), bool))
                if len(m) else None
                for m in self.roster.members]

    # -- partition helpers -------------------------------------------------
    def _route(self, c: int):
        part = self.parts[int(self.roster.assign[c])]
        assert part is not None
        return part, int(self.roster.row[c])

    def _live(self):
        return ((s, p) for s, p in enumerate(self.parts) if p is not None)

    def _assemble1(self, get, dtype, fill=0):
        out = np.full((self.n_clients,), fill, dtype)
        for s, p in self._live():
            out[self.roster.members[s]] = get(p)
        return out

    def place_on(self, mesh) -> None:
        """Move each part's device-resident sync state onto its mesh
        device (round-robin, same placement rule as zone_shard_devices).
        Host-side per-client state stays with the part object — on a real
        multi-host mesh that state lives in the shard's server process."""
        self.devices = zone_shard_devices(mesh, self.n_shards)
        for s, p in self._live():
            p.sync = jax.device_put(p.sync, self.devices[s])

    # -- SessionManager facade: state reads --------------------------------
    @property
    def dirty(self) -> bool:
        return any(p.dirty for _, p in self._live())

    @dirty.setter
    def dirty(self, v: bool) -> None:
        for _, p in self._live():
            p.dirty = v

    @property
    def subscribed(self) -> np.ndarray:
        return self._assemble1(lambda p: p.subscribed, bool, False)

    @property
    def user_pos(self) -> np.ndarray:
        out = np.zeros((self.n_clients, 3), np.float32)
        for s, p in self._live():
            out[self.roster.members[s]] = p.user_pos
        return out

    # -- control plane (routed to the owning shard) ------------------------
    def set_all(self, *, subscribed=None, user_pos=None):
        for s, p in self._live():
            m = self.roster.members[s]
            p.set_all(
                subscribed=None if subscribed is None
                else np.asarray(subscribed, bool)[m],
                user_pos=None if user_pos is None
                else np.asarray(user_pos, np.float32)[m])

    def set_client(self, c: int, **kw):
        part, r = self._route(c)
        part.set_client(r, **kw)

    def reset_client(self, c: int, *, keep_seq: bool = False):
        part, r = self._route(c)
        part.reset_client(r, keep_seq=keep_seq)

    def reset_slots(self, slots):
        # store-slot lifecycle is global: every shard's columns clear,
        # exactly like the unsharded [C, N] column clear
        for _, p in self._live():
            p.reset_slots(slots)

    def ack(self, c: int, seq: int):
        part, r = self._route(c)
        part.ack(r, seq)

    def rollback(self, c: int):
        part, r = self._route(c)
        part.rollback(r)

    def oldest_unacked_tick(self, c: int):
        part, r = self._route(c)
        return part.oldest_unacked_tick(r)

    def deletion_debt(self, store: ObjectStore) -> np.ndarray:
        out = np.zeros((self.n_clients, self.capacity), bool)
        for s, p in self._live():
            out[self.roster.members[s]] = p.deletion_debt(store)
        return out

    # -- hot path ----------------------------------------------------------
    def collect_start(self, store: ObjectStore, *,
                      deliverable: np.ndarray | None = None, zone: int = 0,
                      epoch: np.ndarray | None = None,
                      fresh: np.ndarray | None = None,
                      now: int | None = None,
                      clouds: ClientClouds | None = None) -> _MeshPending:
        """Issue every shard's collect dispatch (the shard devices run
        concurrently under jax async dispatch; on the 1-device container
        the dispatches queue).  Every live part is dispatched whenever the
        tier collects, so part ticks/quiescence advance in lockstep with
        the unsharded session."""
        pend = [None] * self.n_shards
        for s, p in self._live():
            m = self.roster.members[s]
            st, cl = store, clouds
            if self.devices[s] is not None:
                # placed tier: the shard reads a device-local view of the
                # store (no-op when the placement already matches, as on
                # the 1-device container)
                st, cl = jax.device_put((store, clouds), self.devices[s])
            pend[s] = p.collect_start(
                st,
                deliverable=None if deliverable is None
                else np.asarray(deliverable, bool)[m],
                zone=zone,
                epoch=None if epoch is None else np.asarray(epoch)[m],
                fresh=None if fresh is None else np.asarray(fresh)[m],
                now=now, clouds=cl)
        return _MeshPending(pend)

    def collect_finish(self, pending: _MeshPending) -> MeshFleetPacket:
        parts = [None] * self.n_shards
        for s, p in self._live():
            if pending.pending[s] is not None:
                parts[s] = p.collect_finish(pending.pending[s])
        roster = self.roster
        pkt = MeshFleetPacket(
            parts=parts, roster=roster,
            counts=self._assemble_pkt(parts, "counts", np.int64, 0),
            nbytes=self._assemble_pkt(parts, "nbytes", np.int64, 0),
            seqs=self._assemble_pkt(parts, "seqs", np.int64, -1),
            epoch=self._assemble_pkt(parts, "epoch", np.int64, 0),
            fresh=self._assemble_pkt(parts, "fresh", bool, False),
            tick=self.tick,
            zone=parts[self._first_live()].zone
            if self._first_live() is not None else 0,
            proto=self.proto)
        self.tick += 1
        return pkt

    def _first_live(self):
        for s, p in enumerate(self.parts):
            if p is not None:
                return s
        return None

    def _assemble_pkt(self, parts, name, dtype, fill):
        out = np.full((self.n_clients,), fill, dtype)
        for s, pkt in enumerate(parts):
            if pkt is not None:
                out[self.roster.members[s]] = getattr(pkt, name)
        return out

    def collect(self, store: ObjectStore, *,
                deliverable: np.ndarray | None = None, zone: int = 0,
                epoch: np.ndarray | None = None,
                fresh: np.ndarray | None = None,
                now: int | None = None,
                clouds: ClientClouds | None = None) -> MeshFleetPacket:
        return self.collect_finish(self.collect_start(
            store, deliverable=deliverable, zone=zone, epoch=epoch,
            fresh=fresh, now=now, clouds=clouds))


def client_shard_affinity(subscribed: np.ndarray, n_shards: int,
                          zone_shards: np.ndarray | None = None) -> np.ndarray:
    """Assign each client to a session shard by subscribed-zone affinity.

    ``subscribed`` is the fleet's [C, Z] zone-subscription matrix and
    ``zone_shards`` [Z] maps each spatial zone to the session shard whose
    device holds that zone's store arrays (``zone_shard_devices``
    placement: defaults to z % n_shards).  A client is homed on the shard
    that owns the MOST of its subscribed zones — majority vote, lowest
    shard id on ties — so the sharded session tier's sync gathers read
    zone stores resident on the same device.  Clients with no
    subscriptions yet fall back to round-robin (c % n_shards), which
    keeps the partition load-balanced before the first pose arrives.

    Returns [C] int32 shard assignment.  The assignment is computed at
    tier construction; live re-homing of a moving client is a control-
    plane migration (ROADMAP) and is not done per pose update.
    """
    subscribed = np.asarray(subscribed, bool)
    C, Z = subscribed.shape
    if zone_shards is None:
        zone_shards = np.arange(Z) % n_shards
    zone_shards = np.asarray(zone_shards)
    # [C, S] votes: how many of client c's zones live on shard s
    votes = np.zeros((C, n_shards), np.int64)
    for s in range(n_shards):
        votes[:, s] = subscribed[:, zone_shards == s].sum(axis=1)
    assign = votes.argmax(axis=1).astype(np.int32)   # argmax = lowest tie
    none = ~subscribed.any(axis=1)
    assign[none] = (np.arange(C)[none] % n_shards).astype(np.int32)
    return assign
