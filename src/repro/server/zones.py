"""Zone-sharded object store: spatial partition of the server map.

Objects are routed to zones by centroid over a fixed XZ grid; each zone is
an independent, fixed-capacity `ObjectStore` shard, so per-zone work
(per-client sync, queries) touches only that zone's slots.  Clients
subscribe to the zones their pose-radius overlaps — a client whose pose
stays inside one zone receives ZERO downstream bytes for objects mutated
only in other zones (tests/test_fleet.py asserts this with exact
`update_nbytes` accounting).

The mapping frontend stays monolithic (association needs the global view);
``refresh_from`` mirrors its store into the shards incrementally: only rows
whose version advanced since the last copy are re-scattered (one bucketed
jitted scatter per dirty zone, not per object).  Slot bookkeeping is
host-side; freed shard slots are reported so the per-zone SessionManager
can forget stale sync versions before the slot is reused.

Each shard carries its client-cloud column (``clouds``, a
`session.ClientClouds`): every slot's cloud downsampled to client
resolution.  The scatter that writes a row writes its client cloud, so the
per-zone collect gathers whole precomputed rows and never reads the
``[capz, P, 3]`` server cloud.

``place_on(mesh)`` places the shards round-robin on a mesh's devices
(`zone_shard_devices`); refreshes then gather the
changed rows on the global store's device and ship only those.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.knobs import Knobs
from repro.core.store import ObjectStore, deleted_mask, init_store
from repro.core.updates import _bucket, class_budget_table
from repro.obs.trace import span as obs_span
from repro.server.session import ClientClouds, client_clouds


@dataclass(frozen=True)
class ZoneGrid:
    """Fixed XZ-plane partition of the mapped space into nx*nz zones."""
    origin: tuple            # (x0, z0) — min corner of the grid
    zone_size: float         # zone edge length (metres)
    nx: int
    nz: int

    @property
    def n_zones(self) -> int:
        return self.nx * self.nz

    @classmethod
    def for_room(cls, room_size: float, nx: int = 2, nz: int = 2):
        half = room_size / 2
        return cls(origin=(-half, -half), zone_size=room_size / max(nx, nz),
                   nx=nx, nz=nz)

    def zone_of(self, centroids: np.ndarray) -> np.ndarray:
        """[M, 3] centroids -> [M] zone ids (out-of-grid clamps to edge)."""
        c = np.atleast_2d(np.asarray(centroids))
        ix = np.clip(((c[:, 0] - self.origin[0]) // self.zone_size)
                     .astype(np.int64), 0, self.nx - 1)
        iz = np.clip(((c[:, 2] - self.origin[1]) // self.zone_size)
                     .astype(np.int64), 0, self.nz - 1)
        return ix * self.nz + iz

    def overlaps(self, pos, radius: float) -> np.ndarray:
        """[Z] bool — zones whose XZ rectangle intersects the pose circle.

        Border zones extend to infinity on their grid-exterior sides,
        mirroring the clamp in ``zone_of``: an object outside the grid and
        the client standing next to it land in the same zone."""
        pos = np.asarray(pos)
        px, pz = float(pos[0]), float(pos[2])
        inf = float("inf")
        out = np.zeros((self.n_zones,), bool)
        for ix in range(self.nx):
            for iz in range(self.nz):
                x0 = self.origin[0] + ix * self.zone_size
                z0 = self.origin[1] + iz * self.zone_size
                x1, z1 = x0 + self.zone_size, z0 + self.zone_size
                if ix == 0:
                    x0 = -inf
                if ix == self.nx - 1:
                    x1 = inf
                if iz == 0:
                    z0 = -inf
                if iz == self.nz - 1:
                    z1 = inf
                cx = np.clip(px, x0, x1)
                cz = np.clip(pz, z0, z1)
                if (cx - px) ** 2 + (cz - pz) ** 2 <= radius ** 2:
                    out[ix * self.nz + iz] = True
        return out

    def _zone_rects(self):
        """[Z] rectangle bounds (x0, x1, z0, z1) in zone-id order, border
        zones extended to infinity — cached: the grid is frozen."""
        r = getattr(self, "_rects", None)
        if r is None:
            inf = float("inf")
            ix, iz = np.divmod(np.arange(self.n_zones), self.nz)
            x0 = self.origin[0] + ix * self.zone_size
            z0 = self.origin[1] + iz * self.zone_size
            x1, z1 = x0 + self.zone_size, z0 + self.zone_size
            x0 = np.where(ix == 0, -inf, x0)
            x1 = np.where(ix == self.nx - 1, inf, x1)
            z0 = np.where(iz == 0, -inf, z0)
            z1 = np.where(iz == self.nz - 1, inf, z1)
            r = (x0, x1, z0, z1)
            object.__setattr__(self, "_rects", r)
        return r

    def overlaps_batch(self, poses: np.ndarray, radius) -> np.ndarray:
        """[C, 3] poses -> [C, Z] bool, identical to per-client ``overlaps``
        but one broadcast circle-rectangle test instead of a C * Z Python
        loop (the fleet pose-update hot path at C=256+)."""
        p = np.atleast_2d(np.asarray(poses, np.float64))
        x0, x1, z0, z1 = self._zone_rects()
        cx = np.clip(p[:, 0:1], x0[None], x1[None])        # [C, Z]
        cz = np.clip(p[:, 2:3], z0[None], z1[None])
        d2 = (cx - p[:, 0:1]) ** 2 + (cz - p[:, 2:3]) ** 2
        r = np.asarray(radius, np.float64).reshape(-1, 1)
        return d2 <= r ** 2


@jax.jit
def _gather_rows(src: ObjectStore, g_idx: jax.Array) -> ObjectStore:
    """Rows ``g_idx`` of the global store, live/tombstone state included —
    all a zone shard needs of it, so only these rows cross to a shard
    placed on another device."""
    rows = src._replace(deleted=deleted_mask(src),
                        next_id=jnp.zeros((), jnp.int32))
    return jax.tree.map(lambda x: x[g_idx] if x.ndim else x, rows)


@functools.partial(jax.jit, static_argnames=("points_budget",))
def _zone_clouds(zone: ObjectStore, class_budgets: jax.Array, *,
                 points_budget: int) -> ClientClouds:
    """The client-cloud column of every slot of ``zone``."""
    return client_clouds(zone.points, zone.n_points, zone.label,
                         class_budgets, points_budget)


@functools.partial(jax.jit, static_argnames=("points_budget",))
def _zone_scatter(zone: ObjectStore, clouds: ClientClouds, rows: ObjectStore,
                  z_idx: jax.Array, valid: jax.Array, deact_idx: jax.Array,
                  deact_valid: jax.Array, class_budgets: jax.Array, *,
                  points_budget: int):
    """Write gathered ``rows`` into zone rows z_idx, with their client
    clouds, and deactivate deact_idx — one scatter per field, padding rows
    dropped via OOB indices.  Returns (zone, clouds)."""
    capz = zone.ids.shape[0]
    tgt = jnp.where(valid, z_idx, capz)
    dt = jnp.where(deact_valid, deact_idx, capz)

    def put(zf, rf):
        return zf.at[tgt].set(rf, mode="drop")

    # copied rows take the SOURCE row's live/tombstone state (a global
    # tombstone mirrors as a shard tombstone so the deletion propagates
    # through the per-zone sync sessions); freed slots clear both
    active = zone.active.at[dt].set(False, mode="drop") \
                        .at[tgt].set(rows.active, mode="drop")
    deleted = deleted_mask(zone).at[dt].set(False, mode="drop") \
        .at[tgt].set(rows.deleted, mode="drop")
    zone = ObjectStore(
        ids=put(zone.ids, rows.ids), active=active,
        embed=put(zone.embed, rows.embed), label=put(zone.label, rows.label),
        points=put(zone.points, rows.points),
        n_points=put(zone.n_points, rows.n_points),
        centroid=put(zone.centroid, rows.centroid),
        bbox_min=put(zone.bbox_min, rows.bbox_min),
        bbox_max=put(zone.bbox_max, rows.bbox_max),
        obs_count=put(zone.obs_count, rows.obs_count),
        version=put(zone.version, rows.version),
        last_seen=put(zone.last_seen, rows.last_seen),
        next_id=zone.next_id, deleted=deleted)
    # freed slots keep their cloud, as the shard keeps their points: they
    # are not eligible, and a tombstone ships none
    new = client_clouds(rows.points, rows.n_points, rows.label,
                        class_budgets, points_budget)
    return zone, jax.tree.map(put, clouds, new)


def _pad_idx(vals: list, bucket: int):
    arr = np.zeros((bucket,), np.int32)
    arr[:len(vals)] = vals
    return jnp.asarray(arr), jnp.asarray(np.arange(bucket) < len(vals))


@dataclass
class ZoneShardedStore:
    """The server map as Z independent ObjectStore shards + host routing."""
    knobs: Knobs
    embed_dim: int
    grid: ZoneGrid
    zone_capacity: int = 0
    max_points: int = 0
    zones: list = field(default_factory=list)
    devices: list = None               # per-zone jax device (place_on);
    #                                    None = the default device
    indexes: dict = field(default_factory=dict)  # zone -> ClusterIndex
    #                                  (enable_index; core.query discovers
    #                                   this attr for the two-stage plan)
    clouds: list = field(default_factory=list, init=False)  # per zone:
    #                                  ClientClouds of the shard's rows,
    #                                  written with them (_zone_scatter)
    _dropped_oids: set = field(default_factory=set)  # refused by full shard
    _slot: list = field(default_factory=list)   # per zone: {oid -> slot}
    _ver: list = field(default_factory=list)    # per zone: copied version
    _free: list = field(default_factory=list)   # per zone: free slot stack

    def __post_init__(self):
        Z = self.grid.n_zones
        if not self.zone_capacity:
            # headroom over an even split so skewed scenes don't overflow
            self.zone_capacity = max(16, 2 * self.knobs.server_capacity // Z)
        if not self.max_points:
            self.max_points = self.knobs.max_object_points_server
        if not self.zones:
            self.zones = [init_store(self.zone_capacity, self.embed_dim,
                                     self.max_points) for _ in range(Z)]
        else:
            self.zone_capacity = int(self.zones[0].ids.shape[0])
        self._class_budgets = jnp.asarray(class_budget_table(self.knobs))
        self.clouds = [_zone_clouds(
            zone, self._class_budgets,
            points_budget=self.knobs.max_object_points_client)
            for zone in self.zones]
        # bookkeeping is rebuilt from the shards' own arrays, so passing
        # pre-populated zones keeps their occupied slots occupied
        self._slot, self._ver, self._free = [], [], []
        for zone in self.zones:
            act = np.asarray(zone.active) | np.asarray(deleted_mask(zone))
            ids = np.asarray(zone.ids)
            ver = np.asarray(zone.version)
            occ = np.nonzero(act)[0]
            self._slot.append({int(ids[s]): int(s) for s in occ})
            vv = np.full((self.zone_capacity,), -1, np.int64)
            vv[occ] = ver[occ]
            self._ver.append(vv)
            self._free.append([s for s in
                               range(self.zone_capacity - 1, -1, -1)
                               if not act[s]])

    # ------------------------------------------------------------------
    def refresh_from(self, store: ObjectStore):
        """Mirror the global store into the shards (only version-advanced
        rows are copied).  Returns (freed_per_zone, changed_per_zone):
        per-zone lists of freed shard slots — feed these to
        SessionManager.reset_slots before the slot is reused — and per-zone
        dirtiness flags so clean zones can skip their next collect.
        """
        with obs_span("zones.refresh", cat="sync") as sp:
            with obs_span("host.fetch", cat="sync", what="store"):
                active = np.asarray(store.active)
                version = np.asarray(store.version)
                ids = np.asarray(store.ids)
                cent = np.asarray(store.centroid)
                dele = np.asarray(deleted_mask(store))
            # tombstones mirror like live rows (routed by their retained
            # centroid): the shard must hold the version-bumped deletion until
            # every subscriber has shipped it; once the global store retires
            # the slot the row vanishes from `now` and the shard slot is freed
            gidx = np.nonzero(active | dele)[0]
            Z = self.grid.n_zones
            now = [dict() for _ in range(Z)]
            if len(gidx):
                zids = self.grid.zone_of(cent[gidx])
                for g, z in zip(gidx, zids):
                    now[int(z)][int(ids[g])] = int(g)

            freed_per_zone, changed_per_zone, n_copied = [], [], 0
            for z in range(Z):
                slot = self._slot[z]
                freed, g_list, s_list = [], [], []
                for oid in [o for o in slot if o not in now[z]]:
                    s = slot.pop(oid)
                    self._ver[z][s] = -1
                    self._free[z].append(s)
                    freed.append(s)
                for oid, g in now[z].items():
                    s = slot.get(oid)
                    if s is None:
                        if not self._free[z]:
                            self._dropped_oids.add(oid)
                            continue
                        s = self._free[z].pop()
                        slot[oid] = s
                    if self._ver[z][s] != version[g]:
                        self._ver[z][s] = version[g]
                        g_list.append(g)
                        s_list.append(s)
                freed_per_zone.append(freed)
                changed_per_zone.append(bool(freed or g_list))
                n_copied += len(g_list)
                if freed or g_list:
                    B = _bucket(max(len(g_list), 1))
                    gb, gv = _pad_idx(g_list, B)
                    sb, _ = _pad_idx(s_list, B)
                    db, dv = _pad_idx(freed, _bucket(max(len(freed), 1)))
                    rows = _gather_rows(store, gb)
                    if self.devices is not None:
                        rows = jax.device_put(rows, self.devices[z])
                    self.zones[z], self.clouds[z] = _zone_scatter(
                        self.zones[z], self.clouds[z], rows, sb, gv, db, dv,
                        self._class_budgets,
                        points_budget=self.knobs.max_object_points_client)
                    # cluster-index maintenance rides the same delta: exactly
                    # the scattered + freed shard slots are re-indexed
                    zidx = self.indexes.get(z)
                    if zidx is not None:
                        zidx.update_slots(self.zones[z], s_list + freed)
            if sp.on:
                sp.set(rows_changed=n_copied,
                       rows_freed=sum(len(f) for f in freed_per_zone),
                       clouds=n_copied)
        return freed_per_zone, changed_per_zone

    # ------------------------------------------------------------------
    def enable_index(self, *, n_cells_target: int | None = None,
                     cell_cap: int | None = None,
                     min_flat_size: int | None = None) -> dict:
        """Attach one incrementally-maintained ClusterIndex per zone shard
        (repro.index) over the zone's own rectangle; from then on
        ``refresh_from`` keeps them current and ``core.query`` plans the
        coarse-to-fine two-stage sweep on any shard past
        ``min_flat_size`` live objects."""
        from repro.core.updates import bucket
        from repro.index import ClusterIndex, DEFAULT_MIN_FLAT
        from repro.index.cluster import CellGrid
        if min_flat_size is None:
            min_flat_size = DEFAULT_MIN_FLAT
        capz = self.zone_capacity
        if n_cells_target is None:
            n_cells_target = min(max(capz // 256, 16), 16_384)
        for z in range(self.grid.n_zones):
            ix, iz = divmod(z, self.grid.nz)
            x0 = self.grid.origin[0] + ix * self.grid.zone_size
            z0 = self.grid.origin[1] + iz * self.grid.zone_size
            cgrid = CellGrid.for_rect(x0, z0, self.grid.zone_size,
                                      self.grid.zone_size, n_cells_target)
            cc = cell_cap if cell_cap is not None else \
                bucket(max(4 * capz // cgrid.n_cells, 16))
            idx = ClusterIndex(grid=cgrid, embed_dim=self.embed_dim,
                               capacity=capz, cell_cap=int(cc),
                               min_flat_size=min_flat_size)
            idx.refresh(self.zones[z])
            self.indexes[z] = idx
        return self.indexes

    # ------------------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Distinct objects ever refused by a full shard (not retries)."""
        return len(self._dropped_oids)

    def subscriptions(self, pos, radius: float) -> np.ndarray:
        return self.grid.overlaps(pos, radius)

    def n_active(self) -> int:
        return int(sum(int(np.asarray(z.active).sum()) for z in self.zones))

    def place_on(self, mesh) -> None:
        """Place shard z on mesh device z % ndev; later refreshes ship
        only the changed rows to it (``_gather_rows``)."""
        self.devices = zone_shard_devices(mesh, len(self.zones))
        self.zones = [jax.device_put(zone, d)
                      for zone, d in zip(self.zones, self.devices)]
        self.clouds = [jax.device_put(cl, d)
                       for cl, d in zip(self.clouds, self.devices)]


def zone_shard_devices(mesh: Mesh, n_zones: int) -> list:
    """Round-robin device placement for the fleet server's spatial zone
    shards (server/zones.py): zone z lives on mesh device z % ndev, so
    per-zone sync collects and queries run where the shard's arrays live.
    On the 1-device container every zone maps to the same device (no-op)."""
    devs = list(mesh.devices.flat)
    return [devs[z % len(devs)] for z in range(n_zones)]
