"""Object-level sparse local map (device side, paper Sec. 3.2).

Fixed-capacity per-object entries: embedding for query matching + a point
cloud further downsampled to the client budget.  Per-object memory is fixed,
so total device memory grows with retained objects, never with scene size.
When the map is full, admitting a higher-priority update evicts the
lowest-priority retained object (object-level update prioritization).

Priority = semantic relevance to app-declared interests
         + proximity to the user
         + app-declared class boosts.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.knobs import Knobs


class LocalMap(NamedTuple):
    ids: jax.Array        # [cap] int32 (0 = empty)
    active: jax.Array     # [cap] bool
    embed: jax.Array      # [cap, E] f32
    label: jax.Array      # [cap] int32
    points: jax.Array     # [cap, Pc, 3] f16 — client point budget
    n_points: jax.Array   # [cap] int32
    centroid: jax.Array   # [cap, 3] f32
    version: jax.Array    # [cap] int32 — last synced server version
    priority: jax.Array   # [cap] f32


def init_local_map(knobs: Knobs, embed_dim: int) -> LocalMap:
    cap, Pc = knobs.client_capacity, knobs.max_object_points_client
    return LocalMap(
        ids=jnp.zeros((cap,), jnp.int32),
        active=jnp.zeros((cap,), bool),
        embed=jnp.zeros((cap, embed_dim), jnp.float32),
        label=jnp.zeros((cap,), jnp.int32),
        points=jnp.zeros((cap, Pc, 3), jnp.float16),
        n_points=jnp.zeros((cap,), jnp.int32),
        centroid=jnp.zeros((cap, 3), jnp.float32),
        version=jnp.zeros((cap,), jnp.int32),
        priority=jnp.zeros((cap,), jnp.float32),
    )


def local_map_nbytes(m: LocalMap) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in m))


def compute_priority(embed, label, centroid, *, user_pos, knobs: Knobs,
                     interest_embeds=None):
    """Priority score for update admission / eviction (Sec. 3.2)."""
    prox = 1.0 / (1.0 + jnp.linalg.norm(centroid - user_pos, axis=-1))
    score = knobs.proximity_weight * prox
    if interest_embeds is not None and interest_embeds.shape[0] > 0:
        sem = jnp.max(jnp.matmul(embed, interest_embeds.T,
                                 precision=jax.lax.Precision.HIGHEST),
                      axis=-1)
        score = score + knobs.semantic_weight * jnp.maximum(sem, 0.0)
    if knobs.priority_classes:
        boost = jnp.isin(label, jnp.asarray(knobs.priority_classes,
                                            jnp.int32))
        score = score + knobs.priority_class_boost * boost
    return score


class ObjectUpdate(NamedTuple):
    """One object's delta, as shipped over the downlink (see updates.py)."""
    oid: jax.Array        # [] int32
    embed: jax.Array      # [E] f32
    label: jax.Array      # [] int32
    points: jax.Array     # [Pc, 3] f16
    n_points: jax.Array   # [] int32
    centroid: jax.Array   # [3] f32
    version: jax.Array    # [] int32
    deleted: jax.Array = None   # [] bool — tombstone row: the device frees
    #                             the slot and retires the id (None = live)


class UpdateBatch(NamedTuple):
    """Struct-of-arrays update packet: U object deltas as one pytree.

    The wire format equivalent of ``list[ObjectUpdate]`` — built in one
    vmapped gather on the server (updates.collect_updates) and applied in one
    jitted scan on the device (apply_updates_batch).  ``valid`` masks padding
    rows: U is bucketed to a power of two so jit retraces stay bounded.
    """
    oid: jax.Array        # [U] int32
    embed: jax.Array      # [U, E] f32
    label: jax.Array      # [U] int32
    points: jax.Array     # [U, Pc, 3] f16
    n_points: jax.Array   # [U] int32
    centroid: jax.Array   # [U, 3] f32
    version: jax.Array    # [U] int32
    valid: jax.Array      # [U] bool — padding mask
    deleted: jax.Array = None   # [U] bool — tombstone rows (None = all live)


def _admit_one_slot(m: LocalMap, u: ObjectUpdate, priority: jax.Array,
                    enabled: jax.Array):
    """Core admission/eviction step shared by the single and batched paths;
    returns ``(map, touched_slot)`` — the slot this row wrote or freed, or
    -1 when the row was a no-op (stale, padding, unadmitted, or a tombstone
    for an unretained id).  The touched slots feed cluster-index
    maintenance (repro.index.ClusterIndex.update_slots) without a diff.

    A tombstone row (``u.deleted``) frees the matching slot instead of
    admitting: id retired, entry deactivated — the slot is immediately
    reusable by later rows of the same batch (scan order).  Tombstones for
    ids the map never retained are no-ops.

    Idempotent and order-tolerant per object: a row whose version is BELOW
    the retained entry's is stale (a duplicated or reordered delivery) and
    is dropped; an equal-version row rewrites the same bytes (a no-op on
    the payload, refreshing only the priority).  The hardened transport
    leans on this — replaying any suffix of a client's update stream must
    never regress the map."""
    is_del = jnp.asarray(False) if u.deleted is None else u.deleted
    # existing entry?
    hit = (m.ids == u.oid) & m.active
    has = hit.any()
    slot_existing = jnp.argmax(hit)
    # else: first free slot, or eviction candidate
    free = ~m.active
    has_free = free.any()
    slot_free = jnp.argmax(free)
    evict_pri = jnp.where(m.active, m.priority, jnp.inf)
    slot_evict = jnp.argmin(evict_pri)
    can_evict = priority > evict_pri[slot_evict]
    slot = jnp.where(has, slot_existing,
                     jnp.where(has_free, slot_free, slot_evict))
    stale = has & (u.version < m.version[slot_existing])
    admit = (has | has_free | can_evict) & enabled & ~is_del & ~stale
    erase = is_del & has & enabled & ~stale

    def free_slot(m: LocalMap) -> LocalMap:
        return m._replace(
            ids=m.ids.at[slot_existing].set(0),
            active=m.active.at[slot_existing].set(False),
            version=m.version.at[slot_existing].set(0),
            n_points=m.n_points.at[slot_existing].set(0),
            priority=m.priority.at[slot_existing].set(0.0))

    m = jax.lax.cond(erase, free_slot, lambda x: x, m)

    def write(m: LocalMap) -> LocalMap:
        return LocalMap(
            ids=m.ids.at[slot].set(u.oid),
            active=m.active.at[slot].set(True),
            embed=m.embed.at[slot].set(u.embed),
            label=m.label.at[slot].set(u.label),
            points=m.points.at[slot].set(u.points.astype(m.points.dtype)),
            n_points=m.n_points.at[slot].set(u.n_points),
            centroid=m.centroid.at[slot].set(u.centroid),
            version=m.version.at[slot].set(u.version),
            priority=m.priority.at[slot].set(priority),
        )

    m = jax.lax.cond(admit, write, lambda x: x, m)
    touched = jnp.where(erase, slot_existing,
                        jnp.where(admit, slot, -1)).astype(jnp.int32)
    return m, touched


def _admit_one(m: LocalMap, u: ObjectUpdate, priority: jax.Array,
               enabled: jax.Array) -> LocalMap:
    return _admit_one_slot(m, u, priority, enabled)[0]


def prune_slots(m: LocalMap, drop: jax.Array) -> LocalMap:
    """Deactivate every entry where ``drop`` [cap] is True (id retired,
    version forgotten, slot reusable).  The zone-leave staleness fix rides
    this: when a client unsubscribes from a zone, the entries whose
    centroids route there are pruned so a later re-join ships a clean
    catch-up instead of leaving dead objects answering local queries."""
    keep = ~drop
    return m._replace(
        ids=jnp.where(keep, m.ids, 0),
        active=m.active & keep,
        version=jnp.where(keep, m.version, 0),
        n_points=jnp.where(keep, m.n_points, 0),
        priority=jnp.where(keep, m.priority, 0.0))


def apply_update(m: LocalMap, u: ObjectUpdate, priority: jax.Array) -> LocalMap:
    """Admit one object update; evict lowest-priority entry if full and the
    newcomer outranks it. jit-able."""
    return _admit_one(m, u, priority, jnp.asarray(True))


def apply_updates_batch(m: LocalMap, batch: UpdateBatch,
                        priorities: jax.Array) -> LocalMap:
    """Apply a whole UpdateBatch in one jitted call (scan inside the jit).

    Semantically identical to folding ``apply_update`` over the batch rows in
    order — including eviction order — but a single XLA dispatch instead of
    one per object (tests/test_batched_equivalence.py holds the two equal).
    """
    def step(m: LocalMap, x):
        row, pri = x
        u = ObjectUpdate(oid=row.oid, embed=row.embed, label=row.label,
                         points=row.points, n_points=row.n_points,
                         centroid=row.centroid, version=row.version,
                         deleted=row.deleted)
        return _admit_one(m, u, pri, row.valid), None

    m, _ = jax.lax.scan(step, m, (batch, priorities))
    return m


def apply_updates_batch_slots(m: LocalMap, batch: UpdateBatch,
                              priorities: jax.Array):
    """``apply_updates_batch`` that also returns the touched slots [U]
    (written or freed row per batch entry, -1 for no-ops) — the O(changes)
    feed for cluster-index maintenance on the device ingest path."""
    def step(m: LocalMap, x):
        row, pri = x
        u = ObjectUpdate(oid=row.oid, embed=row.embed, label=row.label,
                         points=row.points, n_points=row.n_points,
                         centroid=row.centroid, version=row.version,
                         deleted=row.deleted)
        return _admit_one_slot(m, u, pri, row.valid)

    return jax.lax.scan(step, m, (batch, priorities))
