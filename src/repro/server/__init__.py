"""Multi-tenant fleet server: the paper's server "multiplexes
perception/caption/query work from many XR clients" (Sec. 3.2) — this
subsystem turns the single-tenant pieces into that server.

Three layers:

``session``  SessionManager — C clients' sync state as stacked arrays
             (``synced_version: [C, N]``, per-client pose / min-obs knobs),
             so one update tick for the whole fleet is ONE jitted vmapped
             collect dispatch (`_collect_fleet`) producing C packets, not a
             Python loop over `core.updates.collect_updates`.

``zones``    ZoneShardedStore — objects partitioned into spatial zones
             (grid over the room plane), each zone an independent
             `core.store.ObjectStore` shard, placeable on mesh devices via
             `zones.zone_shard_devices`.  Each shard keeps its rows'
             client-resolution clouds (`session.ClientClouds`), written
             with the rows, for the collect to gather.  Clients subscribe
             to the zones their pose overlaps; downstream work scales with
             per-client zone *changes*, not fleet size.

``mesh``     ClientRoster / MeshSessionTier / MeshFleetPacket — the client
             axis of a zone's session tier partitioned across S session
             shards (one per mesh device) by subscribed-zone affinity;
             control-plane messages route to the owning shard, the k-way
             merge happens only at the wire boundary, packets stay
             byte-identical to the single-device path
             (`FleetServer(n_session_shards=S)`).

``fleet``    FleetServer — the zones x sessions composition and its
             control plane (sync epochs, ack routing, tombstone
             retirement).  The simulated client fleet that drives it
             lives in `sim.fleet`.

Queries against the fleet store go through the declarative engine
(`core.query`, re-exported here): `FleetServer.query(Query(...))` compiles
the spec against the zone-sharded store — zone/near predicates prune shards
before dispatch, every selected shard runs the same fused plan.

Benchmarks: `benchmarks/fleet_scale.py` (tick latency and per-client
downstream bytes vs fleet size C) -> BENCH_fleet_scale.json; see
EXPERIMENTS.md § Fleet scale.  Tests: tests/test_fleet.py.
"""
from repro.core.query import (Query, QueryResult, CompiledQuery,
                              compile_query, execute_query, stack_queries)
from repro.server.session import (FleetBatch, FleetPacket, FleetSync,
                                  SessionManager)
from repro.server.zones import ZoneGrid, ZoneShardedStore
from repro.server.mesh import (ClientRoster, MeshFleetPacket,
                               MeshSessionTier)
from repro.server.fleet import FleetServer
