"""Multi-tenant fleet session: one mapped scene, many XR clients.

Runs the FleetSimulator — C simulated clients with heterogeneous networks
(mixed RTTs, staggered outages), join/leave churn, poses wandering across
spatial zones — against one MappingServer-driven scene.  The server tick is
one vmapped collect dispatch per dirty zone (never a loop over clients),
and clients receive bytes only for the zones their pose overlaps.
Cross-client SQ queries are declarative `Query` specs (similarity + a
radius-around-the-client spatial predicate) multiplexed through the
continuous-batching scheduler; the epilogue runs zone- and label-filtered
queries straight against the zone-sharded fleet store (shard pruning
before dispatch).

    PYTHONPATH=src python examples/fleet_session.py [n_clients]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Knobs, MappingServer
from repro.data.scenes import make_scene, scene_stream
from repro.perception.embedder import OracleEmbedder
from repro.server import Query, ZoneGrid
from repro.sim import FleetSimulator
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    n_clients = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    n_ticks = 30
    kn = Knobs(server_capacity=256, client_capacity=64,
               max_object_points_server=256, max_object_points_client=64,
               max_detections_per_frame=16, min_obs_before_sync=1)
    emb = OracleEmbedder(embed_dim=128)
    scene = make_scene(n_objects=30, seed=4)
    classes = {o.oid: o.class_id for o in scene.objects}
    mapper = MappingServer(knobs=kn, embedder=emb)
    frames = list(scene_stream(scene, n_frames=n_ticks * 5,
                               keyframe_interval=5, h=120, w=160))

    sim = FleetSimulator(knobs=kn, embed_dim=128, n_clients=n_clients,
                         grid=ZoneGrid.for_room(scene.room_size, nx=2, nz=2),
                         seed=7)
    stats = sim.run(n_ticks=n_ticks, mapper=mapper, frames=frames,
                    embedder=emb, classes=classes, key=jax.random.key(0))

    print(f"fleet of {n_clients} clients, {n_ticks} ticks, "
          f"{sim.grid.n_zones} zones")
    print(f"  mapped objects:          {sim.server.zoned.n_active()}")
    print(f"  active clients at end:   {stats['active_at_end']}")
    print(f"  server tick (mean):      {stats['tick_ms_mean']:.2f} ms "
          f"for all clients")
    print(f"  downstream total:        {stats['down_bytes_total'] / 1e3:.1f}"
          f" kB ({stats['down_bytes_per_client'] / 1e3:.1f} kB/client)")
    print(f"  packets delivered:       {stats['delivered_packets']} "
          f"({stats['delayed_packets']} delivered after their send tick)")
    print(f"  SQ queries served:       {stats['served']} "
          f"(hedged: {stats['hedges']}), LQ fallbacks: "
          f"{stats['lq_fallbacks']}")
    per = np.array([c.session.down_bytes for c in sim.clients])
    print(f"  per-client bytes p50/p95: {np.percentile(per, 50) / 1e3:.1f} / "
          f"{np.percentile(per, 95) / 1e3:.1f} kB")

    # declarative queries straight against the zone-sharded fleet store:
    # zone membership prunes shards BEFORE dispatch, labels/min_points ride
    # the fused top-k as -inf score injection
    labels = sorted(set(classes.values()))
    spec = Query(embed=emb.embed_text(labels[0]),
                 zones=(0,), grid=Query.grid_of(sim.grid),
                 min_points=jnp.asarray(4), k=3)
    res = sim.server.query(spec)
    hits = [(int(o), round(float(s), 3))
            for o, s in zip(res.oids, res.scores) if o]
    print(f"  zone-0 query '{labels[0]}':  {hits}")
    spec = Query(embed=emb.embed_text(labels[1]),
                 near=(jnp.asarray([0.0, 1.5, 0.0]), jnp.asarray(3.0)),
                 labels=(int(labels[1]),), k=3)
    res = sim.server.query(spec)
    hits = [(int(o), round(float(s), 3))
            for o, s in zip(res.oids, res.scores) if o]
    print(f"  near+label '{labels[1]}' within 3 m of origin: {hits}")


if __name__ == "__main__":
    main()
