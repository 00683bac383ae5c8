"""Serve a mapped scene to many concurrent clients with continuous batching
and straggler hedging — the serving substrate under the declarative
SemanticXR query engine.

Requests are ``core.query.Query`` specs, not bare embeddings: open-vocab
similarity plus spatial (radius-around-user, in-view AABB) and attribute
(label set, min point count) predicates, all fused into the same top-k
dispatch per scheduler batch.

    PYTHONPATH=src python examples/serve_queries.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Knobs, MappingServer, Query
from repro.data.scenes import make_scene, scene_stream
from repro.perception.embedder import OracleEmbedder
from repro.serving.batching import BatchScheduler, make_query_step_fn
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    scene = make_scene(n_objects=30, seed=0)
    classes = {o.oid: o.class_id for o in scene.objects}
    emb = OracleEmbedder(embed_dim=256)
    kn = Knobs(server_capacity=256, max_object_points_server=256,
               max_detections_per_frame=16, min_obs_before_sync=1)
    srv = MappingServer(knobs=kn, embedder=emb)
    key = jax.random.key(0)
    for i, fr in enumerate(scene_stream(scene, n_frames=40,
                                        keyframe_interval=5, h=120, w=160)):
        srv.process_frame(fr, classes, jax.random.fold_in(key, i))

    # one fused predicate+score+top-k sweep per engine step (same-plan
    # requests stack into a single struct-of-arrays dispatch)
    step_fn = make_query_step_fn(lambda: srv.store, k=5, pad_to=8)
    sched = BatchScheduler(batch_size=8, step_fn=step_fn, hedge_after_ms=50.0)
    mapped = sorted(set(np.asarray(srv.store.label)[
        np.asarray(srv.store.active)]))
    rng = np.random.default_rng(0)
    user = jnp.asarray([0.0, 1.5, 0.0])

    t0 = time.perf_counter()
    n_req = 64
    rids = {}
    for i in range(n_req):
        cid = int(mapped[rng.integers(len(mapped))])
        qe = emb.embed_text(cid)
        if i % 3 == 0:           # "what's near me that looks like <text>?"
            spec = Query(embed=qe, near=(user, jnp.asarray(3.0)),
                         prox_weight=jnp.asarray(0.2), k=5)
        elif i % 3 == 1:         # label-filtered, well-observed objects only
            spec = Query(embed=qe, labels=tuple(int(c) for c in mapped[:4]),
                         min_points=jnp.asarray(8), k=5)
        else:                    # in-view selection: AABB + similarity
            spec = Query(embed=qe,
                         aabb=(jnp.asarray([-4.0, 0.0, -4.0]),
                               jnp.asarray([4.0, 2.5, 4.0])), k=5)
        rids[sched.submit(spec, priority=rng.uniform(0, 2))] = i % 3
    done = sched.drain()
    dt = time.perf_counter() - t0

    kinds = ["near+prox", "labels+min_points", "in-view aabb"]
    print(f"served {len(done)} declarative queries in {dt*1e3:.1f} ms "
          f"({len(done)/dt:.0f} qps, batch=8, hedges={sched.hedge_count})")
    for rid in list(done)[:3]:
        res = done[rid]
        hits = [(int(o), round(float(s), 3))
                for o, s in zip(res.oids, res.scores) if o]
        print(f"  [{kinds[rids[rid]]:18s}] hits: {hits}")

    # cluster-level query through the same compiler: "where is the densest
    # region matching <text>?" — the summaries ARE the results, no object
    # sweep at all (Query(level='cluster') + the coarse-to-fine index)
    from repro.core.query import execute_query
    from repro.index import ClusterIndex

    idx = ClusterIndex.for_target(srv.store, n_cells_target=16,
                                  min_flat_size=1)
    cid = int(mapped[0])
    spec = Query(embed=emb.embed_text(cid),
                 density_weight=jnp.asarray(0.5), k=3, level="cluster")
    cres = execute_query(srv.store, spec, index=idx)
    print(f"densest regions matching class {cid}:")
    for c, s, n, xyz in zip(np.asarray(cres.cells), np.asarray(cres.scores),
                            np.asarray(cres.counts),
                            np.asarray(cres.centroids)):
        if c >= 0:
            print(f"  cell {int(c):3d}: {int(n):2d} objects around "
                  f"({xyz[0]:+.1f}, {xyz[1]:+.1f}, {xyz[2]:+.1f}) "
                  f"score={float(s):.3f}")


if __name__ == "__main__":
    main()
