"""SemanticXR quickstart: build a semantic map of a synthetic room, then ask
"where are my keys?"-style queries against it.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.core import Knobs, MappingServer, Query, execute_query
from repro.data.scenes import CLASS_NAMES, make_scene, scene_stream
from repro.perception.embedder import OracleEmbedder
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    scene = make_scene(n_objects=30, seed=0)
    classes = {o.oid: o.class_id for o in scene.objects}
    embedder = OracleEmbedder(embed_dim=256)
    knobs = Knobs(server_capacity=256, max_object_points_server=512,
                  max_detections_per_frame=16, min_obs_before_sync=1)
    server = MappingServer(knobs=knobs, embedder=embedder, mode="semanticxr")

    print("mapping the room ...")
    key = jax.random.key(0)
    for i, frame in enumerate(scene_stream(scene, n_frames=60,
                                           keyframe_interval=5, h=240, w=320)):
        t = server.process_frame(frame, classes, jax.random.fold_in(key, i))
        print(f"  keyframe {frame.idx:3d}: {t.total_ms:6.1f} ms, "
              f"{int(np.asarray(server.store.active.sum()))} objects mapped")

    print("\nqueries:")
    mapped = set(np.asarray(server.store.label)[np.asarray(server.store.active)])
    for cid in sorted(mapped)[:6]:
        res = execute_query(server.store,
                            Query(embed=embedder.embed_text(int(cid)), k=5))
        c = np.asarray(server.store.centroid[int(res.slots[0])])
        print(f"  'where is the {CLASS_NAMES[cid]}?' -> object "
              f"#{int(res.oids[0])} at ({c[0]:+.2f}, {c[1]:+.2f}, {c[2]:+.2f})"
              f"  score={float(res.scores[0]):.3f}")


if __name__ == "__main__":
    main()
