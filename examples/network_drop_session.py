"""End-to-end device-cloud session with a network outage (paper Fig. 1
scenario), replayed through the deterministic scenario engine: the device
streams RGB-D, the cloud maps; queries ride SemanticXR-SQ while the network
is up, fail over to SemanticXR-LQ on the object-level sparse local map
during the outage, and the missed updates coalesce into one packet on
reconnect.  Mid-run the scene SHRINKS: the RGB-D stream pauses after tick
8 (the camera looks elsewhere) and two mapped objects are removed — they
propagate as 9-byte tombstone rows that free the device slots.  (The pause
matters: frames rendered from the unchanged scene would immediately
re-detect the removed objects and re-insert them under new ids.)

This driver is a thin wrapper over ``repro.sim``: it only declares the
Scenario (client link + outage window + removal events) and pretty-prints
the resulting MetricsLog.  Run the same Scenario twice and the logs are
bit-identical (tests/test_scenario_engine.py holds the engine to that).

    PYTHONPATH=src python examples/network_drop_session.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np

from repro.core import Knobs, MappingServer
from repro.core.runtime import NetworkModel, PowerModel
from repro.data.scenes import make_scene, scene_stream
from repro.perception.embedder import OracleEmbedder
from repro.sim import (ClientSpec, NetTrace, ObjectEvent, PoseTrack,
                       QueryPlan, Scenario, ScenarioEngine)
from repro.sim.scenario import GridSpec
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    scene = make_scene(n_objects=25, seed=2)
    classes = {o.oid: o.class_id for o in scene.objects}
    emb = OracleEmbedder(embed_dim=256)
    kn = Knobs(server_capacity=256, client_capacity=64,
               max_object_points_server=512, max_object_points_client=128,
               max_detections_per_frame=16, min_obs_before_sync=1)
    srv = MappingServer(knobs=kn, embedder=emb)
    frames = list(scene_stream(scene, n_frames=60, keyframe_interval=5,
                               h=240, w=320))

    scenario = Scenario(
        seed=0, n_ticks=len(frames), tick_s=1.0, embed_dim=256, knobs=kn,
        grid=GridSpec(room=scene.room_size, nx=1, nz=1), budget=64,
        clients=(ClientSpec(
            cid=0,
            net=NetTrace(rtt_ms=20.0, outages=((4.0, 8.0),)),
            track=PoseTrack(anchor=(0.0, 1.5, 0.0), orbit_radius=0.0),
            subscribe_radius=scene.room_size),),
        # dynamic scene: two mapped objects vanish after the reconnect —
        # the server prunes them to tombstones, the client frees the slots
        events=(ObjectEvent(tick=9, kind="remove", oid=1),
                ObjectEvent(tick=9, kind="remove", oid=2)),
        query=QueryPlan(prob=0.6, radius=scene.room_size, k=3))

    # stream pauses after tick 8 so the removals are not re-observed
    engine = ScenarioEngine(scenario, mapper=srv, frames=frames[:9],
                            classes=classes, embedder=emb)
    log = engine.run()

    net = NetworkModel(rtt_ms=20.0, outages=((4.0, 8.0),))
    print(f"{'t':>5} {'net':>6} {'mode':>4} {'mapped':>6} {'tomb':>4} "
          f"{'local':>5} {'sentB':>7} {'q_ms':>7}")
    for i in range(log.n_ticks):
        t = i * scenario.tick_s
        up = net.is_up(t)
        mode = {1: "SQ", 0: "LQ", -1: "--"}[int(log.mode_sq[i, 0])]
        q = log.query_ms[i, 0]
        note = ""
        if log.events[i, 2]:
            note = f"  <- {int(log.events[i, 2])} removed (tombstones " \
                   f"{int(log.sent_tomb_bytes[i, 0])} B on the wire)"
        print(f"{t:5.1f} {'UP' if up else 'DOWN':>6} {mode:>4} "
              f"{int(log.server_live[i]):>6} "
              f"{int(log.server_tombstones[i]):>4} "
              f"{int(log.client_live[i, 0]):>5} "
              f"{int(log.sent_bytes[i, 0]):>7} "
              f"{'' if np.isnan(q) else f'{q:7.1f}'}{note}")

    pm = PowerModel()
    mean_p = float(log.power_w[log.client_active[:, 0], 0].mean())
    print(f"\ntotal downstream: {int(log.sent_bytes.sum())} B over "
          f"{log.n_ticks} ticks "
          f"({int(log.delivered.sum())} delivered, "
          f"{int(log.delayed.sum())} delayed packets)")
    print(f"device power (MODEL): {mean_p:.2f} W "
          f"({(mean_p / pm.idle_w - 1) * 100:.1f}% over idle)")
    print(f"device local-map memory: "
          f"{int(log.client_nbytes[-1, 0]) / 2**20:.1f} MiB (fixed cap)")


if __name__ == "__main__":
    main()
