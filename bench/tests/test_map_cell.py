"""The ``map`` driver and its reference on the CPU at a small size.

A tiny shared room (120x160 keyframes, 12 objects, capacity 128, E=16,
P=64, 8 detections, 3 mappers, 4 viewers) runs through the harness, steered
as in ``test_bench_run``: a sound run is ``correct``, and each planted
fault makes it not correct — a shifted lifted point, a wrong target slot,
a packet row dropped, a back-buffer catch-up that leaves the clouds or the
embeddings behind, and the reference's own bfloat16 association and
bfloat16-depth lift in the program's place (the control).  Every sampled
keyframe is checked.
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.test_bench_run import fixture_root

TINY_ROOM = {
    "name": "room_tiny", "source": "test fixture", "scene_objects": 12,
    "room_m": 8.0, "frame_h": 120, "frame_w": 160, "depth_ratio": 5,
    "max_detections": 8, "min_bbox_px": 2000, "min_obs_before_sync": 2,
    "capture_fps": 30, "keyframe_interval": 5, "capacity": 128,
    "embed_dim": 16, "server_points": 64, "client_points": 8,
    "embedder": {"classes": 20, "noise": 0.4}, "mappers": 3, "clients": 4,
    "zones": [1, 1], "zone_capacity": 128, "subscribe_radius_m": 6.0,
    "budget_rows": 4, "query_batch": 4, "query_batches_per_tick": 2,
    "priority": {"proximity_weight": 0.5, "semantic_weight": 0.5},
    "assumed": [], "reduced": []}

TINY_MAP = {
    "driver": "map", "shape_seed": 9,
    "keyframes": {"hz": 3.0, "orbit_keyframes": 6},
    "queries": {"base_hz": 1.0, "burst_factor": 4.0, "burst_entry_hz": 0.5,
                "burst_dwell_s": 0.2, "k": 3, "near_radius_m": 8.0},
    "poses": {"walk_m_per_s": 1.0, "orbit_m": 0.8},
    "check_keyframes": 1000, "settle_cap_ticks": 200, "drain_cap_s": 20.0}

NEW_METRICS = ("detect_host_ms", "ingest_device_ms", "ingest_roofline",
               "keyframe_wait_p95_ms")


def map_root(tmp_path):
    root = fixture_root(tmp_path)
    (root / "bench" / "configs" / "room_tiny.json").write_text(
        json.dumps(TINY_ROOM))
    (root / "bench" / "traffic" / "map_tiny.json").write_text(
        json.dumps(TINY_MAP))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "room_tiny", "source": "test",
                             "file": "bench/configs/room_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "room_tiny.map",
                               "config": "room_tiny", "traffic": "map_tiny",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "room.map" in m.get("workloads", ()):
            m["workloads"].append("room_tiny.map")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(tmp_path, monkeypatch, seed=5, control=False):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "ROOT", map_root(tmp_path))
    bench, cell, config, traffic = run.load_cell("room_tiny.map")
    res, ctx = run.run_cell(bench, cell, config, traffic, seed=seed,
                            seconds=3.0, trace=False, control=control)
    return res, ctx


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_sound_map_run_is_correct(tmp_path, monkeypatch):
    res, ctx = _run(tmp_path, monkeypatch, seed=2 ** 31 + 7)
    assert res["correct"] is True, (_checks(res), ctx.info)
    assert ctx.info["keyframes_checked"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"update_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_shifted_lifted_point(tmp_path, monkeypatch):
    from repro.kernels import ops
    real = ops.lift_compact

    def lift_compact(*a, **kw):
        pts, *rest = real(*a, **kw)
        return (pts.at[0, 0, 0].add(0.01), *rest)

    monkeypatch.setattr(ops, "lift_compact", lift_compact)
    res, _ = _run(tmp_path, monkeypatch)
    c = _checks(res)
    assert res["correct"] is False
    assert c["lift_gap_m"] > res["checks"]["lift_gap_m"]["limit"]


def test_wrong_target_slot(tmp_path, monkeypatch):
    """Each detection's scores are read one slot off, so a merge lands in
    the neighbouring slot."""
    from repro.core import association as assoc
    real = assoc.association_scores

    def association_scores(*a, **kw):
        score, cent = real(*a, **kw)
        return jnp.roll(score, 1, axis=1), cent

    monkeypatch.setattr(assoc, "association_scores", association_scores)
    res, _ = _run(tmp_path, monkeypatch)
    assert res["correct"] is False
    assert _checks(res)["assoc_faults"] > 0


def test_dropped_packet_row(tmp_path, monkeypatch):
    """The collect ships every row but drops the first row of client 0's
    batch from its packet."""
    from repro.server import session
    real = session._collect_fleet

    def collect(*a, **kw):
        batch, synced, ever, nbytes, counts, idx = real(*a, **kw)
        batch = batch._replace(valid=batch.valid.at[0, 0].set(False))
        return batch, synced, ever, nbytes, counts, idx

    monkeypatch.setattr(session, "_collect_fleet", collect)
    res, _ = _run(tmp_path, monkeypatch)
    assert res["correct"] is False
    assert _checks(res)["packet_faults"] > 0


@pytest.mark.parametrize("column", ["points", "embed"])
def test_catch_up_leaves_a_row_column_behind(tmp_path, monkeypatch, column):
    """The back buffer's row copy leaves one row column as it was, so a
    keyframe mapped there is scored and merged against an old row."""
    from repro.serving import loop as loop_mod
    real = loop_mod._copy_rows_donated

    def copy_rows(back, front, slots):
        old = jnp.copy(getattr(back, column))
        return real(back, front, slots)._replace(**{column: old})

    monkeypatch.setattr(loop_mod, "_copy_rows_donated", copy_rows)
    res, ctx = _run(tmp_path, monkeypatch)
    assert res["correct"] is False
    c = _checks(res)
    if column == "points":
        assert c["lift_gap_m"] > res["checks"]["lift_gap_m"]["limit"]
    else:
        assert c["assoc_faults"] > 0


def test_bf16_association_control_fails(tmp_path, monkeypatch):
    res, ctx = _run(tmp_path, monkeypatch, seed=11, control=True)
    assert res["correct"] is False
    c = _checks(res)
    assert c == {x["name"]: x["value"] for x in ctx.control_checks}
    for name in ("assoc_score_gap", "lift_gap_m", "centroid_gap_m"):
        limit = res["checks"][name]["limit"]
        # the control's lower precision fails each limit the program meets
        assert c[name] > limit
        assert {x["name"]: x["value"] for x in ctx.checks}[name] < limit


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_untraced(name):
    """An untraced run, or a program without the spans, gives None."""
    reader = run.load_file(run.ROOT / "bench" / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")
    assert reader.read({"trace": None, "peaks": None, "shapes": {}}) is None


def test_ingest_readers_on_spans_and_modules():
    from bench.mapping import ingest_bytes, ingest_flops
    from bench.peaks import PEAKS
    spans = [("mapping.detect", 0.0, 40e6, 2), ("mapping.ingest", 40e6,
                                                 41e6, 2),
             ("mapping.detect", 50e6, 80e6, 2), ("mapping.ingest", 80e6,
                                                 81e6, 2)]
    shapes = {"n_slots": 4096, "embed_dim": 512, "max_detections": 32,
              "server_points": 2000, "depth_hw": [144, 256]}
    trace = {"spans": spans, "modules": {"jit_ingest_frame": 0.01},
             "module_runs": {"jit_ingest_frame": 2}}
    r = {"trace": trace, "peaks": PEAKS["TPU v5 lite"], "shapes": shapes}
    read = {n: run.load_file(run.ROOT / "bench" / "metrics" / f"{n}.py",
                             f"bench_metric_{n}").read for n in NEW_METRICS}
    assert read["detect_host_ms"](r) == pytest.approx(35.0)
    assert read["ingest_device_ms"](r) == pytest.approx(5.0)
    args = tuple(shapes.values())
    least = max(ingest_bytes(*args) / 819e9, ingest_flops(*args) / 197e12)
    assert read["ingest_roofline"](r) == pytest.approx(
        100 * 2 * least / 0.01)
    # the keyframe queue is read off the spans' args in the profile: a
    # run with no profile under .bench_out reads nothing, and does not raise
    assert read["keyframe_wait_p95_ms"]({"trace": None}) is None
