"""The harness end to end on the CPU at tiny sizes.

The tests steer the harness from here: they point ``bench.run.ROOT`` at a
copy of the benchmark with tiny configurations added (files and entries
only, as a later change would add them), accept the CPU as the platform,
and leave the compile cache off.  ``test_no_tpu_refusal`` keeps the real
platform rule.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench import run

REPO = Path(__file__).resolve().parents[2]

TINY_VENUE = {
    "name": "venue_tiny", "source": "test fixture",
    "live_objects": 40, "capacity": 64, "embed_dim": 16,
    "server_points": 12, "client_points": 4, "labels": 5, "room_m": 16.0,
    "object_half_m": 7.0, "object_height_m": 2.0, "zones": [2, 2],
    "zone_capacity": 48, "clients": 6, "subscribe_radius_m": 6.0,
    "budget_rows": 4, "query_batch": 4, "query_batches_per_tick": 2,
    "priority": {"proximity_weight": 0.5, "semantic_weight": 0.5},
    "assumed": [], "reduced": []}

TINY_SERVE = {
    "driver": "serve", "shape_seed": 5,
    "queries": {"base_hz": 1.0, "burst_factor": 4.0, "burst_entry_hz": 0.5,
                "burst_dwell_s": 0.2, "k": 3, "near_radius_m": 8.0},
    "ingest": {"mappers": 2, "keyframe_hz": 3.0, "rows_per_keyframe": 3,
               "tombstone_share": 0.2, "drift_m": 0.5,
               "max_rows_per_tick": 16},
    "poses": {"hz": 60.0, "walk_m_per_s": 1.0, "orbit_m": 0.8},
    "settle_cap_ticks": 200, "drain_cap_s": 20.0}


def fixture_root(tmp_path: Path, *, extra_metric: bool = False) -> Path:
    """A checkout holding the benchmark plus a tiny venue cell, added
    from files and entries alone."""
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "venue_tiny.json").write_text(
        json.dumps(TINY_VENUE))
    (root / "bench" / "traffic" / "serve_tiny.json").write_text(
        json.dumps(TINY_SERVE))
    bench["configs"].append({"name": "venue_tiny", "source": "test",
                             "file": "bench/configs/venue_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "venue_tiny.serve",
                               "config": "venue_tiny",
                               "traffic": "serve_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "venue.serve" in m.get("workloads", ()):
            m["workloads"].append("venue_tiny.serve")
    if extra_metric:
        (root / "bench" / "metrics" / "window_ticks.py").write_text(
            "def read(run):\n    return run['window_ticks']\n")
        bench["end_to_end"].append({
            "name": "window_ticks", "unit": "ticks", "better": "higher",
            "bound": 0.25, "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def cpu_harness(monkeypatch):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    return monkeypatch


def _result(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_no_tpu_refusal(capsys):
    """On the CPU the real harness prints no result and exits nonzero."""
    rc = run.main(["--workload", "venue.serve", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_serve_cell_end_to_end(tmp_path, cpu_harness, capsys, seed):
    cpu_harness.setattr(run, "ROOT", fixture_root(tmp_path,
                                                  extra_metric=True))
    rc = run.main(["--workload", "venue_tiny.serve", "--seed", str(seed),
                   "--seconds", "3", "--trace", "0"])
    assert rc == 0
    res = _result(capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"query_p95_ms", "update_p95_ms",
                                   "setup_s", "window_ticks"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
