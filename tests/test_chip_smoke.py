"""chip_smoke.py rehearsed on the CPU at tiny sizes: phase A (mapping),
phase B (serving), the four-device placed phase on four virtual CPU
devices, the no-TPU refusal, the oracle comparison it relies on, and the
compile-cache directory its entry points resolve."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.core.knobs import Knobs  # noqa: E402

TINY_SERVING = dict(C=8, ticks=8, n_live=96, cap=128, E=32, P=16, Pc=8,
                    nz=2, zcap=64, churn=16, budget=16, batch=8,
                    max_batches=2, base_hz=20.0, burst_hz=60.0)


def test_phase_mapping_tiny():
    kn = Knobs(server_capacity=128, max_object_points_server=64,
               max_detections_per_frame=8, min_obs_before_sync=1)
    out = cs.phase_mapping(knobs=kn, embed_dim=32, h=120, w=160,
                           n_objects=12, n_keyframes=4)
    assert out["objects_mapped"] > 0


def test_phase_serving_tiny_with_donated_collects():
    """donate=True rehearses, on the CPU, the donated session collects the
    chip turns on by default: no host read may touch a donated buffer."""
    out = cs.phase_serving(TINY_SERVING, donate=True, oracle_every=2)
    assert out["sync_sent_bytes"] == out["overlapped_sent_bytes"] > 0
    assert out["sync_queries"] == out["overlapped_queries"] > 0


def test_phase_sharded_on_four_virtual_devices():
    cfg = dict(TINY_SERVING, C=16, ticks=6)
    code = ("import sys, jax; sys.path.insert(0, %r); import chip_smoke as cs;"
            "print(cs.phase_sharded(%r, devices=jax.devices()[:4], "
            "donate=True))" % (str(ROOT), cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "check sharded_wire_packets_equal_device0: PASS" in proc.stdout
    assert "check sharded_placement_spans_4_devices: PASS" in proc.stdout


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    assert cs.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("got,ok", [
    ([3, 1, 2], True),             # exact order
    ([1, 3, 2], True),             # ranks 0/1 are a near tie
    ([3, 2, 1], False),            # ranks 1/2 are not
])
def test_topk_agreement_tolerates_only_near_ties(got, ok):
    want_o = np.array([3, 1, 2, 4])
    want_s = np.array([0.9, 0.9 - 5e-6, 0.5, 0.1])
    got_s = np.array([0.9, 0.9 - 5e-6, 0.5])
    assert cs.topk_agrees(np.array(got), got_s, want_o, want_s) is ok


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_dir_resolution(env_dir, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    want = str(ROOT / ".jax_cache")
    if env_dir is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = ("import jax; from repro.compile_cache import enable_compile_cache;"
            "print(enable_compile_cache());"
            "print(jax.config.jax_persistent_cache_min_compile_time_secs)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, "0.0"]
