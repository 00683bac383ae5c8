"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload venue.serve --seed 7 --seconds 20 --trace 0

Everything about a cell is found by name: the cell in ``BENCHMARK.json``
names its configuration (``configs[].file``) and its traffic
(``bench/traffic/<traffic>.json``); the traffic file names its driver
(``bench/drivers/<driver>.py``), and each metric of the cell is read by
``bench/metrics/<metric>.py``.  A new cell, configuration or metric is new
files plus new entries; this file does not change.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the run installs the program's span tracer, takes a
``jax.profiler`` trace of the window's last seconds, and carries the
per-layer metrics.  Without an accelerator (JAX's platform is not a TPU),
or with fewer chips than the cell asks for, it prints no result and exits
with 2.  The last line of standard output is one JSON object; the numbers
compared with the reference, each beside its limit, are the last lines of
standard error and the ``checks`` key of that object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up is timed from here

import argparse
import importlib.util
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import stats, xplane               # noqa: E402
from bench.peaks import peaks_for             # noqa: E402

REQUIRED_PLATFORM = "tpu"
TRACE_SECONDS = 4.0                  # profiled tail of a --trace 1 window
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> str:
    """The program's persistent compile cache, inside the checkout
    (``<checkout>/.jax_cache``) unless ``JAX_COMPILATION_CACHE_DIR`` names
    another directory."""
    from repro.compile_cache import enable_compile_cache as enable
    return enable()


class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    hooks that mark the window and the traced part of it."""

    control = False       # bench/calibrate.py: also replay the control
    info = None           # what the driver reports beside the metrics

    def __init__(self, *, config, traffic, seed, seconds, trace, out_dir):
        import jax
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.trace_seconds = min(TRACE_SECONDS, seconds / 2)
        self.out_dir = out_dir
        self.log = log
        self.window_t0 = None
        self.compiles = []               # (fun_name, perf_counter)
        self.window_compiles = None
        self.tracer = None
        self.profile = None              # (perf start, perf end)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append((kw.get("fun_name"), time.perf_counter()))

    def start_window(self):
        self.window_t0 = time.perf_counter()
        self._n_compiles = len(self.compiles)
        if self.trace:
            from repro.obs.trace import Tracer, set_tracer
            self.tracer = Tracer()
            set_tracer(self.tracer)
        return self.tracer

    def _clock_marks(self) -> list:
        import jax
        marks = []
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.clock"):
                marks.append(time.perf_counter())
        return marks

    def trace_start(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._marks = self._clock_marks()
        self.profile = [time.perf_counter(), None]

    def end_window(self):
        self.window_compiles = self.compiles[self._n_compiles:]
        if self.profile is not None:
            import jax
            self.profile[1] = time.perf_counter()
            self._marks += self._clock_marks()
            jax.profiler.stop_trace()
        if self.tracer is not None:
            from repro.obs.trace import set_tracer
            set_tracer(None)

    def memory_peak(self) -> int | None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    # ------------------------------------------------------------------
    def reduce_trace(self) -> dict | None:
        """Device activity of the profiled seconds, the program's spans
        on the trace clock, and the breakdown the result line carries."""
        if self.profile is None:
            return None
        planes = xplane.load(str(self.out_dir))
        if not xplane.device_planes(planes):
            raise RuntimeError(
                f"the profiler trace under {self.out_dir} holds no device "
                f"plane ({xplane.DEVICE_PREFIX}... with an "
                f"{xplane.OPS_LINE!r} line); planes: "
                f"{sorted(p['name'] for p in planes)}")
        marks = xplane.host_events(planes, "bench.clock")
        if len(marks) != len(self._marks):
            raise RuntimeError(f"found {len(marks)} of {len(self._marks)} "
                               "clock marks in the trace")
        offs = sorted(s - p * 1e9 for (s, _), p in zip(marks, self._marks))
        off = offs[len(offs) // 2]
        p0, p1 = self.profile
        red = xplane.reduce(planes, p0 * 1e9 + off, p1 * 1e9 + off)
        spans = [(n, t0 * 1e9 + off, t1 * 1e9 + off, d)
                 for n, _, t0, t1, d, _ in self.tracer.events
                 if p0 <= t0 and t1 <= p1]
        red["spans"] = spans
        red["breakdown"] = {
            "device_ops": xplane.top(red["modules"]),
            "idle_gaps": xplane.name_gaps(red["gaps"], spans)}
        return red


def _entries(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = _entries(bench["workloads"], workload, "workload")
    cfg_entry = _entries(bench["configs"], cell["config"], "config")
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def run_cell(bench, cell, config, traffic, *, seed: int, seconds: float,
             trace: bool, t_start: float = T_PROCESS, control: bool = False):
    """Run the cell once; return (result object, context)."""
    import jax
    devs = jax.devices()
    peaks = peaks_for(devs[0].device_kind) if REQUIRED_PLATFORM == "tpu" \
        else None
    driver = load_file(ROOT / "bench" / "drivers" / f"{traffic['driver']}.py",
                       f"bench_driver_{traffic['driver']}")
    ctx = Context(config=config, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace,
                  out_dir=ROOT / ".bench_out" / cell["name"])
    ctx.control = control
    out = driver.run(ctx)
    # a control run is judged by the control's readings: it has to come
    # out as not correct
    ctx.checks = out["checks"]
    checks = ctx.control_checks if control else out["checks"]
    red = ctx.reduce_trace()
    run = dict(out, setup_s=ctx.window_t0 - t_start, trace=red,
               peaks=peaks, seconds=seconds)
    log(f"setup_s {run['setup_s']:.3f}; compiles inside the window: "
        f"{len(ctx.window_compiles)} "
        f"{sorted({n for n, _ in ctx.window_compiles})}")
    for name, xs in out["samples"].items():
        log(f"{name} latency (ms): {stats.summary(xs)}")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if not applies(m, cell["name"]):
            continue
        reader = load_file(ROOT / "bench" / "metrics" / f"{m['name']}.py",
                           f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif trace and "workloads" in m:
            # a metric that names this cell has to find something here:
            # a trace whose names moved must not drop it in silence
            raise RuntimeError(f"per-layer metric {m['name']} read nothing "
                               f"in {cell['name']}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": all(c["value"] <= c["limit"] for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)

    import jax
    devs = jax.devices()
    log(f"platform {devs[0].platform}, device_kind {devs[0].device_kind}, "
        f"device count {len(devs)}")
    if devs[0].platform != REQUIRED_PLATFORM:
        log(f"no accelerator: JAX platform is {devs[0].platform!r}, the "
            f"benchmark needs {REQUIRED_PLATFORM!r}; nothing was run")
        return 2
    if len(devs) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} chips, JAX sees "
            f"{len(devs)}; nothing was run")
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    result, _ = run_cell(bench, cell, config, traffic, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
