"""Calibration runs on the chip: the knee sweep, the program's readings over
many seeds, and the lower-precision control.  Not part of the benchmark's
own runs; every run here is a whole cell run in this one process.

    python3 bench/calibrate.py knee --workload venue.serve --seconds 10 \
        --rates 1 2 3 --seeds 5
    python3 bench/calibrate.py seeds --workload venue.serve --seconds 10 \
        --seeds 1 2 3
    python3 bench/calibrate.py control --workload venue.serve --seconds 10 \
        --seeds 1 2 3

``knee`` sets the traffic's per-client base query rate to each of
``--rates`` in turn.  ``control`` also replays the reference in bfloat16 in
the program's place: its result line is judged by the control's readings
(and has to read ``correct`` false), with the program's beside them.  Each run
prints one JSON line and appends it to ``chiprun_out/calibrate.jsonl``;
``--dump-trace`` (with ``seeds``) also runs one traced run and writes a
summary of the profiler trace and a small recorded slice of it there.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import run, xplane                 # noqa: E402

OUT = ROOT / "chiprun_out"


def _emit(line: dict) -> None:
    OUT.mkdir(exist_ok=True)
    text = json.dumps(line, default=lambda o: o.item())   # numpy scalars
    with open(OUT / "calibrate.jsonl", "a") as f:
        f.write(text + "\n")
    print(text, flush=True)


def dump_trace(trace_dir: Path, slice_s: float = 0.25) -> None:
    """Plane and line names with a few events each, and the first
    ``slice_s`` of the profiled window as a small recorded trace."""
    planes = xplane.load(str(trace_dir))
    summary = [{"plane": p["name"], "lines": [
        {"line": l["name"], "n": len(l["events"]), "head": l["events"][:8]}
        for l in p["lines"]]} for p in planes]
    (OUT / "trace_summary.json").write_text(json.dumps(summary, indent=1))
    marks = xplane.host_events(planes, "bench.clock")
    t0 = marks[0][0] if marks else min(
        e[1] for p in planes for l in p["lines"] for e in l["events"])
    keep = []
    for p in planes:
        lines = []
        for l in p["lines"]:
            ev = [e for e in l["events"] if t0 <= e[1] <= t0 + slice_s * 1e9]
            if ev and (p["name"].startswith(xplane.DEVICE_PREFIX)
                       or any(e[0] == "bench.clock" for e in ev)):
                lines.append({"name": l["name"], "events": ev})
        if lines:
            keep.append({"name": p["name"], "lines": lines})
    (OUT / "trace_small.json").write_text(json.dumps(
        {"t0_ns": t0, "t1_ns": t0 + slice_s * 1e9, "planes": keep}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("knee", "seeds", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--dump-trace", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = run.load_cell(args.workload)

    import jax
    if jax.devices()[0].platform != run.REQUIRED_PLATFORM:
        run.log("no accelerator; nothing was run")
        return 2
    run.log(f"compile cache: {run.enable_compile_cache()}")
    plan = [(s, r) for r in (args.rates or [None]) for s in args.seeds]
    for seed, rate in plan:
        trf = copy.deepcopy(traffic)
        if rate is not None:
            trf["queries"]["base_hz"] = rate
        t0 = time.perf_counter()
        res, ctx = run.run_cell(bench, cell, config, trf, seed=seed,
                                seconds=args.seconds, trace=False,
                                t_start=t0, control=args.mode == "control")
        line = {"mode": args.mode, "seed": seed, "rate": rate,
                "wall_s": time.perf_counter() - t0, "result": res,
                "info": ctx.info}
        if args.mode == "control":
            # the result carries the control's readings; these are the
            # program's, on the same seed
            line["program"] = {c["name"]: c["value"] for c in ctx.checks}
        _emit(line)
    if args.dump_trace:
        t0 = time.perf_counter()
        try:
            res, ctx = run.run_cell(bench, cell, config, traffic,
                                    seed=args.seeds[0] + 1000,
                                    seconds=args.seconds, trace=True,
                                    t_start=t0)
            _emit({"mode": "trace", "seed": args.seeds[0] + 1000,
                   "wall_s": time.perf_counter() - t0, "result": res,
                   "info": ctx.info})
        finally:
            # the trace is dumped even where its reduction failed
            dump_trace(ROOT / ".bench_out" / cell["name"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
