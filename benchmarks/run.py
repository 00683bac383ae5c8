"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the paper artifact it reproduces).  ``--json`` additionally writes
``BENCH_<suite>.json`` at the repo root so the perf trajectory is tracked
across PRs (see EXPERIMENTS.md)."""
import argparse
import inspect
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from benchmarks import (downstream_bw, fault_tolerance, fleet_scale,
                        ingest_tick, local_map_scale, mapping_latency,
                        power_model, query_engine, query_latency,
                        scenario_suite, serving_loop, upstream_bw)

SUITES = {
    "tab4_fig3_mapping": mapping_latency.run,
    "fig4_query": query_latency.run,
    "fig5_local_map": local_map_scale.run,
    "fig6_downstream": downstream_bw.run,
    "tab5_upstream": upstream_bw.run,
    "fig7_power": power_model.run,
    "ingest_tick": ingest_tick.run,
    "fleet_scale": fleet_scale.run,
    "serving_loop": serving_loop.run,
    "query_engine": query_engine.run,
    "scenario_suite": scenario_suite.run,
    "fault_tolerance": fault_tolerance.run,
}


def _jsonable(obj):
    """Coerce suite return values (numpy scalars/arrays, dataclasses) to
    plain JSON types; drop anything that won't serialize."""
    import numpy as np
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (int, float, str, bool)) or obj is None:
        return obj
    return repr(obj)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", "--suite", dest="only", default=None,
                    help="run one suite")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale scenes (slower)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI smoke (suites that support it)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<suite>.json at the repo root")
    ap.add_argument("--git-sha", default=None,
                    help="commit sha stamped into BENCH_history entries "
                         "(caller-supplied; not sampled in-process)")
    ap.add_argument("--date", default=None,
                    help="ISO date stamped into BENCH_history entries")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    print("name,us_per_call,derived")
    for name, fn in SUITES.items():
        if args.only and args.only != name:
            continue
        print(f"# --- {name} ---")
        kw = {"full": args.full}
        if args.smoke:
            if "smoke" not in inspect.signature(fn).parameters:
                # a suite without a smoke mode would run (and with --json
                # overwrite) its full-shape trajectory — skip it instead
                print(f"# {name}: no smoke mode, skipped")
                continue
            kw["smoke"] = True
        result = fn(**kw)
        if args.json:
            # smoke runs get their own file: never clobber the committed
            # full-shape perf trajectory with tiny-shape numbers
            suffix = "_smoke" if kw.get("smoke") else ""
            out = ROOT / f"BENCH_{name}{suffix}.json"
            payload = _jsonable(result)
            out.write_text(json.dumps(payload, indent=2) + "\n")
            print(f"# wrote {out}")
            # root file stays "latest"; history keeps the trajectory
            from repro.obs.trajectory import append_run
            hist = append_run(name, payload,
                              git_sha=args.git_sha, date=args.date,
                              smoke=bool(kw.get("smoke")))
            print(f"# appended {hist}")


if __name__ == '__main__':
    main()
