"""Reduction of a ``jax.profiler`` trace to device busy time, per-executable
device time and named idle gaps.

``load`` turns the ``.xplane.pb`` that the profiler writes into plain data
(planes of lines of ``(name, start_ns, duration_ns)`` events), so the
reduction below can be checked on a small recorded trace without a chip.

On a TPU, each chip is a plane ``/device:TPU:<i>``: its ``XLA Ops`` line
holds one event per operation, its ``XLA Modules`` line one event per run
of a compiled program (named ``jit_<function>(<id>)``).  Busy time is the
union of the operation intervals, so overlapping operations count once.
Host threads are lines of the ``/host:CPU`` plane; the benchmark's own
``TraceAnnotation`` marks sit there and give the offset between the host
clock the program's spans use and the trace clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``trace_dir`` as
    ``[{"name", "lines": [{"name", "events": [[name, start, dur]]}]}]``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list) -> list:
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
            and any(l["name"] == OPS_LINE for l in p["lines"])]


def _line(plane: dict, name: str) -> list:
    for l in plane["lines"]:
        if l["name"] == name:
            return l["events"]
    return []


def union(intervals: list) -> list:
    """Merge [start, end] intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events: list, t0: float, t1: float) -> list:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def module_name(event_name: str) -> str:
    """``jit__collect_fleet_impl(123)`` -> ``jit__collect_fleet_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes: list, t0_ns: float, t1_ns: float) -> dict:
    """Device activity inside [t0_ns, t1_ns] of the trace clock.

    Returns ``window_s``, ``busy_s`` (mean over the device planes),
    ``modules`` {executable: device seconds, summed over planes / planes},
    ``module_runs`` {executable: runs per plane} and ``gaps``: the idle
    intervals of the first device plane, longest first."""
    devs = device_planes(planes)
    window_s = (t1_ns - t0_ns) / 1e9
    if not devs:
        return {"window_s": window_s, "busy_s": 0.0, "modules": {},
                "module_runs": {}, "gaps": [], "n_devices": 0}
    busy, modules, runs, gaps = 0.0, {}, {}, []
    for i, plane in enumerate(devs):
        op_ev = _clip(_line(plane, OPS_LINE), t0_ns, t1_ns)
        merged = union([[a, b] for _, a, b in op_ev])
        busy += sum(b - a for a, b in merged) / 1e9
        for name, a, b in _clip(_line(plane, MODULES_LINE), t0_ns, t1_ns):
            m = module_name(name)
            modules[m] = modules.get(m, 0.0) + (b - a) / 1e9
            runs[m] = runs.get(m, 0) + 1
        if i == 0:
            edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
            gaps = sorted(([edges[j], edges[j + 1]]
                           for j in range(0, len(edges), 2)
                           if edges[j + 1] > edges[j]),
                          key=lambda g: g[0] - g[1])
    n = len(devs)
    return {"window_s": window_s, "busy_s": busy / n,
            "modules": {k: v / n for k, v in modules.items()},
            "module_runs": {k: v // n for k, v in runs.items()},
            "gaps": gaps, "n_devices": n}


def host_events(planes: list, name: str) -> list:
    """Every host event called ``name``: [(start_ns, duration_ns)]."""
    out = []
    for p in planes:
        if p["name"].startswith(DEVICE_PREFIX):
            continue
        for l in p["lines"]:
            out.extend((s, d) for n, s, d in l["events"] if n == name)
    return sorted(out)


def name_gaps(gaps: list, spans: list, limit: int = 10) -> list:
    """Name each idle gap by the deepest host span covering its midpoint.

    ``spans`` are ``(name, start_ns, end_ns, depth)`` on the trace clock;
    a gap no span covers is ``host.other``."""
    out = []
    for s, e in gaps[:limit]:
        mid = (s + e) / 2
        best, depth = "host.other", -1
        for name, a, b, d in spans:
            if a <= mid <= b and d > depth:
                best, depth = name, d
        out.append([best, (e - s) / 1e9])
    return out


def top(table: dict, limit: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])
            ][:limit]
