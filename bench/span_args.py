"""Args of the program's spans within the profiled seconds of a
``--trace 1`` run, read from the profiler trace.

Each of the program's ``repro.obs`` spans opens a
``jax.profiler.TraceAnnotation`` of its name and hands it the span's args
at exit, so the profiler trace that a traced run writes under
``.bench_out/`` holds the spans with their args as event stats on a host
plane.  The harness's own reduction keeps names and times only.  Here each
span of ``run["trace"]["spans"]`` (the profiled seconds, on the trace
clock) is matched to the annotation of its name that starts nearest to
it, so the args are read for exactly the spans the other readers time.

A reader gets None where there is nothing to read: an untraced run, a
program whose spans leave no annotations, or a cell in which the layer
never ran.  Where the layer ran and the program annotates its spans but
the span or arg a metric reads is missing, it raises: a name that moved
must not drop the metric in silence.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
from pathlib import Path

from bench import xplane
from bench.layers import ticks

TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench_out"
MATCH_NS = 0.5e6     # a span's annotation starts within this of the span


@functools.lru_cache(maxsize=2)
def _annotations(path: str, mtime: float) -> dict:
    """{name: ([start_ns], [args])} of every host event in the trace at
    ``path``, each name's events in order of start."""
    from jax.profiler import ProfileData
    evs = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                evs.setdefault(ev.name, []).append(
                    (float(ev.start_ns), {k: _value(v) for k, v in ev.stats}))
    out = {}
    for name, xs in evs.items():
        xs.sort(key=lambda x: x[0])
        out[name] = ([s for s, _ in xs], [a for _, a in xs])
    return out


def _value(v):
    # a list arg reaches the trace as its Python repr
    if isinstance(v, str) and v.startswith("["):
        try:
            return json.loads(v)
        except ValueError:
            return v
    return v


def spans_with_args(run) -> list | None:
    """``(name, t0_ns, t1_ns, args)`` for each span of the profiled
    seconds, ``args`` None where no annotation of the span was found; None
    for an untraced run or one whose spans left no annotations."""
    tr = run.get("trace")
    if tr is None:
        return None
    paths = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    ann = _annotations(path, os.path.getmtime(path))
    out, found = [], False
    for name, t0, t1, _ in tr["spans"]:
        starts, args = ann.get(name, ((), ()))
        i = bisect.bisect_left(starts, t0)
        near = [j for j in (i - 1, i) if 0 <= j < len(starts)]
        j = min(near, key=lambda j: abs(starts[j] - t0), default=None)
        a = None
        if j is not None and abs(starts[j] - t0) <= MATCH_NS:
            a, found = args[j], True
        out.append((name, t0, t1, a))
    return out if found else None


def _layer_ran(spans, layer: str) -> bool:
    return spans is not None and any(s[0] == layer for s in spans)


def args_of(run, name: str, key: str, *, layer: str) -> list | None:
    """The ``key`` arg of every span called ``name`` that carries it, in
    order of start; None where nothing can be read or the span ``layer``
    never ran.  Raises where ``layer`` ran but no ``name`` span carries
    ``key``."""
    spans = spans_with_args(run)
    if not _layer_ran(spans, layer):
        return None
    vals = [a[key] for n, _, _, a in spans if n == name and a and key in a]
    if not vals:
        raise RuntimeError(f"no {name} span carries {key!r} in a traced run "
                           f"in which {layer} ran")
    return vals


def host_ms_per_tick(run, name: str, *, layer: str,
                     what: tuple = None) -> float | None:
    """Host ms per tick in the spans called ``name`` (those whose ``what``
    arg is in ``what``, where given); None where nothing can be read or
    the span ``layer`` never ran.  Raises where ``layer`` ran and no such
    span did."""
    spans = spans_with_args(run)
    if not _layer_ran(spans, layer) or not ticks(run):
        return None
    sel = [(t0, t1) for n, t0, t1, a in spans if n == name
           and (what is None or (a or {}).get("what") in what)]
    if not sel:
        raise RuntimeError(f"no {name} span{'' if what is None else what} "
                           f"in a traced run in which {layer} ran")
    return sum(t1 - t0 for t0, t1 in sel) / 1e6 / ticks(run)
