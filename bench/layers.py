"""Helpers the per-layer metric readers share: the program's spans and the
device's executables within the profiled seconds of a ``--trace 1`` run.
Each returns None where the run has nothing to read."""
from __future__ import annotations

TICK_SPAN = "serving.sync"      # the serving loop opens it once per tick


def _trace(run):
    return run.get("trace")


def ticks(run) -> int | None:
    tr = _trace(run)
    if tr is None:
        return None
    return sum(1 for s in tr["spans"] if s[0] == TICK_SPAN) or None


def span_ms_per_tick(run, names: tuple) -> float | None:
    tr, n = _trace(run), ticks(run)
    if tr is None or not n:
        return None
    total = sum(b - a for name, a, b, _ in tr["spans"] if name in names)
    return total / 1e6 / n


def _modules(run, part: str):
    tr = _trace(run)
    if tr is None:
        return []
    return [k for k in tr["modules"] if part in k]


def module_seconds(run, part: str) -> float | None:
    names = _modules(run, part)
    if not names:
        return None
    return sum(_trace(run)["modules"][k] for k in names)


def module_runs(run, part: str) -> int | None:
    names = _modules(run, part)
    if not names:
        return None
    return sum(_trace(run)["module_runs"].get(k, 0) for k in names)


def idle_pct(run) -> float | None:
    tr = _trace(run)
    if tr is None or not tr["n_devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
