"""Span tracer: nested wall-clock spans with Chrome trace-event export.

Design constraints (the reasons this file is small and boring):

* **Near-zero overhead when off.**  Instrumented hot paths call the
  module-level ``span(...)`` helper; with no tracer installed it returns a
  shared no-op singleton — one global load and one ``is None`` test per
  call site, no allocation.
* **Deterministic replay stays deterministic.**  Spans record wall clock,
  but only into the tracer's own buffer — never into any value the
  instrumented code returns.  Enabling tracing on a ``ScenarioEngine`` run
  leaves the ``MetricsLog`` bit-identical (tested).
* **Compile-safe.**  Spans wrap *dispatch boundaries* (host-side calls
  into jitted functions), never code inside a jitted body — a tracer call
  under ``jax.jit`` would trace once and lie forever.  For async dispatch
  the span can *fence*: hand the result pytree to ``Span.fence`` and — on
  a ``Tracer(fenced=True)`` — the exit timestamp is taken after
  ``jax.block_until_ready``, so a span covering ``_collect_fleet``
  measures the real device cost, not the dispatch enqueue.  Fencing is
  opt-in because the extra syncs serialize work that would otherwise
  overlap (it trades wall-clock overhead for attribution honesty).
* **On the profiler's clock too.**  Each span also opens a
  ``jax.profiler.TraceAnnotation`` of its name for its extent and hands
  it the span's args at exit, so a ``jax.profiler`` trace shows the
  program's spans, with their args as event stats, on its host plane
  beside the device ops.  Outside a profiling session the annotation is
  a no-op.
* **Args link the spans.**  Within a tick, the span that caused another
  contains it; across ticks, spans link by id in their args (request ids
  ``rids``, a collect's ``issue_tick``).  Call sites compute args only
  when ``Span.on`` is true, so tracing off computes nothing.  A callee
  annotates the span its caller opened through ``current_span()``.

Export is the Chrome trace-event JSON format (chrome://tracing, Perfetto
UI): complete events (``"ph": "X"``) with microsecond timestamps; nesting
is implicit from containment per (pid, tid).
"""
from __future__ import annotations

import functools
import json
import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "current_span", "get_tracer", "set_tracer",
           "span", "traced"]


class _NullSpan:
    """Shared no-op span: the disabled-path cost is one ``is None`` test."""
    __slots__ = ()
    on = False          # call sites compute span args only when on

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, value):
        return value

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


@dataclass
class Span:
    """One open span; append-to-buffer happens at exit."""
    tracer: "Tracer"
    name: str
    cat: str
    t0: float = 0.0
    tid: int = 0
    args: dict = None
    _fence: object = None
    _ann: TraceAnnotation = None
    on = True

    def __enter__(self):
        self.tid = len(self.tracer._open)
        self.tracer._open.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def fence(self, value):
        """Block on ``value`` (a JAX array/pytree) before the span closes —
        async dispatch must not make the stage look free."""
        self._fence = value
        return value

    def set(self, **args):
        """Attach key/value args shown in the trace viewer."""
        if self.args is None:
            self.args = {}
        self.args.update(args)

    def __exit__(self, *exc):
        if self._fence is not None and self.tracer.fenced:
            import jax
            jax.block_until_ready(self._fence)
        t1 = time.perf_counter()
        if self.args:
            self._ann.set_metadata(**self.args)
        self._ann.__exit__(*exc)
        tr = self.tracer
        tr._open.pop()
        tr.events.append((self.name, self.cat, self.t0, t1, self.tid,
                          self.args))
        return False


@dataclass
class Tracer:
    """Collects spans; export with ``chrome_trace()`` / ``save()``."""
    events: list = field(default_factory=list)   # (name, cat, t0, t1,
    #                                               depth, args)
    # fencing is opt-in: Tracer(fenced=True) blocks on each span's fenced
    # pytree before closing, charging async device work to the span that
    # dispatched it.  Off by default — the extra syncs serialize work that
    # would otherwise overlap, so the unfenced tracer stays in the <5%
    # overhead budget while the fenced one trades overhead for honesty.
    fenced: bool = False
    # the open spans, outermost first (``current_span`` reads the last)
    _open: list = field(default_factory=list)
    _origin: float = field(default_factory=time.perf_counter)

    def span(self, name: str, cat: str = "", **args) -> Span:
        return Span(self, name, cat, args=args or None)

    def clear(self) -> None:
        self.events.clear()
        self._open.clear()
        self._origin = time.perf_counter()

    def __len__(self) -> int:
        return len(self.events)

    # ------------------------------------------------------------------
    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON object (``{"traceEvents": [...]}``).

        Spans become complete events; the recorded nesting depth maps to
        ``tid`` so sibling stacks render as lanes and containment shows
        parent/child (Perfetto infers nesting from time containment per
        track, which holds by construction here: a child's [t0, t1] lies
        inside its parent's).
        """
        evs = []
        for name, cat, t0, t1, depth, args in self.events:
            ev = {"name": name, "cat": cat or "default", "ph": "X",
                  "pid": 1, "tid": 1,
                  "ts": (t0 - self._origin) * 1e6,
                  "dur": max((t1 - t0) * 1e6, 0.0),
                  "args": dict(args) if args else {}}
            ev["args"]["depth"] = depth
            evs.append(ev)
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)

    # ------------------------------------------------------------------
    def durations_ms(self, name: str | None = None) -> list:
        """[ms] span durations (optionally filtered by name) — the bridge
        from traces to metrics histograms."""
        return [(t1 - t0) * 1e3 for n, _, t0, t1, _, _ in self.events
                if name is None or n == name]


# ---------------------------------------------------------------------------
# the process-wide tracer (None = tracing off, the default)
# ---------------------------------------------------------------------------
_TRACER: Tracer | None = None


def get_tracer() -> Tracer | None:
    return _TRACER


def set_tracer(tracer: Tracer | None) -> Tracer | None:
    """Install (or clear, with None) the process-wide tracer; returns the
    previous one so callers can restore it."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


def span(name: str, cat: str = "", **args):
    """Open a span on the process-wide tracer (no-op when tracing is off).

        with obs.span("fleet.collect", cat="sync", zone=z) as sp:
            pkt = sess.collect(...)
            sp.fence(pkt.batch)
    """
    t = _TRACER
    if t is None:
        return _NULL_SPAN
    return t.span(name, cat, **args)


def current_span():
    """The innermost open span of the process-wide tracer, or the no-op
    span when tracing is off or no span is open."""
    t = _TRACER
    if t is None or not t._open:
        return _NULL_SPAN
    return t._open[-1]


def traced(name: str | None = None, cat: str = ""):
    """Decorator form: trace every call of ``fn`` as one span."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            t = _TRACER
            if t is None:
                return fn(*a, **kw)
            with t.span(label, cat):
                return fn(*a, **kw)
        return wrapper
    return deco
