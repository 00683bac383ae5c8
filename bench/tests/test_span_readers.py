"""The per-layer readers of the program's span args, on hand-built runs.

Each test writes a small profiler trace on the CPU in which the
program's spans carry known args, points ``bench.span_args`` at it, and
hands the readers a ``run`` whose ``trace`` holds those spans at their
annotations' starts on the trace clock, with hand-chosen lengths; the
expected values are worked out by hand below.
"""
from __future__ import annotations

import jax
import pytest

from bench import run as harness
from bench import span_args, xplane
from bench.peaks import peaks_for
from repro.obs import Tracer, set_tracer, span

MS = 1e6    # ns


def _reader(name):
    return harness.load_file(harness.ROOT / "bench" / "metrics"
                             / f"{name}.py", f"test_reader_{name}")


NEW = ("query_wait_p95_ms", "query_backlog", "resolve_host_ms",
       "rows_owed_per_client", "fetch_host_ms.query", "fetch_host_ms.sync")
OLD = ("query_host_ms", "query_sweep_roofline", "device_idle_pct.serve",
       "mirror_host_ms", "framing_host_ms", "collect_device_ms")

# two ticks of the program's spans in the profiled seconds:
# (name, args, length in ms)
EVENTS = [
    ("serving.sync", {}, 5),
    ("zones.refresh", {"rows_changed": 3, "rows_freed": 0}, 1),
    ("host.fetch", {"what": "store"}, 2),
    ("serving.query", {"mode": "overlapped", "waiting": 1,
                       "waiting_ms": [9999.0]}, 1),
    ("query.batch", {"rids": [0, 1, 2, 3],
                     "wait_ms": [10.0, 20.0, 30.0, 40.0]}, 1),
    ("query.batch", {}, 1),                   # a step that found no work
    ("serving.resolve", {"rids": [0, 1, 2, 3]}, 3),
    ("host.fetch", {"what": "result"}, 2),
    ("session.collect_finish", {"zone": 0, "issue_tick": 7, "clients": 4,
                                "rows_shipped": 64, "rows_owed": 10,
                                "bytes": 1000}, 1),
    ("host.fetch", {"what": "counts"}, 1),
    ("serving.sync", {}, 5),
    ("zones.refresh", {"rows_changed": 0, "rows_freed": 1}, 1),
    ("host.fetch", {"what": "store"}, 4),
    ("serving.query", {"mode": "overlapped", "waiting": 2,
                       "waiting_ms": [2500.0, 50.0]}, 1),
    ("query.batch", {"rids": [4, 5], "wait_ms": [5.0, 1000.0]}, 1),
    ("serving.resolve", {"rids": []}, 1),
    ("host.fetch", {"what": "owed"}, 3),      # tracing's own read
    ("session.collect_finish", {"zone": 1, "issue_tick": 8, "clients": 6,
                                "rows_shipped": 90, "rows_owed": 30,
                                "bytes": 2000}, 1),
]


def _profile(tmp_path, events, *, annotate=True) -> list:
    """Profile ``events`` as the program's spans (``annotate`` False: a
    program whose spans leave no annotation) and return the run's spans:
    each at its annotation's start on the trace clock (``annotate`` False:
    at made-up starts) with its hand-chosen length."""
    jax.profiler.start_trace(str(tmp_path))
    prev = set_tracer(Tracer() if annotate else None)
    try:
        with jax.profiler.TraceAnnotation("bench.clock"):
            pass
        for name, a, _ in events:
            with span(name) as sp:
                if a:
                    sp.set(**a)
    finally:
        set_tracer(prev)
        jax.profiler.stop_trace()
    planes = xplane.load(str(tmp_path))
    starts = {n: [s for s, _ in xplane.host_events(planes, n)]
              for n in {n for n, _, _ in events}}
    spans = []
    for i, (name, _, ms) in enumerate(events):
        t0 = starts[name].pop(0) if annotate else i * 10 * MS
        spans.append((name, t0, t0 + ms * MS, 1))
    return spans


def _run(spans):
    trace = {"spans": spans, "window_s": 0.04, "busy_s": 0.01,
             "n_devices": 1,
             "modules": {"jit__execute": 0.002,
                         "jit__collect_fleet_impl": 0.006},
             "module_runs": {"jit__execute": 2,
                             "jit__collect_fleet_impl": 4}}
    return {"trace": trace, "shapes": {"n_slots": 16384, "embed_dim": 512,
                                       "query_batch": 16},
            "peaks": peaks_for("TPU v5 lite")}


def _traced(tmp_path, monkeypatch, events, *, annotate=True):
    spans = _profile(tmp_path, events, annotate=annotate)
    monkeypatch.setattr(span_args, "TRACE_ROOT", tmp_path)
    return _run(spans)


@pytest.mark.parametrize("name,want", [
    # nearest rank: ceil(0.95 * 8) = 8th of the six batched waits 5, 10,
    # 20, 30, 40, 1000 and the two still queued at the last serving.query,
    # 50 and 2500 (the first serving.query's 9999 is no longer queued)
    ("query_wait_p95_ms", 2500.0),
    ("query_backlog", (1 + 2) / 2),
    ("resolve_host_ms", (3 + 1) / 2),
    ("rows_owed_per_client", (10 + 30) / (4 + 6)),
    ("fetch_host_ms.query", 2 / 2),
    # store 2 + counts 1 + store 4; the owed read is tracing's own
    ("fetch_host_ms.sync", (2 + 1 + 4) / 2),
])
def test_new_readers_by_hand(tmp_path, monkeypatch, name, want):
    run = _traced(tmp_path, monkeypatch, EVENTS)
    assert _reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("query_backlog", 2.0),
    ("rows_owed_per_client", 30 / 6),
    ("fetch_host_ms.sync", 4.0),
])
def test_new_readers_read_only_the_runs_spans(tmp_path, monkeypatch, name,
                                              want):
    """Annotations of spans outside the profiled seconds (here the first
    tick's) are not read: args are matched to the run's own spans."""
    run = _traced(tmp_path, monkeypatch, EVENTS)
    second = [i for i, s in enumerate(run["trace"]["spans"])
              if s[0] == "serving.sync"][1]
    run["trace"]["spans"] = run["trace"]["spans"][second:]
    assert _reader(name).read(run) == pytest.approx(want)


def test_new_readers_read_nothing_without_annotations(tmp_path,
                                                      monkeypatch):
    """A program whose spans leave no annotations gives None, not an
    error: the harness then leaves the metric out."""
    run = _traced(tmp_path, monkeypatch, EVENTS, annotate=False)
    for name in NEW:
        assert _reader(name).read(run) is None, name
    assert all(_reader(n).read({"trace": None}) is None for n in NEW)


def test_new_readers_read_nothing_where_the_layer_never_ran(tmp_path,
                                                            monkeypatch):
    """A cell with no query step, zone mirror or collect (only ticks) has
    nothing to read."""
    run = _traced(tmp_path, monkeypatch, [("serving.sync", {}, 5)] * 2)
    for name in NEW:
        assert _reader(name).read(run) is None, name


def _without(name, key=None):
    """EVENTS with ``name`` spans dropped, or with their ``key`` arg
    dropped."""
    out = []
    for n, a, ms in EVENTS:
        if n == name and key is None:
            continue
        if n == name:
            a = {k: v for k, v in a.items() if k != key}
        out.append((n, a, ms))
    return out


@pytest.mark.parametrize("name,events", [
    ("query_wait_p95_ms", _without("query.batch", "wait_ms")),
    ("query_wait_p95_ms", _without("serving.query", "waiting_ms")),
    ("query_backlog", _without("serving.query", "waiting")),
    ("resolve_host_ms", _without("serving.resolve")),
    ("rows_owed_per_client", _without("session.collect_finish",
                                      "rows_owed")),
    ("fetch_host_ms.query", _without("host.fetch", "what")),
    ("fetch_host_ms.sync", _without("host.fetch")),
])
def test_new_readers_raise_where_the_layer_ran_unread(tmp_path, monkeypatch,
                                                      name, events):
    """Where the layer ran and the program annotates its spans, a span or
    arg that moved fails the run instead of dropping the metric."""
    run = _traced(tmp_path, monkeypatch, events)
    with pytest.raises(RuntimeError, match="in a traced run"):
        _reader(name).read(run)


def test_old_readers_unmoved_by_span_args(tmp_path, monkeypatch):
    spans = _profile(tmp_path, EVENTS)
    monkeypatch.setattr(span_args, "TRACE_ROOT", tmp_path)
    with_args = {n: _reader(n).read(_run(spans)) for n in OLD}
    monkeypatch.setattr(span_args, "TRACE_ROOT", tmp_path / "empty")
    assert {n: _reader(n).read(_run(spans)) for n in OLD} == with_args
    assert all(v is not None for v in with_args.values())
