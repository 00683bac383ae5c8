"""Observability contract tests.

The load-bearing invariant: attaching the tracer + metrics registry to a
run OBSERVES and never PERTURBS — the golden churn scenario's MetricsLog
stays bit-identical to the committed snapshot with observability on.
Plus: deterministic histogram percentile math (empty/single-sample
edges), Chrome trace-export round-trip, the BENCH trajectory log, and
the regression gate (fails on an injected regression, passes on the
repo's real artifacts)."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.obs import (Histogram, MetricsRegistry, Tracer, get_registry,
                       get_tracer, set_registry, set_tracer)
from repro.obs.metrics import exact_percentiles
from repro.obs.trajectory import append_run, latest_run, load_history

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

GOLDEN = Path(__file__).parent / "golden" / "scenario_churn_v1.json"


@pytest.fixture
def obs():
    """Install a fresh tracer + registry; restore whatever was there."""
    tr, reg = Tracer(), MetricsRegistry()
    prev_tr, prev_reg = set_tracer(tr), set_registry(reg)
    yield tr, reg
    set_tracer(prev_tr), set_registry(prev_reg)


def _golden_scenario():
    from repro.sim import churn_scenario
    return churn_scenario(seed=23, n_objects=20, n_ticks=20, n_clients=3,
                          remove_frac=0.25, drain_ticks=8)


# ------------------------------------------------------------ replay purity
def test_golden_replay_unperturbed_by_observability(obs):
    """THE acceptance invariant: tracing + metrics on, the golden churn
    scenario's MetricsLog is bit-identical to the observability-off run
    and still matches the committed snapshot."""
    from repro.sim import run_scenario
    tr, reg = obs
    log_on = run_scenario(_golden_scenario())
    assert len(tr) > 0, "tracer saw no spans — instrumentation is dead"
    assert reg.histogram("engine_tick_ms").count() > 0
    set_tracer(None), set_registry(None)
    log_off = run_scenario(_golden_scenario())
    assert log_on.equals(log_off), \
        f"observability perturbed replay: {log_on.diff(log_off)}"
    log_on.assert_matches_snapshot(json.loads(GOLDEN.read_text()))


def test_engine_spans_cover_the_tick_loop(obs):
    from repro.sim import run_scenario
    tr, _ = obs
    run_scenario(_golden_scenario())
    names = {e[0] for e in tr.events}
    assert "engine.tick" in names
    assert "session.collect_fleet" in names
    assert "engine.client_step" in names
    # 20 ticks + 8 drain ticks
    assert len(tr.durations_ms("engine.tick")) == 28


# ------------------------------------------------------- percentile math
def test_exact_percentiles_empty_and_single():
    z = exact_percentiles([])
    assert z == {"n": 0, "p50": 0.0, "p95": 0.0, "p99": 0.0,
                 "mean": 0.0, "max": 0.0}
    s = exact_percentiles([7.5])
    assert s["n"] == 1
    assert s["p50"] == s["p95"] == s["p99"] == s["max"] == 7.5


def test_exact_percentiles_nearest_rank():
    xs = list(range(1, 101))          # 1..100
    p = exact_percentiles(xs)
    assert p["p50"] == 50 and p["p95"] == 95 and p["p99"] == 99
    # nearest-rank returns an observed sample, never an interpolation
    p = exact_percentiles([1.0, 2.0])
    assert p["p50"] == 1.0 and p["p99"] == 2.0


def test_histogram_percentile_edges():
    h = Histogram("t", bounds=(1.0, 10.0, 100.0))
    assert h.percentile(50) == 0.0            # empty series
    h.observe(5.0)
    # single sample: every percentile is its bucket's upper edge
    assert h.percentile(50) == h.percentile(99) == 10.0
    h.observe(500.0)                          # overflow bucket
    assert h.percentile(99) == float("inf")
    assert h.count() == 2


def test_histogram_percentiles_are_bucket_edges_and_deterministic():
    h1 = Histogram("a", bounds=(1.0, 2.0, 4.0, 8.0))
    h2 = Histogram("b", bounds=(1.0, 2.0, 4.0, 8.0))
    samples = [0.5, 1.5, 1.5, 3.0, 3.0, 3.0, 7.0, 7.0, 0.2, 1.0]
    for v in samples:
        h1.observe(v)
    for v in reversed(samples):               # order must not matter
        h2.observe(v)
    for p in (50, 95, 99):
        assert h1.percentile(p) == h2.percentile(p)
        assert h1.percentile(p) in (1.0, 2.0, 4.0, 8.0)
    # cross-check rank math against the raw-sample reference: the bucket
    # edge must be >= the true nearest-rank sample and <= the next edge
    ref = exact_percentiles(samples)
    assert h1.percentile(50) >= ref["p50"]
    assert h1.percentile(95) >= ref["p95"]


def test_histogram_labels_are_independent_series():
    h = Histogram("t", bounds=(1.0, 10.0))
    h.observe(0.5, stage="lift")
    h.observe(5.0, stage="embed")
    assert h.percentile(50, stage="lift") == 1.0
    assert h.percentile(50, stage="embed") == 10.0
    assert h.count() == 0                     # unlabeled series untouched


def test_registry_exports(tmp_path):
    reg = MetricsRegistry()
    reg.counter("bytes_total", "sent bytes").inc(100, client=0)
    reg.counter("bytes_total").inc(50, client=1)
    reg.gauge("live_objects").set(42)
    h = reg.histogram("lat_ms", bounds=(1.0, 10.0))
    h.observe(0.5), h.observe(20.0)
    snap = reg.snapshot()
    assert snap["counters"]["bytes_total"] == {'{client="0"}': 100,
                                               '{client="1"}': 50}
    assert snap["histograms"]["lat_ms"]["_"]["n"] == 2
    prom = reg.to_prometheus()
    assert 'bytes_total{client="0"} 100' in prom
    assert "# TYPE lat_ms histogram" in prom
    assert 'lat_ms_bucket{le="+Inf"} 2' in prom
    assert "lat_ms_count 2" in prom
    p = tmp_path / "m.json"
    reg.save(p)
    assert json.loads(p.read_text()) == snap


# -------------------------------------------------------- trace round-trip
def test_chrome_trace_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("outer", cat="engine", tick=3):
        with tr.span("inner", cat="query"):
            pass
        with tr.span("inner2", cat="sync") as sp:
            sp.set(zone=1)
    p = tmp_path / "trace.json"
    tr.save(p)
    doc = json.loads(p.read_text())           # valid JSON by construction
    evs = doc["traceEvents"]
    assert len(evs) == 3
    assert all(e["ph"] == "X" for e in evs)
    assert all(set(e) >= {"name", "cat", "pid", "tid", "ts", "dur", "args"}
               for e in evs)
    by = {e["name"]: e for e in evs}
    # nesting: children lie inside the parent's [ts, ts+dur] window
    o = by["outer"]
    for name in ("inner", "inner2"):
        c = by[name]
        assert o["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1e-6
        assert c["args"]["depth"] == o["args"]["depth"] + 1
    assert by["outer"]["args"]["tick"] == 3
    assert by["inner2"]["args"]["zone"] == 1


def test_span_disabled_path_is_noop():
    from repro.obs import span
    assert get_tracer() is None or True       # don't assume global state
    prev = set_tracer(None)
    try:
        sp = span("x")
        with sp as s:
            assert s.fence(123) == 123        # fence passes through
        assert span("y") is sp                # shared singleton
    finally:
        set_tracer(prev)


def test_fenced_tracer_blocks_on_jax_values():
    import jax.numpy as jnp
    tr = Tracer(fenced=True)
    with tr.span("dispatch", cat="test") as sp:
        sp.fence(jnp.arange(8) * 2)
    assert len(tr) == 1
    assert tr.durations_ms("dispatch")[0] >= 0.0


def test_traced_decorator(obs):
    from repro.obs import traced
    tr, _ = obs

    @traced("my.fn", cat="test")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert tr.durations_ms("my.fn")


# ------------------------------------------------------------- trajectory
def test_trajectory_append_and_load(tmp_path):
    h = tmp_path / "hist"
    p1 = append_run("s1", {"tick_ms": 1.0}, git_sha="abc", date="2026-08-08",
                    history_dir=h)
    append_run("s1", {"tick_ms": 2.0}, git_sha="def", date="2026-08-09",
               smoke=True, history_dir=h)
    assert p1 == h / "s1.jsonl"
    assert len(load_history("s1", history_dir=h)) == 2
    assert len(load_history("s1", history_dir=h, smoke=False)) == 1
    last = latest_run("s1", history_dir=h, smoke=True)
    assert last["git_sha"] == "def" and last["result"] == {"tick_ms": 2.0}
    assert latest_run("missing", history_dir=h) is None


# --------------------------------------------------------- regression gate
def _gate():
    from benchmarks import regression_gate
    return regression_gate


def test_gate_fails_on_injected_regression(tmp_path):
    """A 10x latency blow-up and a byte-count drift must both FAIL."""
    g = _gate()
    baseline = {"replay_bit_identical": True, "converged": True,
                "tick_ms_mean": 10.0, "sent_bytes_total": 1000,
                "tombstone_bytes": 50, "sq_queries": 5, "lq_queries": 1}
    bad = dict(baseline, tick_ms_mean=100.0, sent_bytes_total=1001,
               replay_bit_identical=False)
    rows = g.compare_suite(g.SPECS["scenario_suite"], baseline, bad)
    failed = {r["metric"] for r in rows if r["status"] == "FAIL"}
    assert failed == {"replay_bit_identical", "tick_ms_mean",
                      "sent_bytes_total"}
    # end-to-end through run_gate: history-backed baseline, nonzero exit
    hist = tmp_path / "hist"
    append_run("scenario_suite", baseline, git_sha="aaa", date="2026-08-08",
               history_dir=hist)
    (tmp_path / "BENCH_scenario_suite.json").write_text(json.dumps(bad))
    all_rows, n_fail = g.run_gate(["scenario_suite"], root=tmp_path,
                                  history_dir=hist)
    assert n_fail == 3
    md = g.dashboard_md(all_rows, smoke=False)
    assert "FAIL" in md and "tick_ms_mean" in md


def test_gate_passes_on_identical_run(tmp_path):
    g = _gate()
    base = {"replay_bit_identical": True, "converged": True,
            "tick_ms_mean": 10.0, "sent_bytes_total": 1000,
            "tombstone_bytes": 50, "sq_queries": 5, "lq_queries": 1}
    hist = tmp_path / "hist"
    append_run("scenario_suite", base, git_sha="aaa", date="2026-08-08",
               history_dir=hist)
    (tmp_path / "BENCH_scenario_suite.json").write_text(json.dumps(base))
    _, n_fail = g.run_gate(["scenario_suite"], root=tmp_path,
                           history_dir=hist)
    assert n_fail == 0
    # latency wobble inside the tolerance band also passes
    ok = dict(base, tick_ms_mean=10.0 * (1.0 + g.LAT) - 0.01)
    (tmp_path / "BENCH_scenario_suite.json").write_text(json.dumps(ok))
    _, n_fail = g.run_gate(["scenario_suite"], root=tmp_path,
                           history_dir=hist)
    assert n_fail == 0


def test_gate_passes_on_real_artifacts():
    """The committed BENCH artifacts gate cleanly against themselves
    (HEAD baseline == working tree at commit time)."""
    g = _gate()
    _, n_fail = g.run_gate()
    assert n_fail == 0


def test_gate_skips_without_baseline_or_artifact(tmp_path):
    g = _gate()
    all_rows, n_fail = g.run_gate(["scenario_suite"], root=tmp_path,
                                  history_dir=tmp_path / "none")
    assert n_fail == 0
    assert all_rows[0][2][0]["status"] == "SKIP"


# --------------------------------------------------------- LQ latency model
def test_lq_model_interpolates_measured_curve():
    from repro.sim.engine import LQ_MODEL_MS, load_lq_curve, lq_model_ms
    curve = load_lq_curve()
    assert curve is not None, "committed BENCH_query_engine.json missing"
    ns, ms = curve
    assert list(ns) == sorted(ns) and len(ns) >= 2
    # endpoints + clamping
    assert lq_model_ms(int(ns[0]), curve) == pytest.approx(float(ms[0]))
    assert lq_model_ms(int(ns[-1]) * 100, curve) == \
        pytest.approx(float(ms[-1]))
    assert lq_model_ms(1, curve) == pytest.approx(float(ms[0]))
    # interior point lies between its neighbors
    mid = int(np.sqrt(float(ns[0]) * float(ns[1])))
    v = lq_model_ms(mid, curve)
    assert min(ms[0], ms[1]) <= v <= max(ms[0], ms[1])
    # no curve -> documented fallback constant
    assert lq_model_ms(5000, None) == LQ_MODEL_MS


def test_lq_curve_missing_file(tmp_path):
    from repro.sim.engine import load_lq_curve
    assert load_lq_curve(tmp_path / "nope.json") is None
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert load_lq_curve(bad) is None


# ------------------------------------------------ spans on the profiler
def _clock_marks(n: int = 5) -> list:
    """perf_counter readings inside ``bench.clock`` annotations, as the
    benchmark harness takes them around a profiled window."""
    import time

    import jax
    marks = []
    for _ in range(n):
        with jax.profiler.TraceAnnotation("bench.clock"):
            marks.append(time.perf_counter())
    return marks


@pytest.fixture(scope="module")
def profiled_spans(tmp_path_factory):
    """A CPU profiler trace around nested spans of an installed tracer:
    (tracer events, planes loaded by ``bench.xplane.load``, clock marks)."""
    import time

    import jax
    from bench import xplane
    out = tmp_path_factory.mktemp("prof")
    tr = Tracer()
    prev = set_tracer(tr)
    jax.profiler.start_trace(str(out))
    try:
        marks = _clock_marks()
        from repro.obs import span
        with span("outer", cat="test", tick=1):
            with span("inner.a", cat="test") as sp:
                time.sleep(0.002)
                sp.set(rids=[3, 4], waiting=2)
            for _ in range(3):
                with span("inner.b", cat="test"):
                    time.sleep(0.001)
        marks += _clock_marks()
    finally:
        jax.profiler.stop_trace()
        set_tracer(prev)
    return list(tr.events), xplane.load(str(out)), marks


def test_spans_land_on_the_profiler_host_plane(profiled_spans):
    from bench import xplane
    events, planes, _ = profiled_spans
    assert {e[0] for e in events} == {"outer", "inner.a", "inner.b"}
    for name in {e[0] for e in events}:
        n = sum(1 for e in events if e[0] == name)
        assert len(xplane.host_events(planes, name)) == n, name


def test_clock_marks_map_spans_onto_their_annotations(profiled_spans):
    """The harness's offset (median over its ``bench.clock`` marks of
    trace start minus perf_counter) puts each span within 0.5 ms of its
    annotation."""
    from bench import xplane
    events, planes, marks = profiled_spans
    found = xplane.host_events(planes, "bench.clock")
    assert len(found) == len(marks)
    offs = sorted(s - p * 1e9 for (s, _), p in zip(found, marks))
    off = offs[len(offs) // 2]
    for name in {e[0] for e in events}:
        ours = sorted((t0, t1) for n, _, t0, t1, _, _ in events
                      if n == name)
        for (t0, t1), (s, d) in zip(ours,
                                    xplane.host_events(planes, name)):
            assert abs(t0 * 1e9 + off - s) < 0.5e6, name
            assert abs(t1 * 1e9 + off - (s + d)) < 0.5e6, name


def test_span_off_creates_no_annotation(tmp_path):
    import jax
    from bench import xplane
    from repro.obs import span
    prev = set_tracer(None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = span("off.span", cat="test", zone=1)
        with sp as s:
            assert s.on is False
            s.set(rows=[1, 2])
        assert span("off.other") is sp        # the shared no-op
        _clock_marks(1)
    finally:
        jax.profiler.stop_trace()
        set_tracer(prev)
    planes = xplane.load(str(tmp_path))
    assert xplane.host_events(planes, "bench.clock")
    assert not xplane.host_events(planes, "off.span")
    assert not xplane.host_events(planes, "off.other")
