"""The yardstick's parts on the CPU: exact percentiles, the wall-clock
traffic generators, and the trace reduction."""
from __future__ import annotations

import math
import statistics

import numpy as np
import pytest

from bench import generators as tg, stats, xplane


# -- percentiles ------------------------------------------------------------
@pytest.mark.parametrize("xs, p, want", [
    ([5.0], 95, 5.0),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 50, 50),
    (list(range(1, 21)), 95, 19),          # ceil(0.95 * 20) = 19th value
    ([3.0, 1.0, 2.0], 50, 2.0),
])
def test_percentile_nearest_rank(xs, p, want):
    assert stats.percentile(xs, p) == want


def test_percentile_empty_and_observed():
    assert stats.percentile([], 95) is None
    xs = np.random.default_rng(0).exponential(size=1001)
    assert stats.percentile(xs, 95) in xs


def test_spread_is_quartiles_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 20.0, 9.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


# -- traffic ------------------------------------------------------------------
def test_mmpp_seeded_and_repeatable():
    a = tg.mmpp_arrivals(np.random.default_rng(3), 8, 5.0, 1.0, 8.0, 0.6,
                         0.2)
    b = tg.mmpp_arrivals(np.random.default_rng(3), 8, 5.0, 1.0, 8.0, 0.6,
                         0.2)
    c = tg.mmpp_arrivals(np.random.default_rng(4), 8, 5.0, 1.0, 8.0, 0.6,
                         0.2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert np.all(np.diff(a[0]) >= 0) and a[0].min() >= 0 and a[0].max() < 5


def test_mmpp_offers_the_stated_rate():
    C, T = 64, 200.0
    due, who = tg.mmpp_arrivals(np.random.default_rng(1), C, T, 0.5, 8.0,
                                0.6, 0.2)
    want = tg.mmpp_mean_hz(0.5, 8.0, 0.6, 0.2) * C * T
    assert abs(len(due) - want) < 4 * math.sqrt(want) + 0.02 * want
    assert set(np.unique(who)) <= set(range(C))
    # bursts: some one-second windows of one client hold far more than
    # the base rate would give
    per = np.bincount(who * int(T) + due.astype(int), minlength=C * int(T))
    assert per.max() >= 4


def test_periodic_events_rate_and_phase():
    due, src = tg.periodic_events(np.random.default_rng(2), 4, 6.0, 10.0)
    assert len(due) in range(236, 241)
    for s in range(4):
        d = np.diff(due[src == s])
        assert np.allclose(d, 1 / 6.0)


def test_orbit_walks_at_the_stated_speed():
    anchor = np.zeros((2, 3))
    phase = np.array([0.0, 1.0])
    a = tg.orbit_poses(anchor, phase, 0.8, 1.0, 0.0)
    b = tg.orbit_poses(anchor, phase, 0.8, 1.0, 0.01)
    assert np.allclose(np.linalg.norm(a, axis=1), 0.8, atol=1e-6)
    assert np.allclose(np.linalg.norm(b - a, axis=1) / 0.01, 1.0, atol=1e-3)


def test_seed_reorders_the_same_work():
    """Two seeds offer the same arrivals and rows, in another order."""
    from bench.tests.test_bench_run import TINY_SERVE, TINY_VENUE
    from bench.drivers.serve import Traffic
    a = Traffic(TINY_VENUE, TINY_SERVE, 1, 10.0)
    a2 = Traffic(TINY_VENUE, TINY_SERVE, 1, 10.0)
    b = Traffic(TINY_VENUE, TINY_SERVE, 2 ** 31 + 7, 10.0)
    assert np.array_equal(a.q_embed, a2.q_embed)
    assert np.array_equal(a.q_due, b.q_due)
    assert np.array_equal(a.row_due, b.row_due)
    assert not np.array_equal(a.q_client, b.q_client)
    assert not np.array_equal(a.q_embed, b.q_embed)
    assert np.array_equal(np.sort(np.bincount(a.q_client, minlength=6)),
                          np.sort(np.bincount(b.q_client, minlength=6)))


# -- trace reduction ----------------------------------------------------------
def _plane(name, lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def test_reduce_busy_union_and_gaps():
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [["a", 10, 10], ["b", 15, 10], ["c", 40, 5]],
        "XLA Modules": [["jit_f(1)", 10, 15], ["jit_g(2)", 40, 5]]})
    host = _plane("/host:CPU", {"python": [["bench.clock", 0, 1]]})
    red = xplane.reduce([dev, host], 0, 100)
    assert red["busy_s"] == pytest.approx(20e-9)       # [10,25] + [40,45]
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["modules"] == {"jit_f": pytest.approx(15e-9),
                              "jit_g": pytest.approx(5e-9)}
    assert red["module_runs"] == {"jit_f": 1, "jit_g": 1}
    assert [g[1] - g[0] for g in red["gaps"]] == [55, 15, 10]
    named = xplane.name_gaps(red["gaps"], [("outer", 40, 100, 0),
                                           ("inner", 60, 90, 1)])
    assert named[0][0] == "inner" and named[1][0] == "host.other"


def test_reduce_clips_to_window():
    dev = _plane("/device:TPU:0", {"XLA Ops": [["a", 0, 100]],
                                   "XLA Modules": [["jit_f(3)", 0, 100]]})
    red = xplane.reduce([dev], 50, 150)
    assert red["busy_s"] == pytest.approx(50e-9)
    assert red["modules"]["jit_f"] == pytest.approx(50e-9)
