"""Serving substrate: continuous batching and straggler hedging."""
import heapq
import time

import pytest

from repro.serving.batching import BatchScheduler, Request


def test_continuous_batching_serves_all():
    calls = []

    def step_fn(payloads):
        calls.append(len(payloads))
        return [p * 2 for p in payloads]

    s = BatchScheduler(batch_size=4, step_fn=step_fn)
    rids = [s.submit(i) for i in range(10)]
    done = s.drain()
    assert len(done) == 10
    assert all(done[r] == i * 2 for i, r in enumerate(rids))
    assert max(calls) <= 4


def test_priority_order():
    order = []

    def step_fn(payloads):
        order.extend(payloads)
        return payloads

    s = BatchScheduler(batch_size=1, step_fn=step_fn)
    s.submit("low", priority=0.1)
    s.submit("high", priority=9.0)
    s.submit("mid", priority=1.0)
    s.drain()
    assert order == ["high", "mid", "low"]


def test_straggler_hedging():
    """A request stuck in `running` past the hedge deadline is re-dispatched;
    first completion wins and the duplicate is dropped."""
    def step_fn(payloads):
        return [p for p in payloads]

    s = BatchScheduler(batch_size=2, step_fn=step_fn, hedge_after_ms=0.0)
    rid = s.submit("x")
    # simulate a worker that claimed the request but never finished
    import heapq
    from repro.serving.batching import Request
    req = Request(priority=-1.0, rid=rid, payload="x",
                  started_at=time.perf_counter() - 1.0)
    s.running[rid] = req
    s.waiting.clear()
    out = s.step()           # hedge fires, re-enqueues, completes
    assert s.hedge_count == 1
    assert s.done[rid] == "x"


def _run_adversarial_schedule(n, lost, batch_size, idle_steps):
    """Build a scheduler where the ``lost`` subset was claimed by workers
    that never return, then step until quiescent.  Returns (scheduler,
    emitted rid sequence)."""
    s = BatchScheduler(batch_size=batch_size,
                       step_fn=lambda ps: [p * 10 for p in ps],
                       hedge_after_ms=0.0)
    rids = [s.submit(i) for i in range(n)]
    s.waiting.clear()
    for rid, gone in zip(rids, lost):
        req = Request(priority=-1.0, rid=rid, payload=rid,
                      started_at=time.perf_counter() - 1.0)
        if gone:
            s.running[rid] = req          # claimed, never completes
        else:
            heapq.heappush(s.waiting, req)
    emitted = []
    steps = 0
    while (s.waiting or s.running) and steps < 200:
        emitted.extend(s.step().keys())
        steps += 1
        if steps in idle_steps:           # adversarial idle engine steps
            emitted.extend(s.step().keys())
    return s, rids, emitted


def test_hedging_idempotent_under_adversarial_timing():
    """Property: for any subset of lost workers, any batch size, and any
    interleaving of idle steps — every rid is served exactly once (hedged
    duplicates discarded by rid) and hedge_count counts exactly the lost
    requests."""
    hyp = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 10), st.data(), st.integers(1, 4),
           st.sets(st.integers(1, 20), max_size=4))
    def prop(n, data, batch_size, idle_steps):
        lost = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
        s, rids, emitted = _run_adversarial_schedule(n, lost, batch_size,
                                                     idle_steps)
        assert sorted(emitted) == sorted(rids)          # exactly once each
        assert s.done == {rid: i * 10 for i, rid in enumerate(rids)}
        assert s.hedge_count == sum(lost)
        assert not s.running and not s.waiting
        # the lost worker's duplicate finally shows up: discarded by rid,
        # nothing re-emitted, results unchanged
        for rid, gone in zip(rids, lost):
            if gone:
                heapq.heappush(s.waiting, Request(
                    priority=-1.0, rid=rid, payload=-999))
        late = s.step()
        assert late == {} and s.done == \
            {rid: i * 10 for i, rid in enumerate(rids)}

    prop()


def test_hedging_duplicate_discard_deterministic():
    """Hypothesis-free subset of the property above (always runs): mixed
    lost/healthy requests across batch sizes; exactly-once service, accurate
    hedge_count, late duplicates discarded."""
    for n, lost, bs in [(1, [True], 1), (4, [True, False, True, False], 2),
                        (6, [True] * 6, 3), (5, [False] * 5, 4)]:
        s, rids, emitted = _run_adversarial_schedule(n, lost, bs, set())
        assert sorted(emitted) == sorted(rids)
        assert s.hedge_count == sum(lost)
        assert s.done == {rid: i * 10 for i, rid in enumerate(rids)}
        for rid, gone in zip(rids, lost):
            if gone:
                heapq.heappush(s.waiting, Request(
                    priority=-1.0, rid=rid, payload=-999))
        assert s.step() == {}
        assert s.done == {rid: i * 10 for i, rid in enumerate(rids)}

