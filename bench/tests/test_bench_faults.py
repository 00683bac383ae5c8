"""The comparison that decides ``correct`` fails what it has to fail.

Each test drives a whole tiny run on the CPU with the timed path broken
underneath (the harness's look for a chip skipped), and sees ``correct``
come out false: the ingest step that returns the store unchanged, half of
the session collect's client batch left out, a query answer altered
where it is produced, and a fleet that never starts a zone's collect.  The venue runs on one chip, so it
has no exchange between chips to leave out.  The last test runs the
reference in bfloat16 in the program's place (the control) and sees it
fail the comparison.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import run
from bench.tests.test_bench_run import fixture_root


def _run(tmp_path, monkeypatch, seed: int = 4):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "ROOT", fixture_root(tmp_path))
    bench, cell, config, traffic = run.load_cell("venue_tiny.serve")
    res, ctx = run.run_cell(bench, cell, config, traffic, seed=seed,
                            seconds=3.0, trace=False)
    return res, ctx


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_sound_run_is_correct(tmp_path, monkeypatch):
    res, _ = _run(tmp_path, monkeypatch)
    assert res["correct"] is True, _checks(res)


def test_ingest_returning_state_unchanged(tmp_path, monkeypatch):
    from repro.serving import loop as loop_mod
    monkeypatch.setattr(loop_mod, "_apply_delta_donated",
                        lambda back, cur: back)
    monkeypatch.setattr(loop_mod, "_apply_delta2_donated",
                        lambda back, pending, cur: back)
    res, _ = _run(tmp_path, monkeypatch)
    c = _checks(res)
    assert res["correct"] is False
    assert c["mirror_faults"] > 0 and c["packet_faults"] > 0


def _broken_execute(monkeypatch, mangle):
    from repro.core import query as q
    real = q._execute

    def execute(spec, cols, *, use_pallas=False):
        return mangle(real(spec, cols, use_pallas=use_pallas))

    monkeypatch.setattr(q, "_execute", execute)


def test_half_of_collect_batch_left_out(tmp_path, monkeypatch):
    """The session collect computes every client's rows but ships only
    the first half of its client batch."""
    from repro.server import session
    real = session._collect_fleet

    def collect(*a, **kw):
        batch, synced, ever, nbytes, counts, idx = real(*a, **kw)
        C = counts.shape[0]
        keep = jnp.arange(C) < C // 2
        batch = batch._replace(valid=batch.valid & keep[:, None])
        return (batch, synced, ever, jnp.where(keep, nbytes, 0),
                jnp.where(keep, counts, 0), idx)

    monkeypatch.setattr(session, "_collect_fleet", collect)
    res, _ = _run(tmp_path, monkeypatch)
    assert res["correct"] is False
    assert _checks(res)["packet_faults"] > 0


def test_answer_altered_where_produced(tmp_path, monkeypatch):
    def nudge(res):
        return res._replace(scores=res.scores.at[:, 0].add(1e-3))

    _broken_execute(monkeypatch, nudge)
    res, _ = _run(tmp_path, monkeypatch)
    assert res["correct"] is False
    assert _checks(res)["query_score_gap"] > res["checks"][
        "query_score_gap"]["limit"]


def test_bf16_control_fails(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "ROOT", fixture_root(tmp_path))
    bench, cell, config, traffic = run.load_cell("venue_tiny.serve")
    res, ctx = run.run_cell(bench, cell, config, traffic, seed=9,
                            seconds=3.0, trace=False, control=True)
    assert res["correct"] is False
    c = _checks(res)
    assert c == {x["name"]: x["value"] for x in ctx.control_checks}
    assert c["query_score_gap"] > res["checks"]["query_score_gap"]["limit"]


def test_collect_never_started(tmp_path, monkeypatch):
    """The fleet issues no zone's collect: rows owed never ship."""
    from repro.server.fleet import FleetServer
    monkeypatch.setattr(FleetServer, "tick_start",
                        lambda self, deliverable, *, tick=None: [])
    res, _ = _run(tmp_path, monkeypatch)
    c = _checks(res)
    assert res["correct"] is False
    assert c["packet_faults"] > 0 and c["undelivered_pairs"] > 0
