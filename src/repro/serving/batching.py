"""Serving substrate: continuous batching + straggler mitigation.

The SemanticXR server multiplexes perception/caption/query work from many
XR clients.  Requests join a waiting queue; each engine step assembles a
fixed-size batch from running + waiting requests (continuous batching — a
finished request's slot is refilled next step, no batch drain).  Straggler
mitigation: a request whose assigned worker misses its deadline is hedged —
re-enqueued at the front for the next step; first completion wins, the
duplicate is cancelled (idempotent by request id).
"""
from __future__ import annotations

import heapq
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from repro.obs.trace import current_span, span as obs_span


@dataclass(order=True)
class Request:
    priority: float
    rid: int = field(compare=False)
    payload: Any = field(compare=False)
    enqueued_at: float = field(compare=False, default=0.0)
    deadline_ms: float = field(compare=False, default=100.0)
    started_at: float = field(compare=False, default=0.0)
    hedged: bool = field(compare=False, default=False)


@dataclass
class BatchScheduler:
    batch_size: int
    step_fn: Callable[[list], list]       # batch of payloads -> results
    hedge_after_ms: float = 50.0
    waiting: list = field(default_factory=list)   # heap by priority
    running: dict = field(default_factory=dict)   # rid -> Request
    done: dict = field(default_factory=dict)      # rid -> result
    hedge_count: int = 0
    _next_rid: int = 0

    def submit(self, payload, *, priority: float = 1.0,
               deadline_ms: float = 100.0) -> int:
        rid = self._next_rid
        self._next_rid += 1
        heapq.heappush(self.waiting, Request(
            priority=-priority, rid=rid, payload=payload,
            enqueued_at=time.perf_counter(), deadline_ms=deadline_ms))
        return rid

    def _hedge_stragglers(self, now):
        for rid, req in list(self.running.items()):
            if (now - req.started_at) * 1e3 > self.hedge_after_ms \
                    and not req.hedged:
                req.hedged = True
                self.hedge_count += 1
                heapq.heappush(self.waiting, Request(
                    priority=-1e9, rid=rid, payload=req.payload,
                    enqueued_at=now, deadline_ms=req.deadline_ms))

    def step(self) -> dict:
        """One engine iteration: fill the batch, run, retire completions."""
        now = time.perf_counter()
        self._hedge_stragglers(now)
        with obs_span("query.batch", cat="query") as sp:
            batch = []
            while self.waiting and len(batch) < self.batch_size:
                req = heapq.heappop(self.waiting)
                if req.rid in self.done:  # hedged duplicate already served
                    continue
                req.started_at = now
                self.running[req.rid] = req
                batch.append(req)
            if not batch:
                return {}
            if sp.on:
                sp.set(rids=[r.rid for r in batch],
                       wait_ms=[(now - r.enqueued_at) * 1e3 for r in batch])
            results = self.step_fn([r.payload for r in batch])
        out = {}
        for req, res in zip(batch, results):
            if req.rid not in self.done:  # first completion wins
                self.done[req.rid] = res
                out[req.rid] = res
            self.running.pop(req.rid, None)
        return out

    def drain(self, max_steps: int = 10_000) -> dict:
        for _ in range(max_steps):
            if not self.waiting and not self.running:
                break
            self.step()
        return self.done


def _result_row(oids, scores, slots, i: int, legacy: bool):
    """Row ``i`` of a fetched [Q, k] result as the step fn returns it: the
    top hit ``(oid, score)`` for a legacy embedding payload, else the
    request's ``QueryResult`` of numpy rows."""
    if legacy:
        return (int(oids[i, 0]), float(scores[i, 0]))
    from repro.core.query import QueryResult
    return QueryResult(oids=oids[i], scores=scores[i], slots=slots[i])


class _BatchFetch:
    """One dispatched group's [Q, k] ``QueryResult``, shared by its rows.

    The host copy of ``oids``, ``scores`` and ``slots`` starts when the
    group is dispatched, so it lands while the rest of the tick runs;
    ``host`` waits for it once, on the first row resolved, and drops the
    device arrays."""

    __slots__ = ("_dev", "_host", "_rows")

    def __init__(self, res, rows: int):
        self._dev = (res.oids, res.scores, res.slots)
        for x in self._dev:
            x.copy_to_host_async()
        self._host = None
        self._rows = rows              # real rows served (no padding)

    def host(self) -> tuple:
        if self._host is None:
            with obs_span("host.fetch", cat="query", what="result") as sp:
                if sp.on:
                    sp.set(rows=self._rows,
                           ready=all(x.is_ready() for x in self._dev))
                self._host = jax.device_get(self._dev)
            self._dev = None
        return self._host


class PendingResult:
    """A query result whose dispatch has been issued but not materialized.

    ``make_query_step_fn(block=False)`` stores one of these per request in
    ``BatchScheduler.done``.  The rows of one dispatched group share one
    host copy of the group's [Q, k] result, started at dispatch; the
    serving loop resolves rows after its tick instead of forcing a host
    sync inside the scheduler step (which would serialize query dispatch
    with ingest/sync compute).  The first row resolved waits for that copy
    once, every other row slices it on the host.  ``resolve`` is
    idempotent and returns exactly what the blocking path would have."""

    __slots__ = ("_batch", "_i", "_legacy", "_out")

    def __init__(self, batch: _BatchFetch, i: int, legacy: bool):
        self._batch, self._i, self._legacy = batch, i, legacy
        self._out = None

    def resolve(self):
        if self._out is None:
            self._out = _result_row(*self._batch.host(), self._i,
                                    self._legacy)
            self._batch = None
        return self._out


def resolve_results(done: dict) -> dict:
    """Materialize every PendingResult in a scheduler's ``done`` dict (in
    place) — the drain step of the overlapped serving loop."""
    for rid, r in done.items():
        if isinstance(r, PendingResult):
            done[rid] = r.resolve()
    return done


def make_query_step_fn(get_map, *, k: int = 5, use_pallas: bool = False,
                       pad_to: int | None = None, block: bool = True,
                       get_index=None):
    """Build a BatchScheduler ``step_fn`` over the declarative query engine.

    Payloads are ``core.query.Query`` specs — semantic, spatial, and
    attribute predicates all ride the same dispatch.  Raw embedding arrays
    [E] are accepted as legacy payloads and normalized to
    ``Query(embed=..., k=k)``.

    Each engine step groups same-plan specs, stacks each group into ONE
    batched spec (struct-of-arrays leading Q dim) with one compiled
    dispatch (``stack_queries``), and runs a SINGLE fused
    predicate+score+top-k sweep per group over the map (the bias-kernel
    Pallas sweep when use_pallas — the embedding table streams through once
    for the whole batch, instead of Q full sweeps).  A uniform scheduler
    batch (the common case: every client sends the same plan shape) is
    two dispatches on a flat target, the stack and the sweep; the enclosing
    span (the scheduler's ``query.batch``) gets ``groups``, the number of
    groups the step dispatched.

    ``get_map`` returns the current query target (ObjectStore, LocalMap, or
    ZoneShardedStore), re-read every step so a live mapping server can keep
    mutating it between steps.  ``pad_to`` pads a ragged group to a fixed Q
    (defaults to the scheduler batch size at the call site) so the jitted
    step sees one shape, not one per tail size.

    Returns, in payload order: ``(oid, score)`` of the top hit for legacy
    embedding payloads, or the request's full ``QueryResult`` row (numpy)
    for Query payloads.

    ``block=False`` returns ``PendingResult`` handles instead: the fused
    dispatch is issued and each group's one host copy is started, but
    nothing waits for it inside the step — the overlapped serving loop
    ``resolve``s a tick later, with one blocking read per group.

    ``get_index`` (optional) returns the current cluster index over the
    map, re-read every step like ``get_map`` — the serving loop keeps its
    index maintained against the PUBLISH buffer, so a two-stage plan is
    exact against the same snapshot the flat sweep would scan.
    """
    import jax.numpy as jnp

    from repro.core.query import Query, execute_query, stack_queries

    def step_fn(payloads: list) -> list:
        m = get_map()
        index = get_index() if get_index is not None else None
        legacy = [not isinstance(p, Query) for p in payloads]
        specs = [Query(embed=jnp.asarray(p), k=k) if leg else p
                 for p, leg in zip(payloads, legacy)]
        # group by plan structure: each group is one fused dispatch
        groups: dict = {}
        for pos, s in enumerate(specs):
            key = (jax.tree.structure(s), s.tree_flatten()[1])
            groups.setdefault(key, []).append(pos)
        sp = current_span()          # the scheduler's ``query.batch``
        if sp.on:
            sp.set(groups=len(groups))
        results: list = [None] * len(specs)
        for positions in groups.values():
            q = len(positions)
            width = max(pad_to or 0, q)
            batched = stack_queries([specs[p] for p in positions],
                                    pad_to=width)
            res = execute_query(m, batched, use_pallas=use_pallas,
                                index=index)
            if not block:
                batch = _BatchFetch(res, q)
                for i, pos in enumerate(positions):
                    results[pos] = PendingResult(batch, i, legacy[pos])
                continue
            host = jax.device_get((res.oids, res.scores, res.slots))
            for i, pos in enumerate(positions):
                results[pos] = _result_row(*host, i, legacy[pos])
        return results

    return step_fn
