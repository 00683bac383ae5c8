"""Unified observability: span tracing, metrics, BENCH trajectories.

Three pieces, one contract — *observation never perturbs the replay*:

* ``obs.trace``     — near-zero-overhead span tracer (context manager +
                      decorator, nested spans, optional JAX fencing) with
                      Chrome/Perfetto trace-event JSON export; each span
                      is also a ``jax.profiler`` annotation carrying its
                      args.
* ``obs.metrics``   — process-wide registry of counters / gauges /
                      fixed-bucket histograms with deterministic
                      percentile math and Prometheus-text / JSON export.
* ``obs.trajectory``— git-sha-stamped BENCH run history
                      (``BENCH_history/<suite>.jsonl``) feeding the
                      cross-PR regression gate
                      (``benchmarks/regression_gate.py``).

Wall-clock only ever flows INTO spans/metrics, never into the
deterministic ``MetricsLog`` replay contract (asserted by
``tests/test_obs.py::test_golden_replay_unperturbed_by_obs``).
"""
from repro.obs.metrics import (Histogram, MetricsRegistry, get_registry,
                               set_registry)
from repro.obs.trace import (Tracer, get_tracer, set_tracer, span, traced)

__all__ = ["Histogram", "MetricsRegistry", "get_registry", "set_registry",
           "Tracer", "get_tracer", "set_tracer", "span", "traced"]
