"""95th percentile (nearest rank) of the queue wait of every query batched
in the profiled seconds or still queued at their end (ms).  A batched
request's wait is its ``query.batch`` span's start minus its
``enqueued_at`` (the span's ``wait_ms``); a request still queued counts
with its age when the last ``serving.query`` span of the seconds closes
(that span's ``waiting_ms``), a lower bound on its wait.  So a request the
scheduler never picks lengthens the tail instead of leaving it."""
from bench.span_args import args_of
from bench.stats import percentile


def read(run):
    served = args_of(run, "query.batch", "wait_ms", layer="serving.query")
    if served is None:
        return None
    queued = args_of(run, "serving.query", "waiting_ms",
                     layer="serving.query")[-1]
    return percentile([w for ws in served for w in ws] + queued, 95)
