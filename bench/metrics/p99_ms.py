"""p99_ms: the 99th percentile of completion minus scheduled send (ms),
over the requests due in the traced run's host span (the window up to the
moment the trace opens); a failed request misses the tail.  It shows the
stalls of the serving process, which hold about one request in a hundred
(PERF.md)."""
from bench import stats


def read(run):
    if run.due_at is None:
        return None
    ok = stats.in_span(run.due_at, run.host_span)
    if not ok.any():
        return None
    return stats.percentile(stats.latencies_ms(
        run.completed_at[ok], run.due_at[ok], run.failed[ok]), 99)
