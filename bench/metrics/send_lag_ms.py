"""send_lag_ms: p99 of actual minus scheduled submit time (ms) over the
requests due in the traced run's host span — how late the load generator
ran."""
from bench import stats


def read(run):
    if run.sent_at is None:
        return None
    ok = stats.in_span(run.due_at, run.host_span)
    if not ok.any():
        return None
    return stats.percentile((run.sent_at[ok] - run.due_at[ok]) * 1e3, 99)
