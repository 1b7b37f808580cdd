"""queue_wait_ms: mean of dispatched_at - submitted_at (ms) over the
requests dispatched in the traced run's host span (the window up to the
moment the trace opens), from the engine's per-request stamps."""
from bench import stats


def read(run):
    if run.dispatched_at is None:
        return None
    ok = stats.in_span(run.dispatched_at, run.host_span)
    if not ok.any():
        return None
    return float((run.dispatched_at[ok] - run.submitted_at[ok]).mean() * 1e3)
