"""p95_ms: the 95th percentile, over every request due in the window, of
completion minus scheduled send (ms); a failed request misses the tail."""
from bench import stats


def read(run):
    if run.due_at is None:
        return None
    return stats.percentile(
        stats.latencies_ms(run.completed_at, run.due_at, run.failed), 95)
