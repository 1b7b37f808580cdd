"""flush_ms: mean of device_done_at - dispatched_at (ms), one reading per
flush, over the flushes dispatched in the traced run's host span, from the
engine's per-request stamps."""
import numpy as np

from bench import stats


def read(run):
    if run.flush_index is None:
        return None
    ok = stats.in_span(run.dispatched_at, run.host_span) & np.isfinite(
        run.device_done_at)
    _, first = np.unique(run.flush_index[ok], return_index=True)
    if first.size == 0:
        return None
    t = (run.device_done_at[ok] - run.dispatched_at[ok])[first]
    return float(t.mean() * 1e3)
