"""qps: queries completed inside the window, per second of the window."""
import numpy as np

from bench import stats


def read(run):
    if run.completed_at is None or run.window_end is None:
        return None
    done = (~run.failed) & (run.completed_at <= run.window_end)
    return stats.rate(int(np.sum(done)), run.seconds)
