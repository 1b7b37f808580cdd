"""inserts_per_s: rows inserted by the build-and-refine regime in the
window, over the time from the window's start to the end of its last
chunk."""
from bench import stats


def read(run):
    if not run.rows_inserted:
        return None
    return stats.rate(run.rows_inserted, run.build_end - run.window_start)
