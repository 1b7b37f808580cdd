"""range_search_roofline: share (%) of the HBM roofline the search program
reaches; it is bounded by bandwidth.  The least time a flush can take is
the bytes the search must read, (sum(evals) x dim + sum(hops) x degree)
x 4 B per flush over the traced run's host span (the engine's counters),
over the HBM peak; it is divided by the device time of one range_search
program call in the trace's stretch."""
import numpy as np

from bench import stats


def read(run):
    tr = run.trace
    if (tr is None or run.peaks is None or run.host_span is None
            or not tr["module_calls"].get("range_search")):
        return None
    done = stats.in_span(run.device_done_at, run.host_span)
    flushes = np.unique(run.flush_index[done]).size
    if not flushes:
        return None
    per_flush = (run.span_evals * run.dim
                 + run.span_hops * run.degree) * 4 / flushes
    floor_s = per_flush / run.peaks["hbm_bytes_per_s"]
    per_call = (tr["modules"]["range_search"]
                / tr["module_calls"]["range_search"])
    return 100.0 * floor_s / per_call
