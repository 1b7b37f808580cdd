"""filtered_beam_roofline: share (%) of the HBM roofline the filtered beam
program reaches; it is bounded by bandwidth.  The least time a call can
take is the bytes it must read: the range_search reader's, (beam evals x
dim + hops x degree) x 4 B, plus the tag rows of the hops' neighbours,
hops x degree x T x 4 B, per range_search call that carried a filter, over
the traced run's host span (the engine's counters; the beam's evals are
serving_evals_total less the rows the scan read), over the HBM peak; it is
divided by the device time of one range_search call in the trace's
stretch."""


def read(run):
    tr, x = run.trace, run.extra
    calls = x.get("serving_filter_beam_calls_total")
    if (tr is None or run.peaks is None or not calls
            or not tr["module_calls"].get("range_search")):
        return None
    evals = run.span_evals - x["serving_filter_scan_rows_total"]
    per_call = (evals * run.dim + run.span_hops * run.degree
                * (1 + x["tag_slots"])) * 4 / calls
    floor_s = per_call / run.peaks["hbm_bytes_per_s"]
    per_run = (tr["modules"]["range_search"]
               / tr["module_calls"]["range_search"])
    return 100.0 * floor_s / per_run
