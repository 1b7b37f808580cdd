"""scan_roofline: share (%) of the HBM roofline the filtered_scan program
reaches; it is bounded by bandwidth.  The least time a call can take is
the bytes it must read, each scanned row and its id, (dim x 4 + 4) B a
row, per filtered_scan call over the traced run's host span (the engine's
counters), over the HBM peak; it is divided by the device time of one
filtered_scan call in the trace's stretch."""


def read(run):
    tr, x = run.trace, run.extra
    calls = x.get("serving_filter_scan_calls_total")
    if (tr is None or run.peaks is None or not calls
            or not tr["module_calls"].get("filtered_scan")):
        return None
    per_call = (x["serving_filter_scan_rows_total"] * (run.dim * 4 + 4)
                / calls)
    floor_s = per_call / run.peaks["hbm_bytes_per_s"]
    per_run = (tr["modules"]["filtered_scan"]
               / tr["module_calls"]["filtered_scan"])
    return 100.0 * floor_s / per_run
