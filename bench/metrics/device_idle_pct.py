"""device_idle_pct: 100 x (1 - busy / stretch) over the trace's
bench.window stretch, busy being the union of the device's op intervals."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_pct"]
