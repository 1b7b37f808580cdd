"""lanes_per_flush: requests per engine flush (the engine's stats.queries
/ stats.flushes) over the traced run's host span."""


def read(run):
    if run.host_span is None or not run.span_flushes:
        return None
    return run.span_queries / run.span_flushes
