"""rejected_evals_pct: share (%) of the distance evaluations over the
traced run's host span that scored a vertex its request's filter did not
admit: the engine's serving_filter_rejected_evals_total over its
serving_evals_total (the scanned rows included)."""


def read(run):
    rejected = run.extra.get("serving_filter_rejected_evals_total")
    if rejected is None or not run.span_evals:
        return None
    return 100.0 * rejected / run.span_evals
