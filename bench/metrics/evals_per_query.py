"""evals_per_query: distance evaluations per query over the traced run's
host span: the engine's serving_evals_total counter across the span, over
the requests whose flush came back from the device in it."""
from bench import stats


def read(run):
    if run.host_span is None or run.span_evals is None:
        return None
    done = int(stats.in_span(run.device_done_at, run.host_span).sum())
    return run.span_evals / done if done else None
