"""refine_share_pct: share (%) of the build's time spent in refine()
calls, from the benchmark's own spans around add() and refine(), over the
chunks that started in the traced run's host span."""
from bench import stats


def read(run):
    if run.chunks is None or not len(run.chunks):
        return None
    c = run.chunks[stats.in_span(run.chunks[:, 0], run.host_span)]
    total = c[:, 1].sum() + c[:, 2].sum()
    return 100.0 * c[:, 2].sum() / total if total > 0 else None
