"""Program spans (obs/trace.py): off they are one flag check and touch
neither the profiler nor the span table; on they annotate the profiler's
timeline and fill the table with per-thread nesting, self time and
roots.  Also the beam loop's trip count and the engine's lane-trip
counter it feeds."""
import queue
import threading

import jax.profiler
import numpy as np
import pytest

from repro.core.build import DEGParams, build_deg
from repro.obs import clock, trace
from repro.obs.metrics import MetricsRegistry


@pytest.fixture
def spans_on():
    trace.reset()
    trace.enable(True)
    yield
    trace.enable(False)
    trace.reset()


class _Refused:
    def __init__(self, *a, **kw):
        raise AssertionError("the profiler was called with spans off")


@pytest.mark.parametrize("metric", [None, "m"])
def test_off_span_touches_no_profiler_and_no_table(monkeypatch, metric):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    reg = MetricsRegistry()
    trace.reset()
    assert not trace.enabled()
    with trace.span("deg.refine.edge", reg, device=True, metric=metric,
                    flush=3) as s:
        pass
    assert trace.snapshot() == {}
    if metric is None:
        # the shared null context: nothing made, nothing timed
        assert s is trace.span("deg.other")
        assert s.seconds == 0.0 and reg.snapshot() == \
            MetricsRegistry().snapshot()
    else:
        assert reg.histogram("m_ms").count == 1 and s.seconds > 0


class _Clock:
    """A clock the test moves by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Stepper(threading.Thread):
    """Runs a generator one step per command, on a thread of its own."""

    def __init__(self, gen):
        super().__init__(daemon=True)
        self.gen, self.cmd, self.done = gen, queue.Queue(), queue.Queue()
        self.start()

    def run(self):
        while self.cmd.get():
            try:
                next(self.gen)
            except StopIteration:
                pass
            self.done.put(True)

    def step(self):
        self.cmd.put(True)
        self.done.get(timeout=10)

    def stop(self):
        self.cmd.put(False)
        self.join(10)


def test_on_spans_nest_per_thread_with_self_time_roots_and_max(
        monkeypatch, spans_on):
    seen = []

    class _Recorder:
        def __init__(self, name, **meta):
            seen.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    now = _Clock()
    monkeypatch.setattr(clock, "now", now)
    span = trace.span

    def refine():                       # thread A
        with span("deg.refine"):                          # t 0 .. 10
            yield
            with span("deg.refine.edge"):                 # t 2 .. 8
                yield
                with span("deg.search_from", device=True):  # t 5 .. 7
                    yield
                yield
            with span("deg.refine.edge"):                 # t 8 .. 9
                yield
            yield

    def serve():                        # thread B
        with span("deg.serve.dispatch", flush=7, lanes=3):  # t 1 .. 4
            yield
        yield
        with span("deg.add"):                             # t 11 .. 14
            yield
            with span("deg.refine.edge"):                 # t 12 .. 13
                yield
            yield

    a, b = _Stepper(refine()), _Stepper(serve())
    for t, th in [(0, a), (1, b), (2, a), (4, b), (5, a), (7, a), (8, a),
                  (9, a), (10, a), (11, b), (12, b), (13, b), (14, b)]:
        now.t = t
        th.step()
    a.stop()
    b.stop()
    tab = trace.snapshot()
    row = {k: (v.count, v.total_s, v.self_s, v.max_s, v.device)
           for k, v in tab.items()}
    assert row == {
        ("deg.refine", "deg.refine"): (1, 10, 3, 10, False),
        ("deg.refine", "deg.refine.edge"): (2, 7, 5, 6, False),
        ("deg.refine", "deg.search_from"): (1, 2, 2, 2, True),
        ("deg.serve.dispatch", "deg.serve.dispatch"): (1, 3, 3, 3, False),
        ("deg.add", "deg.add"): (1, 3, 2, 3, False),
        ("deg.add", "deg.refine.edge"): (1, 1, 1, 1, False),
    }
    assert ("deg.serve.dispatch", {"flush": 7, "lanes": 3}) in seen
    assert sum(v.count for v in tab.values() if v.device) == 1


def test_snapshots_difference(spans_on):
    with trace.span("deg.tick"):
        pass
    before = trace.snapshot()
    for _ in range(3):
        with trace.span("deg.tick"):
            pass
    with trace.span("deg.add"):
        pass
    d = trace.diff(trace.snapshot(), before)
    assert d[("deg.tick", "deg.tick")].count == 3
    assert d[("deg.add", "deg.add")].count == 1
    assert before[("deg.tick", "deg.tick")].count == 1


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(3)
    return rng.normal(size=(300, 8)).astype(np.float32)


@pytest.mark.parametrize("on", [False, True])
def test_build_and_refine_histograms_keep_their_names(points, on):
    reg = MetricsRegistry()
    idx = build_deg(points[:40], DEGParams(degree=8, k_ext=16),
                    wave_size=8)
    idx.metrics = reg
    trace.reset()
    trace.enable(on)
    try:
        idx.add(points[40:], wave_size=16)
        idx.refine(40, seed=1)
    finally:
        trace.enable(False)
    waves = -(-260 // 16)
    assert reg.histogram("build_wave_search_ms").count == waves
    assert reg.histogram("build_wave_extend_ms").count == waves
    assert reg.histogram("refine_chunk_ms").count == 3
    assert reg.counter("build_vertices_total").value == 260
    assert idx.build_stats["search_s"] > 0 and idx.build_stats["extend_s"] > 0
    tab = trace.snapshot()
    trace.reset()
    if not on:
        assert tab == {}
        return
    assert tab[("deg.add", "deg.add.wave")].count == waves
    assert tab[("deg.add", "deg.add.search")].device
    assert tab[("deg.refine", "deg.refine.chunk")].count == 3
    for name in ("deg.refine.conform", "deg.refine.search_batch",
                 "deg.refine.propose"):
        assert tab[("deg.refine", name)].device
    edges = tab[("deg.refine", "deg.refine.edge")]
    assert edges.count > 0 and edges.self_s <= edges.total_s


def test_beam_trips_are_the_slowest_lane_at_expand_width_one(points):
    import jax.numpy as jnp

    from repro.core import beam

    idx = build_deg(points, DEGParams(degree=8, k_ext=16), wave_size=16)
    qs = jnp.asarray(points[:24] + 0.05)
    seeds = jnp.full((24, 1), idx.medoid(), jnp.int32)
    st = beam.beam_search(idx.frozen(), idx._dev_vectors, qs, seeds, k=8,
                          eps=0.1, beam_width=24, max_hops=500)
    hops = np.asarray(st.hops)
    assert int(st.trips) == hops.max() and hops.min() < hops.max()
    res = idx.search_batch(np.asarray(qs), np.asarray(seeds), k=8, eps=0.1,
                           beam_width=24)
    assert int(res.trips) == np.asarray(res.hops).max()


def test_engine_counts_lane_trips_per_bucket(points):
    from repro.serving.async_engine import AsyncQueryEngine

    idx = build_deg(points, DEGParams(degree=8, k_ext=16), wave_size=16)
    with AsyncQueryEngine(idx, k=5, max_batch=8, bucket_floor=8,
                          deadline_ms=None, linger_ms=50.0) as eng:
        eng.search(points[:5])
    hops = eng.metrics.counter("serving_hops_total").value
    trips = eng.metrics.counter("serving_lane_trips_total").value
    assert eng.stats.flushes >= 1 and trips % 8 == 0
    # a bucket of 8 lanes, 5 of them real: padded lanes and finished
    # lanes run every trip without expanding
    assert 0 < hops < trips
