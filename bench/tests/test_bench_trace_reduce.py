"""The reduction from a profiler trace to device numbers."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data"


def synthetic() -> tr.Trace:
    ms = 1e6
    return tr.Trace(
        device_ops={0: [("fusion.1", 10 * ms, 20 * ms),
                        ("fusion.2", 15 * ms, 25 * ms),   # overlaps
                        ("copy", 40 * ms, 50 * ms),
                        ("early", 0, 5 * ms)]},           # before window
        device_modules={0: [("jit_range_search(3)", 10 * ms, 25 * ms),
                            ("jit_range_search(3)", 40 * ms, 50 * ms)]},
        host=[("bench.window", 8 * ms, 108 * ms),
              ("bench.refine", 60 * ms, 100 * ms),
              ("bench.add", 26 * ms, 30 * ms)])


def test_union_counts_overlaps_once():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_busy_idle_and_modules():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.025)      # 10..25 and 40..50
    assert r["idle_pct"] == pytest.approx(75.0)
    assert r["modules"]["range_search"] == pytest.approx(0.025)
    assert r["module_calls"]["range_search"] == 2
    assert r["top_ops"][0] == ["fusion.1", pytest.approx(0.010)]


def test_idle_gaps_are_named_by_the_host_annotation():
    gaps = tr.reduce(synthetic())["idle_gaps"]
    assert gaps[0] == ["bench.refine", pytest.approx(0.058)]   # 50..108
    assert gaps[1] == ["bench.add", pytest.approx(0.015)]      # 25..40
    assert gaps[2] == [tr.UNANNOTATED, pytest.approx(0.002)]   # 8..10


def test_a_trace_with_no_device_gives_none():
    assert tr.reduce(tr.Trace(host=[("bench.window", 0, 1)])) is None


def chip_slice() -> tr.Trace:
    """A slice of a trace recorded on a TPU v5e (see the file's "about")."""
    import gzip
    import json

    with gzip.open(DATA / "trace_build_slice.json.gz", "rt") as f:
        d = json.load(f)
    names = d["names"]

    def events(rows):
        return [(names[i], float(s), float(s + dur)) for i, s, dur in rows]

    return tr.Trace(device_ops={0: events(d["device_ops"])},
                    device_modules={0: events(d["device_modules"])},
                    host=[(tr.WINDOW, 0.0, float(d["window_ns"]))])


def test_a_chip_trace_reduces_to_consistent_numbers():
    t = chip_slice()
    r = tr.reduce(t)
    ops = t.device_ops[0]
    assert r["window_s"] == pytest.approx(0.009)
    assert 0 < r["busy_s"] <= sum(e - s for _, s, e in ops) / 1e9
    assert 0 < r["idle_pct"] < 100
    # one whole search program in the slice, its ops inside it
    assert r["module_calls"]["range_search"] == 1
    (_, ms, me), = [m for m in t.device_modules[0]
                    if "range_search" in m[0]]
    inside = tr.union([(s, e) for _, s, e in ops if s >= ms and e <= me])
    assert r["modules"]["range_search"] == pytest.approx((me - ms) / 1e9)
    assert sum(e - s for s, e in inside) <= me - ms
    # busy + the idle gaps listed never exceed the window
    assert r["busy_s"] + sum(g for _, g in r["idle_gaps"]) <= \
        r["window_s"] + 1e-9
    secs = [v for _, v in r["top_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) == 10


def test_load_reads_the_benchmark_annotations(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench.add"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    t = tr.load(path)
    names = [h[0] for h in t.host]
    assert tr.WINDOW in names and "bench.add" in names
    assert tr.reduce(t) is None          # the CPU has no device plane
