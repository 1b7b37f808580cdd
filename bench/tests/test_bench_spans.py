"""Program spans read over a run (bench/spans.py): idle gaps named by the
span whose self time covers them, programs per device op, the readings
of a span table, and each cell rehearsed with spans on."""
import glob
import threading
import time

import jax
import pytest

from bench import drivers, spans, spec
from bench import trace_reduce as tr
from bench.tests.test_bench_trace_reduce import chip_slice
from repro.obs import trace


def test_the_chip_slice_reduces_as_before():
    """The reducer's numbers on the slice recorded on the chip are pinned:
    reading program spans moves none of them."""
    t = chip_slice()
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(0.009)
    assert r["busy_s"] == pytest.approx(0.007202798)
    assert r["idle_pct"] == pytest.approx(19.968911111111108)
    assert r["modules"] == pytest.approx({"range_search": 0.00706411,
                                          "_scatter_rows": 0.000140401,
                                          "convert_element_type": 5.93e-07})
    assert r["module_calls"] == {"range_search": 1, "_scatter_rows": 1,
                                 "convert_element_type": 1}
    assert r["top_ops"][:2] == [["%while.5", pytest.approx(0.005361979)],
                                ["%copy.653", pytest.approx(0.001565983)]]
    # the same gaps, unnamed: the slice holds no host span
    assert spans.idle_gaps(t, []) == r["idle_gaps"]
    assert list(spans.device_programs(r)) == [
        "range_search", "_scatter_rows", "convert_element_type"]
    progs = spans.op_programs(t, ["%while.5", "%copy.653"])
    assert progs == {"%while.5": "range_search", "%copy.653": "range_search"}


def test_self_time_leaves_out_the_children_on_the_same_thread():
    evs = [("deg.refine", 0, 10, "a"), ("deg.refine.edge", 2, 8, "a"),
           ("deg.search_from", 5, 7, "a"), ("deg.serve.dispatch", 1, 9, "b")]
    assert spans.self_time(evs) == [
        ("deg.refine", [(0, 2), (8, 10)]),
        ("deg.refine.edge", [(2, 5), (7, 8)]),
        ("deg.search_from", [(5, 7)]),
        ("deg.serve.dispatch", [(1, 9)])]


def _record_two_threads(path):
    """A CPU profiler trace: thread B in one deg.serve.readback, then
    thread A in deg.refine > deg.refine.edge > deg.search_from, the spans
    annotated with metadata."""
    def other():
        with trace.span("deg.serve.readback", device=True, flush=4):
            time.sleep(0.004)

    trace.reset()
    trace.enable(True)
    jax.profiler.start_trace(str(path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            b = threading.Thread(target=other)
            b.start()
            b.join()
            with trace.span("deg.refine"):
                time.sleep(0.002)
                with trace.span("deg.refine.edge", chunk=1):
                    time.sleep(0.030)         # the edge's own host work
                    with trace.span("deg.search_from", device=True):
                        time.sleep(0.010)
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
        trace.enable(False)
        trace.reset()
    p, = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    return p


def test_a_gap_is_named_by_the_innermost_span_with_self_time(tmp_path):
    t, evs = spans.load(_record_two_threads(tmp_path))
    names = {n for n, *_ in evs}
    assert {"deg.refine", "deg.refine.edge", "deg.search_from",
            "deg.serve.readback", tr.WINDOW} <= names
    assert not any("#" in n for n in names)      # metadata stripped
    at = {n: (s, e, th) for n, s, e, th in evs}
    assert at["deg.serve.readback"][2] != at["deg.refine"][2]
    e_s = at["deg.refine.edge"][0]
    f_s, f_e, _ = at["deg.search_from"]
    r_s, r_e, _ = at["deg.serve.readback"]
    lo, hi, _ = at[tr.WINDOW]
    # the device is busy but for three gaps: one inside the edge's own
    # work (inside deg.refine too), one inside the search nested in the
    # edge, one inside the other thread's readback
    gaps = sorted([(e_s + 2e6, e_s + 27e6), (f_s + 1e6, f_e - 1e6),
                   (r_s + 0.5e6, r_e - 0.5e6)])
    edges = [lo] + [x for g in gaps for x in g] + [hi]
    t.device_ops = {0: [("busy", edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)]}
    named = spans.idle_gaps(t, evs)
    assert [n for n, _ in named[:3]] == [
        "deg.refine.edge", "deg.search_from", "deg.serve.readback"]
    assert named[0][1] == pytest.approx(0.025)


def test_without_program_spans_a_gap_takes_the_benchmark_annotation():
    ms = 1e6
    t = tr.Trace(device_ops={0: [("op", 0, 10 * ms), ("op", 20 * ms,
                                                        30 * ms)]},
                 host=[(tr.WINDOW, 0, 30 * ms)])
    evs = [(tr.WINDOW, 0, 30 * ms, "x"), ("bench.refine", 5 * ms, 25 * ms,
                                          "main")]
    assert spans.idle_gaps(t, evs) == [["bench.refine",
                                        pytest.approx(0.010)]]
    assert spans.idle_gaps(t, evs[:1]) == [[tr.UNANNOTATED,
                                            pytest.approx(0.010)]]


def _row(count, total, self_s, device=False):
    return trace.SpanStat(count, total, self_s, total, device)


def test_the_readings_of_a_span_table():
    table = {
        ("deg.refine", "deg.refine"): _row(2, 10.0, 1.0),
        ("deg.refine", "deg.refine.chunk"): _row(4, 9.0, 1.0),
        ("deg.refine", "deg.refine.search_batch"): _row(4, 1.0, 0.5, True),
        ("deg.refine", "deg.graph.sync"): _row(4, 0.5, 0.5, True),
        ("deg.refine", "deg.refine.edge"): _row(10, 7.0, 5.0),
        ("deg.refine", "deg.search_from"): _row(12, 2.0, 2.0, True),
        ("deg.add", "deg.refine.edge"): _row(99, 1.0, 1.0),
        ("deg.serve.dispatch", "deg.serve.dispatch"): _row(4, 0.002, 0.002),
        ("deg.serve.complete", "deg.serve.complete"): _row(4, 0.004, 0.004),
    }
    b = spans.readings("build", table)
    assert b["refine_device_calls_per_edge.build"] == pytest.approx(2.0)
    assert b["refine_wait_pct.build"] == pytest.approx(30.0)
    s = spans.readings("serve", table, [300, 0, 4, 10, 400])
    assert s == {"dispatch_ms.serve": pytest.approx(0.5),
                 "complete_ms.serve": pytest.approx(1.0),
                 "lockstep_waste_pct.serve": pytest.approx(25.0)}
    assert spans.readings("explore", {}, [0, 0, 0, 0, 0]) == {}
    assert spans.readings("build", {}) == {}


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_a_rehearsal_with_spans_reads_every_span_reading(workload, capsys):
    kind = drivers.load_kind(spec.mix(spec.cell(
        spec.load_benchmark(), workload)["traffic"])["kind"]).CELL_KIND
    out = spans.main(["--workload", workload, "--seed", str(2**31 + 7),
                      "--seconds", "6", "--trace", "1", "--rehearse"])
    assert not trace.enabled()
    want = {"build": ["refine_device_calls_per_edge", "refine_wait_pct"]}
    want = want.get(kind, ["complete_ms", "dispatch_ms",
                           "lockstep_waste_pct"])
    assert out["readable"] == sorted(f"{m}.{kind}" for m in want)
    assert "readings" not in out
    printed = capsys.readouterr().out.splitlines()
    assert printed[-1].startswith('{"spans": ')
