#!/usr/bin/env python3
"""Program spans over one benchmark run: the host work behind each idle
gap of the device, and the per-layer readings of the span table.

    python bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

runs the cell exactly as ``bench/run.py`` does (same arguments, same
result line), with the program's spans on (``repro.obs.trace.enable``)
for the whole process, and then prints one more JSON line, ``{"spans":
...}``:

* ``readings``: from the span table and the engine's counters over the
  traced run's host span (``--trace 1``):

  - ``refine_device_calls_per_edge.build``: device spans under the root
    ``deg.refine`` per ``deg.refine.edge`` under it (round trips per
    Alg. 4 call);
  - ``refine_wait_pct.build``: 100 x the device spans' self time under
    ``deg.refine`` / the self time of all spans under it (the
    ``deg.refine`` time); the rest is host Python;
  - ``dispatch_ms`` / ``complete_ms`` (``.serve``, ``.explore``): mean
    ``deg.serve.dispatch`` / ``deg.serve.complete`` per flush;
  - ``lockstep_waste_pct`` (``.serve``, ``.explore``): 100 x (1 -
    serving_hops_total / (expand_width x serving_lane_trips_total)), the
    beam loop's lane-trips that expanded nothing (finished or padded
    lanes);

* ``idle_gaps``: the trace's ten longest idle stretches of the device,
  each named by the program span whose self time (on its own thread)
  covers most of it, else by the benchmark annotation that does, else
  ``host:unannotated``;
* ``device_programs``: device seconds and calls of each program in the
  trace's stretch; ``op_programs``: the program each of the costliest
  device ops ran in.

With ``--trace 0`` only the result line and the span table's totals are
printed: that run measures what spans cost when on.  ``--rehearse``
prints the names of the readings that were readable, not their values.

``bench/run.py`` itself never turns spans on.  This tool wraps its tracer
(:class:`SpanTracer`) and its engine counters (the lane-trip counter
added) without changing either.
"""
from __future__ import annotations

import bisect
import glob
import json
import sys
from collections import defaultdict
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import drivers, trace_reduce  # noqa: E402
from repro.obs import trace  # noqa: E402

PROGRAM = "deg."
BENCH = "bench."
LANE_TRIPS = "serving_lane_trips_total"


# ---------------------------------------------------------------------------
# the trace: host spans with their threads, and the idle gaps they explain
# ---------------------------------------------------------------------------
def load(path: str):
    """``(trace_reduce.Trace, spans)`` of an ``.xplane.pb``: the Trace as
    ``trace_reduce.load`` reads it, and every ``deg.*`` and ``bench.*``
    host event as ``(name, start_ns, end_ns, thread)``, its name without
    the ``#key=value#`` metadata a profiler annotation may carry."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith((PROGRAM, BENCH)):
                    s = float(e.start_ns)
                    spans.append((name, s, s + float(e.duration_ns),
                                  (plane.name, i)))
    return trace_reduce.load(path), spans


def self_time(spans) -> list:
    """``(name, [(start, end), ...])`` per span: its interval less those
    of its children on the same thread (spans on one thread nest)."""
    by_thread = defaultdict(list)
    for k, (_, s, e, th) in enumerate(spans):
        by_thread[th].append((s, -e, k))
    children = defaultdict(list)
    for evs in by_thread.values():
        stack: list = []
        for s, neg_e, k in sorted(evs):
            while stack and spans[stack[-1]][2] <= s:
                stack.pop()
            if stack:
                children[stack[-1]].append(k)
            stack.append(k)
    out = []
    for k, (name, s, e, _) in enumerate(spans):
        pieces, at = [], s
        for cs, ce in trace_reduce.union(
                (spans[c][1], spans[c][2]) for c in children[k]):
            if cs > at:
                pieces.append((at, cs))
            at = max(at, ce)
        if e > at:
            pieces.append((at, e))
        out.append((name, pieces))
    return out


def _overlap(pieces, lo: float, hi: float) -> float:
    return sum(min(e, hi) - max(s, lo) for s, e in pieces
               if e > lo and s < hi)


def idle_gaps(tr, spans, top: int = 10) -> list:
    """The ``top`` longest stretches of the traced window with no op on
    the device, longest first, as ``[name, seconds]``: named by the
    program span whose self time covers most of the stretch, else the
    benchmark annotation that covers most of it, else
    ``trace_reduce.UNANNOTATED``."""
    if not tr.device_ops:
        return []
    lo, hi = trace_reduce.window(tr)
    gaps = []
    for c in sorted(tr.device_ops):
        busy = trace_reduce.union(trace_reduce.clip(
            [(s, e) for _, s, e in tr.device_ops[c]], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    own = [(n, p) for n, p in self_time(spans) if n.startswith(PROGRAM)]
    annotations = [(n, [(s, e)]) for n, s, e, _ in spans
                   if n.startswith(BENCH) and n != trace_reduce.WINDOW]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        label = trace_reduce.UNANNOTATED
        for named in (own, annotations):
            cover = defaultdict(float)
            for name, pieces in named:
                cover[name] += _overlap(pieces, s, e)
            cover = {k: v for k, v in cover.items() if v > 0}
            if cover:
                label = max(cover, key=cover.get)
                break
        out.append([label, (e - s) / 1e9])
    return out


def device_programs(reduced: dict) -> dict:
    """``{program: [device seconds, calls]}`` from a reduced trace."""
    return {k: [v, reduced["module_calls"].get(k, 0)]
            for k, v in sorted(reduced["modules"].items(),
                               key=lambda kv: -kv[1])}


def op_programs(tr, ops) -> dict:
    """``{op: program}``: the program whose events hold most of each
    named op's device time in the traced window."""
    lo, hi = trace_reduce.window(tr)
    want = set(ops)
    secs = defaultdict(lambda: defaultdict(float))
    for c, evs in tr.device_ops.items():
        mods = sorted((s, e, trace_reduce._module_name(n))
                      for n, s, e in tr.device_modules.get(c, []))
        starts = [m[0] for m in mods]
        for name, s, e in evs:
            if name not in want or s < lo or e > hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= e else "(none)"
            secs[name][prog] += (e - s) / 1e9
    return {op: max(p, key=p.get) for op, p in secs.items()}


# ---------------------------------------------------------------------------
# the readings of a span-table difference (repro.obs.trace.diff)
# ---------------------------------------------------------------------------
def _under(table: dict, root: str) -> dict:
    return {name: row for (r, name), row in table.items() if r == root}


def refine_device_calls_per_edge(table: dict):
    rows = _under(table, "deg.refine")
    edges = rows.get("deg.refine.edge")
    if edges is None or not edges.count:
        return None
    return sum(r.count for r in rows.values() if r.device) / edges.count


def refine_wait_pct(table: dict):
    """Self times, so that a sync nested in a search counts once, and so
    that a ``refine()`` still open at the host span's end counts by its
    spans that closed: the denominator is the self time of every span
    under the root, which is the ``deg.refine`` time when all closed."""
    rows = _under(table, "deg.refine")
    spent = sum(r.self_s for r in rows.values())
    if "deg.refine.edge" not in rows or spent <= 0:
        return None
    wait = sum(r.self_s for r in rows.values() if r.device)
    return 100.0 * wait / spent


def mean_ms(table: dict, name: str):
    row = table.get((name, name))
    if row is None or not row.count:
        return None
    return 1e3 * row.total_s / row.count


def lockstep_waste_pct(hops: float, lane_trips: float, expand_width: int):
    if not lane_trips:
        return None
    return 100.0 * (1.0 - hops / (expand_width * lane_trips))


def readings(kind: str, table: dict, counters=None,
             expand_width: int = 1) -> dict:
    """The readings of one cell kind (``build``, ``serve``, ``explore``)
    from a span-table difference over the host span, and for serving the
    counter differences ``(hops, evals, flushes, queries, lane_trips)``;
    a reading with nothing to read is left out."""
    if kind == "build":
        out = {"refine_device_calls_per_edge":
               refine_device_calls_per_edge(table),
               "refine_wait_pct": refine_wait_pct(table)}
    else:
        out = {"dispatch_ms": mean_ms(table, "deg.serve.dispatch"),
               "complete_ms": mean_ms(table, "deg.serve.complete")}
        if counters is not None and len(counters) > 4:
            out["lockstep_waste_pct"] = lockstep_waste_pct(
                counters[0], counters[4], expand_width)
    return {f"{k}.{kind}": v for k, v in out.items() if v is not None}


# ---------------------------------------------------------------------------
# the run: bench/run.py with spans on
# ---------------------------------------------------------------------------
class SpanTracer(drivers.Tracer):
    """``drivers.Tracer`` that also keeps the span table at the host
    span's edges and reduces the trace's program spans; ``made`` holds
    the tracers of the run."""

    made: list = []

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.spans_before = self.spans_after = None
        self.extended = None
        SpanTracer.made.append(self)

    def start(self, t0):
        if self.on:
            self.spans_before = trace.snapshot()
        super().start(t0)

    def end_host_span(self):
        super().end_host_span()
        self.spans_after = trace.snapshot()

    def reduce(self):
        if self.on:
            self.join()
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if paths:
                tr, spans = load(paths[0])
                red = trace_reduce.reduce(tr)
                if red is not None:
                    self.extended = {
                        "idle_gaps": idle_gaps(tr, spans),
                        "device_programs": device_programs(red),
                        "op_programs": op_programs(
                            tr, [n for n, _ in red["top_ops"]])}
        return super().reduce()


def counters_with_lane_trips(engine_counters):
    """``drivers.engine_counters`` with the lane-trip counter appended."""
    def counters(eng):
        base = engine_counters(eng)
        trips = eng.metrics.counter(LANE_TRIPS)
        return lambda: (*base(), trips.value)
    return counters


def main(argv=None) -> dict:
    from bench import run, spec

    argv = list(sys.argv[1:] if argv is None else argv)
    args = {a: b for a, b in zip(argv, argv[1:])}
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args["--workload"])
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    kind = drivers.load_kind(mix["kind"]).CELL_KIND
    rehearse = "--rehearse" in argv

    SpanTracer.made.clear()
    trace.reset()
    trace.enable(True)
    try:
        with mock.patch.object(drivers, "Tracer", SpanTracer), \
                mock.patch.object(
                    drivers, "engine_counters",
                    counters_with_lane_trips(drivers.engine_counters)):
            run.run(argv)
    finally:
        trace.enable(False)
    out = {"workload": cell["name"], "kind": kind}
    t = SpanTracer.made[-1] if SpanTracer.made else None
    if t is not None and t.spans_before is not None:
        table = trace.diff(t.spans_after, t.spans_before)
        counters = None
        if t.before is not None:
            counters = [a - b for a, b in zip(t.after, t.before)]
        got = readings(kind, table, counters,
                       cfg["search"].get("expand_width", 1))
        out["readable" if rehearse else "readings"] = (
            sorted(got) if rehearse else got)
        if t.extended is not None and not rehearse:
            out.update(t.extended)
    totals = trace.snapshot()
    out["table"] = {f"{r}|{n}": [s.count, s.total_s, s.self_s, s.max_s,
                                 s.device]
                    for (r, n), s in sorted(totals.items())}
    if rehearse:
        out["table"] = sorted(out["table"])
    print(json.dumps({"spans": out}), flush=True)
    return out


if __name__ == "__main__":
    main()
