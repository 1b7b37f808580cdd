"""What the drivers of every traffic kind are made of.

A traffic mix (``bench/traffic/<mix>.json``) names its ``kind``; the
driver of a kind is a file of its own, ``bench/kinds/<kind>.py``, which
:func:`load_kind` finds by that name, so a later change adds a kind as a
new file and edits none.  A kind's file defines:

* ``Driver(cfg, mix, seed, tracer_cfg, bench_dir)``, which runs one cell
  in phases:
  ``setup()`` (data from the seed, the index, and a warm-up of every
  program shape the mix will use, so that nothing compiles in the window);
  ``window(seconds) -> Run``, the measured stretch, driving the program's
  own entry points; ``settle(run) -> answers``, which waits for every
  answer due and reads what the metrics need; ``free()``, which drops the
  program's state; ``judge(run, answers) -> numbers`` against the plain
  references; and ``counts(run) -> (attempted, failed)``;
* ``control_requests(cfg, mix, seed, seconds, requests, rows,
  bench_dir)``: the requests a run of the kind judges, made from the seed
  as a run makes them, as ``(base, queries, truth_ids, admissible)``, the
  last the requests' admissible rows (``bench/reference.py``), or None
  where every row is (``bench/control.py``);
* ``CELL_KIND``: the suffix ``bench/spans.py`` gives its cells' span
  readings (``serve``, ``explore``, ``build``).

Here are the pieces kinds share: :class:`Run`, the record metric readers
read; :class:`Tracer`; :class:`Ledger`, each request's stamps and answer;
the index and engine as a configuration states them; and
:class:`SearchDriver`, the set-up, settling and judging of a cell that
searches through ``AsyncQueryEngine``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import sys
import time
from pathlib import Path

import numpy as np

from bench import data, judge, spec

now = time.perf_counter

BENCH_DIR = Path(__file__).resolve().parent


def load_kind(kind: str, bench_dir: Path = BENCH_DIR):
    """The module ``bench/kinds/<kind>.py``."""
    return spec.load_file(Path(bench_dir) / "kinds" / f"{kind}.py",
                          "bench_kind_")


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read.  A field a cell does not fill
    stays None, and readers that need it return None.  ``extra`` holds
    what a later kind adds for its own readers."""

    seconds: float = 0.0
    setup_s: float | None = None
    dim: int | None = None
    degree: int | None = None
    # serve / explore: one entry per request
    due_at: np.ndarray | None = None
    sent_at: np.ndarray | None = None
    submitted_at: np.ndarray | None = None
    dispatched_at: np.ndarray | None = None
    device_done_at: np.ndarray | None = None
    completed_at: np.ndarray | None = None
    failed: np.ndarray | None = None
    flush_index: np.ndarray | None = None
    window_end: float | None = None
    flushes: int | None = None
    queries: int | None = None
    hops: float | None = None
    evals: float | None = None
    recall: float | None = None
    # build
    rows_inserted: int | None = None
    build_end: float | None = None
    window_start: float | None = None
    chunks: np.ndarray | None = None    # (start, add s, refine s) rows
    built_recall: float | None = None
    # device trace (bench/trace_reduce.reduce), and the program's counters
    # over the host span of a traced run (see Tracer)
    trace: dict | None = None
    span_hops: float | None = None
    span_evals: float | None = None
    span_flushes: int | None = None
    span_queries: int | None = None
    host_span: tuple | None = None      # see Tracer, host clock
    peaks: dict | None = None           # bench/peaks.py, on a chip only
    extra: dict = dataclasses.field(default_factory=dict)


class Tracer:
    """A profiler trace of a short stretch of the window.

    Starting and stopping the profiler stall the whole process (stopping
    writes the trace, about half a second per MB on the chip), so the
    trace is short and comes late: the host-side per-layer metrics of a
    traced run read the window from its start up to the moment the trace
    opens (``host_span``), and the device metrics read the trace's
    ``bench.window`` stretch, which begins ``SETTLE_S`` after the profiler
    started.  The tracer runs on a thread of its own.  Off, it costs
    nothing and every annotation is a no-op."""

    SETTLE_S = 0.02

    def __init__(self, on: bool, start_after: float, seconds: float,
                 counters=None):
        self.on = on
        self.start_after, self.seconds = start_after, seconds
        self.counters = counters   # () -> (hops, evals, flushes, queries)
        self.dir = None
        self.before = self.after = None    # counters over host_span
        self.host_span = None              # (start, end) on the host clock
        self._thread = None

    def annotate(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.enable_hlo_proto = False
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1        # the benchmark's annotations
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def close(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def _body(self, t0: float) -> None:
        time.sleep(max(0.0, t0 + self.start_after - now()))
        self.end_host_span()
        self.open()
        try:
            time.sleep(self.SETTLE_S)
            with self.annotate("bench.window"):
                time.sleep(self.seconds)
            time.sleep(self.SETTLE_S)
        finally:
            self.close()

    def end_host_span(self) -> None:
        if self.counters:
            self.after = self.counters()
        self.host_span = (self.t0, now())

    def start(self, t0: float) -> None:
        """Arm the tracer for a window that opened at ``t0``."""
        if not self.on:
            return
        import tempfile
        import threading

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.t0 = t0
        if self.counters:
            self.before = self.counters()
        self._thread = threading.Thread(target=self._body, args=(t0,),
                                        name="bench-tracer")
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    def reduce(self) -> dict | None:
        """The reduced trace; the raw files are removed."""
        if not self.on:
            return None
        import glob
        import shutil

        from bench import trace_reduce

        self.join()
        try:
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not paths:
                return None
            t = now()
            out = trace_reduce.reduce(trace_reduce.load(paths[0]))
            print(f"trace: {os.path.getsize(paths[0])} bytes, reduced in "
                  f"{now() - t:.1f} s", file=sys.stderr, flush=True)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Ledger:
    """Each request's stamps and answer, copied out of its future once it
    completes; the future is then dropped.  The benchmark so keeps no
    per-request object alive through the window: a heap of retained
    futures would make the cyclic collector's passes, and with them part
    of the tail, the benchmark's own.

    ``add`` files a future in send order; ``harvest`` copies out the
    oldest futures that are done, ``wait_oldest`` waits for the oldest
    one, and ``drain`` for all that are left."""

    STAMPS = ("submitted_at", "dispatched_at", "device_done_at",
              "completed_at", "flush_index")

    def __init__(self, k: int, capacity: int = 1 << 14):
        self.k = k
        self.n = 0
        self.pending = collections.deque()      # (slot, future)
        self._alloc(capacity)

    def _alloc(self, cap: int) -> None:
        n = self.n
        stamps = {a: np.full(cap, np.nan) for a in self.STAMPS}
        ids = np.full((cap, self.k), -1, np.int64)
        dists = np.full((cap, self.k), np.inf, np.float64)
        failed = np.ones(cap, bool)
        if n:
            for a in self.STAMPS:
                stamps[a][:n] = self.stamps[a][:n]
            ids[:n], dists[:n] = self.ids[:n], self.dists[:n]
            failed[:n] = self.failed[:n]
        self.stamps, self.ids, self.dists = stamps, ids, dists
        self.failed = failed

    def add(self, fut) -> int:
        if self.n == len(self.failed):
            self._alloc(2 * self.n)
        slot = self.n
        self.n += 1
        self.pending.append((slot, fut))
        return slot

    def _record(self, slot: int, f) -> None:
        for a in self.STAMPS:
            v = getattr(f, a)
            if v is not None:
                self.stamps[a][slot] = v
        ok = f.done() and not (f.failed or f.cancelled or f.partial)
        self.failed[slot] = not ok
        if ok:
            self.ids[slot] = f.ids[: self.k]
            self.dists[slot] = f.dists[: self.k]

    def harvest(self) -> None:
        p = self.pending
        while p and p[0][1].done():
            self._record(*p.popleft())

    def wait_oldest(self, deadline: float) -> None:
        slot, f = self.pending.popleft()
        try:
            f.result(timeout=max(0.0, deadline - now()))
        except Exception:       # noqa: BLE001 — a failure is judged, later
            pass
        self._record(slot, f)

    def drain(self, deadline: float) -> None:
        while self.pending:
            self.wait_oldest(deadline)

    def fill(self, run: Run) -> None:
        n = self.n
        run.submitted_at = self.stamps["submitted_at"][:n]
        run.dispatched_at = self.stamps["dispatched_at"][:n]
        run.device_done_at = self.stamps["device_done_at"][:n]
        run.completed_at = self.stamps["completed_at"][:n]
        run.flush_index = self.stamps["flush_index"][:n]
        run.failed = self.failed[:n]

    def answers(self) -> dict:
        n = self.n
        return {"ids": self.ids[:n], "dists": self.dists[:n],
                "ok": ~self.failed[:n]}


def deg_params(cfg: dict):
    from repro.core.build import DEGParams

    d = cfg["deg"]
    s = cfg["search"]
    return DEGParams(degree=d["degree"], k_ext=d["k_ext"],
                     eps_ext=d["eps_ext"], k_opt=d["k_opt"],
                     eps_opt=d["eps_opt"], i_opt=d["i_opt"],
                     metric=cfg["metric"], expand_width=s["expand_width"],
                     hop_backend=s["hop_backend"])


def engine(index, cfg: dict, mix: dict):
    from repro.serving.async_engine import AsyncQueryEngine

    s, e = cfg["search"], mix["engine"]
    return AsyncQueryEngine(
        index, k=s["k"], eps=s["eps"], beam_width=s["beam_width"],
        expand_width=s["expand_width"], hop_backend=s["hop_backend"],
        max_batch=e["max_batch"], bucket_floor=e["bucket_floor"],
        deadline_ms=e["deadline_ms"], linger_ms=e["linger_ms"],
        pipeline_depth=e["pipeline_depth"], max_queue=e["max_queue"])


def served_index(cfg: dict, base: np.ndarray):
    """The index a serving cell searches, built as the configuration says."""
    from repro.core.build import build_deg

    b = cfg["build"]
    return build_deg(base, deg_params(cfg), wave_size=b["wave_size"],
                     refine_iterations=b["refine_iterations"],
                     capacity=len(base))


def warm_buckets(eng, index, explore: bool) -> None:
    """Compile every bucket program the engine may dispatch, with the
    operands the mix sends: plain queries, or seeded queries with an
    exclude list."""
    import jax

    from repro.serving import buckets

    view = index.acquire_view()
    try:
        for b in eng.buckets:
            item = buckets.BatchItem(
                query=np.zeros(index.dim, np.float32),
                exclude=[0] if explore else (),
                seed_vertex=0 if explore else None)
            qs, seeds, excl = buckets.pad_batch([item] * b, b,
                                                view.medoid(), 8)
            res = buckets.dispatch(view, eng.cfg, qs, seeds, excl)
            jax.block_until_ready(res.ids)
    finally:
        index.release_view(view)


def engine_counters(eng):
    """() -> (hops, evals, flushes, queries) so far."""
    hops = eng.metrics.counter("serving_hops_total")
    evals = eng.metrics.counter("serving_evals_total")
    return lambda: (hops.value, evals.value, eng.stats.flushes,
                    eng.stats.queries)


class SearchDriver:
    """A cell that searches an index built in set-up through
    ``AsyncQueryEngine``.  A kind fills in ``window`` (which files each
    future in ``self.ledger``) and ``judged()``: the queries of the
    window's requests in send order, their exact neighbours, and their
    admissible rows (``bench/reference.py``), or None where every row
    is.  ``bench_dir`` is the tree whose ``generators/`` holds the
    configuration's generator."""

    #: the operands the bucket programs are warmed with: seeded queries
    #: with an exclude list, or plain ones
    explore = False

    def __init__(self, cfg: dict, mix: dict, seed: int, tracer_cfg,
                 bench_dir: Path):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.tracer_cfg, self.bench_dir = tracer_cfg, bench_dir

    def setup(self) -> None:
        cfg = self.cfg
        c = data.corpus(cfg, self.seed, self.bench_dir)
        self.base, self.pool, self.meta = c.base, c.pool, c.meta
        self.index = served_index(cfg, self.base)
        self.eng = engine(self.index, cfg, self.mix)
        warm_buckets(self.eng, self.index, self.explore)
        self.tracer = self.tracer_cfg(engine_counters(self.eng))
        self.ledger = Ledger(cfg["search"]["k"])

    def settle(self, run: Run) -> dict:
        self.tracer.join()
        self.ledger.drain(max(run.window_end, now()) + 60.0)
        self.ledger.fill(run)
        st = self.eng.stats
        run.flushes, run.queries = st.flushes, st.queries
        run.hops, run.evals = engine_counters(self.eng)()[:2]
        if self.tracer.before is not None:
            b, a = self.tracer.before, self.tracer.after
            run.span_hops, run.span_evals = a[0] - b[0], a[1] - b[1]
            run.span_flushes = a[2] - b[2]
            run.span_queries = a[3] - b[3]
            run.host_span = self.tracer.host_span
        run.dim, run.degree = self.cfg["dim"], self.cfg["deg"]["degree"]
        self.eng.close()
        return self.ledger.answers()

    def free(self) -> None:
        del self.eng, self.index

    def judge(self, run: Run, ans: dict) -> dict:
        q, truth, admissible = self.judged()
        nums = judge.answer_numbers(self.base, q, ans["ids"], ans["dists"],
                                    ans["ok"], truth, self.cfg["search"]["k"],
                                    admissible, self.cfg["metric"])
        run.recall = 1.0 - nums["recall_miss"]
        return nums

    def counts(self, run: Run) -> tuple[int, int]:
        return len(run.failed), int(run.failed.sum())
