"""filtered_closed: a closed loop of ``outstanding`` filtered requests,
each a query drawn uniformly from the configuration's pool and submitted
with its filter (``submit(query, filter=...)``): one tag or the AND of
two, at the selectivities of the pool's bands (the configuration's
generator).  The index is built with the rows' tags, and both of a
filtered request's routes, the beam with a filter operand and the scan of
the admitted rows, are compiled in set-up.

A request admits the rows carrying every tag of its filter; truth,
control and ``bad_rows`` keep to them (``bench/reference.py``), from a
bitset per filter of the pool made here from the generator's tags."""
from __future__ import annotations

import numpy as np

from bench import closed_loop, data, drivers, reference
from bench.drivers import Ledger, Run, SearchDriver

CELL_KIND = "filtered"

#: the engine's filter counters a traced run reads over its host span
#: (bench/metrics/rejected_evals_pct.py, filtered_beam_roofline.py,
#: scan_roofline.py), in ``Run.extra`` under these names
COUNTERS = ("serving_filter_rejected_evals_total",
            "serving_filter_scan_rows_total",
            "serving_filter_scan_calls_total",
            "serving_filter_beam_calls_total")


def filters(meta: dict) -> list[tuple]:
    """Each pool query's filter, as ``submit`` takes it."""
    return [tuple(int(t) for t in f if t >= 0) for f in meta["filters"]]


def admitted_bits(meta: dict, n: int) -> np.ndarray:
    """(pool, ceil(n / 8)) uint8: the base rows each pool filter admits,
    one bit a row (numpy's ``packbits`` order)."""
    indptr = np.asarray(meta["tag_indptr"], np.int64)
    tags = np.asarray(meta["tag_indices"], np.int64)
    rows = np.repeat(np.arange(n), np.diff(indptr))
    order = np.argsort(tags, kind="stable")
    tags, rows = tags[order], rows[order]
    out = np.zeros((len(meta["filters"]), (n + 7) // 8), np.uint8)
    for p, filt in enumerate(meta["filters"]):
        keep = np.ones(n, bool)
        for t in filt:
            if t >= 0:
                has = np.zeros(n, bool)
                has[rows[np.searchsorted(tags, t):
                         np.searchsorted(tags, t, side="right")]] = True
                keep &= has
        out[p] = np.packbits(keep)
    return out


def admissible(bits: np.ndarray, pool_of: np.ndarray):
    """The admissible rows of request ``r``, which sent pool query
    ``pool_of[r]`` (``reference.brute_force``'s predicate)."""
    pool_of = np.asarray(pool_of)
    return lambda r, x: ((bits[pool_of[r], x >> 3] >> (7 - (x & 7))) & 1
                         ).astype(bool)


def pool_truth(base, pool, bits, k: int, metric: str) -> np.ndarray:
    """The exact ``k`` nearest admitted rows of every pool query."""
    _, truth = reference.brute_force(pool, base, k,
                                     admissible(bits, np.arange(len(pool))),
                                     metric)
    return truth


def draws(seed: int):
    """The generator that picks a run's queries from the pool."""
    return np.random.default_rng([seed, 3])


def filter_counters(eng):
    """``drivers.engine_counters`` with the filter counters appended."""
    base = drivers.engine_counters(eng)
    cs = [eng.metrics.counter(name) for name in COUNTERS]
    return lambda: (*base(), *(c.value for c in cs))


class Driver(SearchDriver):

    def setup(self) -> None:
        from repro.core.build import build_deg

        cfg = self.cfg
        c = data.corpus(cfg, self.seed, self.bench_dir)
        self.base, self.pool, self.meta = c.base, c.pool, c.meta
        b = cfg["build"]
        self.index = build_deg(
            self.base, drivers.deg_params(cfg), wave_size=b["wave_size"],
            refine_iterations=b["refine_iterations"], capacity=len(self.base),
            tags=(self.meta["tag_indptr"], self.meta["tag_indices"]),
            tag_slots=cfg["tags"]["slots"])
        self.eng = drivers.engine(self.index, cfg, self.mix)
        self.eng.warm_filtered()
        self.filters = filters(self.meta)
        self.tracer = self.tracer_cfg(filter_counters(self.eng))
        self.ledger = Ledger(cfg["search"]["k"])

    def window(self, seconds: float) -> Run:
        eng, pool, filt = self.eng, self.pool, self.filters
        tracer, led = self.tracer, self.ledger
        rng = draws(self.seed)
        sent = []

        def send():
            i = int(rng.integers(0, len(pool)))
            with tracer.annotate("bench.submit"):
                led.add(eng.submit(pool[i], filter=filt[i]))
            sent.append(i)

        t0, t_end = closed_loop.run(send, self.mix["outstanding"], led,
                                    tracer, seconds)
        self.picks = np.asarray(sent, np.int64)
        return Run(seconds=seconds, window_start=t0, window_end=t_end)

    def settle(self, run: Run) -> dict:
        ans = super().settle(run)
        run.extra["tag_slots"] = self.cfg["tags"]["slots"]
        t = self.tracer
        if t.before is not None:
            n = len(COUNTERS)
            run.extra.update(zip(COUNTERS, (a - b for a, b in zip(
                t.after[-n:], t.before[-n:]))))
        return ans

    def judged(self):
        bits = admitted_bits(self.meta, len(self.base))
        truth = pool_truth(self.base, self.pool, bits,
                           self.cfg["search"]["k"], self.cfg["metric"])
        return (self.pool[self.picks], truth[self.picks],
                admissible(bits, self.picks))


def control_requests(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int, rows: int, bench_dir):
    c = data.corpus(cfg, seed, bench_dir)
    p = draws(seed).integers(0, len(c.pool), size=requests)
    bits = admitted_bits(c.meta, len(c.base))
    truth = pool_truth(c.base, c.pool, bits, cfg["search"]["k"],
                       cfg["metric"])
    return c.base, c.pool[p], truth[p], admissible(bits, p)
