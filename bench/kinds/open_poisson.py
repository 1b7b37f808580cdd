"""open_poisson: single-query submits to ``AsyncQueryEngine`` at Poisson
instants of a fixed rate, ``rate_qps`` (open loop: a late server sees the
same schedule), each query drawn uniformly from the configuration's pool.
Every seed gets the same set of gaps, in another order."""
from __future__ import annotations

import time

import numpy as np

from bench import data, reference
from bench.drivers import Run, SearchDriver, now

CELL_KIND = "serve"


def schedule(mix: dict, seed: int, seconds: float, pool: int):
    """(due instants from the window's start, pool index of each query)."""
    rate = float(mix["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 1])
    # the same Poisson gaps for every seed (exponential quantiles), in a
    # seeded order: the work is fixed, only its order moves
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due, rng.integers(0, pool, size=n)


class Driver(SearchDriver):

    def window(self, seconds: float) -> Run:
        due, qi = schedule(self.mix, self.seed, seconds, len(self.pool))
        eng, pool, tracer, led = self.eng, self.pool, self.tracer, self.ledger
        sent = np.empty(len(due))
        t0 = now()
        tracer.start(t0)
        for i in range(len(due)):
            led.harvest()
            wait = t0 + due[i] - now()
            if wait > 0:
                time.sleep(wait)
            with tracer.annotate("bench.submit"):
                sent[i] = now()
                led.add(eng.submit(pool[qi[i]]))
        t_end = max(t0 + seconds, now())
        self.qi = qi
        return Run(seconds=seconds, due_at=t0 + due, sent_at=sent,
                   window_start=t0, window_end=t_end)

    def judged(self):
        _, truth = reference.brute_force(self.pool, self.base,
                                         self.cfg["search"]["k"],
                                         metric=self.cfg["metric"])
        return self.pool[self.qi], truth[self.qi], None


def control_requests(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int, rows: int, bench_dir):
    base, pool = data.make_corpus(cfg, seed, bench_dir)
    _, qi = schedule(mix, seed, seconds, len(pool))
    _, truth = reference.brute_force(pool, base, cfg["search"]["k"],
                                     metric=cfg["metric"])
    return base, pool[qi], truth[qi], None
