"""closed_batch: a closed loop of ``outstanding`` plain requests, each a
query drawn uniformly from the configuration's pool and submitted with no
seed vertex, exclude list or filter: the beam program the other operands
leave as it is."""
from __future__ import annotations

import numpy as np

from bench import closed_loop, data, reference
from bench.drivers import Run, SearchDriver

CELL_KIND = "batch"


def draws(seed: int):
    """The generator that picks a run's queries from the pool."""
    return np.random.default_rng([seed, 4])


class Driver(SearchDriver):

    def window(self, seconds: float) -> Run:
        eng, pool, tracer, led = self.eng, self.pool, self.tracer, self.ledger
        rng = draws(self.seed)
        sent = []

        def send():
            i = int(rng.integers(0, len(pool)))
            with tracer.annotate("bench.submit"):
                led.add(eng.submit(pool[i]))
            sent.append(i)

        t0, t_end = closed_loop.run(send, self.mix["outstanding"], led,
                                    tracer, seconds)
        self.picks = np.asarray(sent, np.int64)
        return Run(seconds=seconds, window_start=t0, window_end=t_end)

    def judged(self):
        _, truth = reference.brute_force(self.pool, self.base,
                                         self.cfg["search"]["k"],
                                         metric=self.cfg["metric"])
        return self.pool[self.picks], truth[self.picks], None


def control_requests(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int, rows: int, bench_dir):
    base, pool = data.make_corpus(cfg, seed, bench_dir)
    p = draws(seed).integers(0, len(pool), size=requests)
    _, truth = reference.brute_force(pool, base, cfg["search"]["k"],
                                     metric=cfg["metric"])
    return base, pool[p], truth[p], None
