"""closed_explore: ``outstanding`` requests always in flight, each an
indexed vertex ``v`` drawn from a pool of ``pool`` vertices and queried
with ``seed_vertex=v`` and ``exclude=[v]`` (paper Sec. 6.7)."""
from __future__ import annotations

import numpy as np

from bench import data, reference
from bench.drivers import Run, SearchDriver, now

CELL_KIND = "explore"


def vertex_pool(mix: dict, seed: int, n: int):
    """(the pool of vertices, the generator that draws from it)."""
    rng = np.random.default_rng([seed, 2])
    return rng.choice(n, size=min(mix["pool"], n), replace=False), rng


def explore_truth(base: np.ndarray, picks: np.ndarray, k: int,
                  metric: str):
    """The exact ``k`` nearest of each picked vertex, itself left out."""
    uniq, inv = np.unique(picks, return_inverse=True)
    _, near = reference.brute_force(base[uniq], base, k,
                                    reference.all_but(uniq), metric)
    return near[inv]


class Driver(SearchDriver):

    explore = True

    def setup(self) -> None:
        super().setup()
        self.verts, self.rng = vertex_pool(self.mix, self.seed,
                                           len(self.base))

    def window(self, seconds: float) -> Run:
        eng, base, verts = self.eng, self.base, self.verts
        tracer, led = self.tracer, self.ledger
        picks = []

        def send():
            v = int(verts[self.rng.integers(0, len(verts))])
            with tracer.annotate("bench.submit"):
                led.add(eng.submit(base[v], seed_vertex=v, exclude=[v]))
            picks.append(v)

        t0 = now()
        t_end = t0 + seconds
        tracer.start(t0)
        for _ in range(int(self.mix["outstanding"])):
            send()
        while led.pending:
            led.wait_oldest(t_end + 60.0)
            if now() < t_end:
                send()
        self.picks = np.asarray(picks, np.int64)
        return Run(seconds=seconds, window_start=t0, window_end=t_end)

    def judged(self):
        k = self.cfg["search"]["k"]
        return (self.base[self.picks],
                explore_truth(self.base, self.picks, k, self.cfg["metric"]),
                reference.all_but(self.picks))


def control_requests(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int, rows: int, bench_dir):
    base, _ = data.make_corpus(cfg, seed, bench_dir)
    verts, rng = vertex_pool(mix, seed, len(base))
    picks = verts[rng.integers(0, len(verts), size=requests)]
    return (base, base[picks],
            explore_truth(base, picks, cfg["search"]["k"], cfg["metric"]),
            reference.all_but(picks))
