"""build_refine: rows in seeded order through ``DEGIndex.add`` in chunks
of ``chunk_rows`` (waves of ``wave_size``), each chunk followed by
``DEGIndex.refine(refine_per_chunk)``, for the whole window.  The first
chunk is inserted in set-up, where it warms the build programs.

Around each ``refine`` call the driver keeps the adjacency rows the call
changed, before and after (a copy of the host graph's rows, outside the
timed spans); the judge weighs those edges against the rows inserted."""
from __future__ import annotations

import numpy as np

from bench import data, judge, reference
from bench.drivers import Run, deg_params, now

CELL_KIND = "build"


def insert_order(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, 3]).permutation(n)


class Driver:

    def __init__(self, cfg: dict, mix: dict, seed: int, tracer_cfg,
                 bench_dir):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.tracer_cfg, self.bench_dir = tracer_cfg, bench_dir

    def _chunk(self) -> None:
        m, i, idx = self.mix, self.inserted, self.index
        rows = self.base[self.order[i: i + m["chunk_rows"]]]
        with self.tracer.annotate("bench.add"):
            t0 = now()
            idx.add(rows, wave_size=m["wave_size"])
            t1 = now()
        before = idx.builder.adjacency[: idx.n].copy()
        with self.tracer.annotate("bench.refine"):
            t2 = now()
            idx.refine(m["refine_per_chunk"],
                       seed=int(self.rng.integers(0, 2**31 - 1)))
            t3 = now()
        after = idx.builder.adjacency[: idx.n]
        changed = np.flatnonzero((before != after).any(axis=1))
        self.refines.append((changed, before[changed], after[changed].copy()))
        self.chunks.append((t0, t1 - t0, t3 - t2))
        self.inserted += len(rows)

    def setup(self) -> None:
        from repro.core.build import DEGIndex

        cfg = self.cfg
        self.base, self.probe = data.make_corpus(cfg, self.seed,
                                                 self.bench_dir)
        self.rng = np.random.default_rng([self.seed, 4])
        self.order = insert_order(self.seed, len(self.base))
        self.index = DEGIndex(cfg["dim"], deg_params(cfg),
                              capacity=len(self.base))
        self.tracer = self.tracer_cfg(None)
        self.inserted, self.chunks, self.refines = 0, [], []
        self._chunk()                       # warms the build programs
        warm_build(self.index, self.mix)
        self.chunks, self.refines, self.first = [], [], self.inserted

    def window(self, seconds: float) -> Run:
        t0 = now()
        self.tracer.start(t0)
        end = t0
        while now() < t0 + seconds and (
                self.inserted + self.mix["chunk_rows"] <= len(self.base)):
            self._chunk()
            end = now()
        return Run(seconds=seconds, window_start=t0, build_end=end,
                   rows_inserted=self.inserted - self.first)

    def settle(self, run: Run) -> dict:
        self.tracer.join()
        run.host_span = self.tracer.host_span
        run.chunks = np.array(self.chunks, np.float64).reshape(-1, 3)
        idx, n = self.index, self.index.n
        s = self.cfg["search"]
        res = idx.search(self.probe, k=s["k"], eps=s["eps"],
                         beam_width=s["beam_width"])
        out = {"ids": np.asarray(res.ids), "dists": np.asarray(res.dists),
               "ok": np.ones(len(self.probe), bool),
               "stored": np.asarray(idx._dev_vectors[:n]),
               "adjacency": np.asarray(idx.frozen().adjacency)[:n]}
        run.dim, run.degree = self.cfg["dim"], self.cfg["deg"]["degree"]
        return out

    def free(self) -> None:
        del self.index

    def judge(self, run: Run, ans: dict) -> dict:
        k = self.cfg["search"]["k"]
        inserted = self.base[self.order[: self.inserted]]
        nums = judge.build_numbers(ans["stored"], inserted, ans["adjacency"],
                                   self.cfg["deg"]["degree"])
        nums["refine_idle"] = judge.refine_idle(inserted, self.refines)
        metric = self.cfg["metric"]
        _, truth = reference.brute_force(self.probe, inserted, k,
                                         metric=metric)
        # the probe searches report ids as rows of the index, which are
        # the inserted rows in order
        nums.update(judge.answer_numbers(inserted, self.probe, ans["ids"],
                                         ans["dists"], ans["ok"], truth, k,
                                         metric=metric))
        run.built_recall = 1.0 - nums["recall_miss"]
        return nums

    def counts(self, run: Run) -> tuple[int, int]:
        return run.rows_inserted, 0


def warm_build(index, mix: dict) -> None:
    """Compile the refine programs whose batch bucket a later chunk may
    reach and the first did not: the batched candidate search (8 to 512
    lanes), the swap proposal (4 to 512) and the single-query search of
    Alg. 4, and the dirty-row sync of every width up to the full-upload
    threshold."""
    import jax
    import jax.numpy as jnp

    from repro.core import extend, graph

    p = index.params
    g = index.frozen()
    top = graph.pow2_bucket(16 * (p.degree + 1))
    b = 4
    while b <= top:
        # operands made as refine_sweep makes them (numpy, then device)
        ids = np.full((b, p.k_opt), -1, np.int32)
        z = np.zeros((b,), np.int32)
        jax.block_until_ready(extend.propose_swaps(
            g.adjacency, g.weights, jnp.asarray(ids),
            jnp.asarray(np.full((b, p.k_opt), np.inf, np.float32)),
            jnp.asarray(z), jnp.asarray(z),
            jnp.asarray(np.zeros((b,), np.float32))))
        if b >= 8:
            index._search_from_batch(
                np.zeros((b, index.dim), np.float32),
                np.zeros((b, 1), np.int32), p.k_opt, p.eps_opt)
        b *= 2
    index._search_from(np.zeros(index.dim, np.float32), [0, 1], p.k_opt,
                       p.eps_opt)
    top = graph.pow2_bucket(-(-index.capacity // graph._FULL_SYNC_FRACTION))
    w = 1
    while w <= top:
        rows = jnp.zeros((w,), jnp.int32)
        adj, wt = graph._scatter_rows(
            jnp.zeros_like(g.adjacency), jnp.zeros_like(g.weights), rows,
            jnp.zeros((w, p.degree), jnp.int32),
            jnp.zeros((w, p.degree), jnp.float32))
        jax.block_until_ready(adj)
        w *= 2


def control_requests(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int, rows: int, bench_dir):
    base, pool = data.make_corpus(cfg, seed, bench_dir)
    base = base[insert_order(seed, len(base))[:rows]]
    _, truth = reference.brute_force(pool, base, cfg["search"]["k"],
                                     metric=cfg["metric"])
    return base, pool, truth, None
