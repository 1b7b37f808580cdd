"""Sharded Dynamic Exploration Graph (DESIGN.md §4).

The DB of N vectors is partitioned **round-robin** into S sub-DEGs, one per
``"model"``-axis shard (global id g lives on shard ``g % S`` at local row
``g // S``).  Each sub-DEG is an independent even-regular DEG built and
refined incrementally — DEG's incrementality is what makes per-shard
growth/rebalancing cheap at this scale.  Queries are sharded along the DP
axes (throughput) and replicated along ``"model"``; one search step is:

    local in-shard beam search  ->  all_gather(k best per shard, "model")
                                ->  exact top-k merge

Collective volume per query: ``S * k * 8`` bytes — independent of N.  Pods
replicate the index, so losing a pod degrades throughput, not recall; losing
one model shard degrades recall by ~1/S while the other shards keep serving
(fault-tolerance posture; simulated in tests/test_distributed.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import set_mesh, shard_map

from repro.core import beam
from repro.core.build import DEGIndex, DEGParams
from repro.core.graph import INVALID
from .collectives import topk_merge_allgather

Array = jax.Array


# ---------------------------------------------------------------------------
# the pure, lowerable search step
# ---------------------------------------------------------------------------
def make_sharded_search(mesh: Mesh, *, k: int, eps: float = 0.1,
                        beam_width: Optional[int] = None,
                        metric: str = "l2", shard_axis: str = "model",
                        batch_axes="data", exclude_width: int = 0,
                        codec: str = "float32",
                        rerank_k: int = 0, expand_width: int = 1,
                        visited_size: Optional[int] = None,
                        hop_backend: str = "jnp") -> Callable:
    """Build the jit-able sharded search step.

    f(adjacency (S, Ns, d) i32, vectors (S, Ns, m) f32, n (S,) i32,
      seeds (S,) i32, queries (B, m) f32[, exclude (B, X) i32])
      -> (ids (B, k) global i32, dists (B, k) f32)

    With a compressed ``codec``, f additionally takes
    ``codes (S, Ns, m)`` / ``scales (S, m)`` after ``vectors`` and runs the
    two-stage protocol: each shard's beam traverses its *quantized* store,
    ``rerank_k`` (default ``4 * k``) candidates per shard merge through
    ``topk_merge_allgather``, and the merged list is re-scored exactly
    AFTER the merge — each shard scores the merged rows it owns against its
    float store and a ``pmin`` over the shard axis fills every lane.  The
    extra collective volume is one (B, rerank_k) f32 pmin; the final top-k
    ordering is exactly the float ordering of the surviving candidates.

    ``expand_width`` / ``visited_size`` / ``hop_backend`` configure the
    shard-local multi-expansion engine (``visited_size=None`` auto-sizes
    like ``range_search``); the collective protocol is unchanged — multi-
    expansion only reshapes the per-shard ``while_loop``.
    """
    from repro.quant.store import VectorStore

    n_shards = int(mesh.shape[shard_axis])
    quantized = codec != "float32"
    rr = max(rerank_k, k) if quantized else k
    if quantized and rerank_k <= 0:
        rr = 4 * k

    def local(adj, vecs, codes, scales, books, n, seed, queries, exclude):
        adj, vecs = adj[0], vecs[0]              # strip leading shard dim
        from repro.core.graph import DEGraph

        store = (VectorStore(data=codes[0], scale=scales[0], codec=codec,
                             codebooks=None if books is None else books[0])
                 if quantized else beam.as_store(vecs))
        g = DEGraph(adjacency=adj, weights=jnp.zeros_like(adj, jnp.float32),
                    n=n[0])
        B = queries.shape[0]
        shard = jax.lax.axis_index(shard_axis)
        if exclude is None:
            seeds = jnp.broadcast_to(seed[0], (B, 1)).astype(jnp.int32)
            excl_local = None
        else:
            # exploration: global seed/exclude ids -> local rows where owned
            own = (exclude % n_shards) == shard
            local_rows = jnp.where(own, exclude // n_shards, INVALID)
            seeds = jnp.concatenate(
                [local_rows[:, :1],
                 jnp.broadcast_to(seed[0], (B, 1)).astype(jnp.int32)], axis=1)
            excl_local = local_rows
        # shard-local beam engine program (same primitives as range_search,
        # embedded directly in the shard_map body)
        n_ex = excl_local.shape[1] if excl_local is not None else 0
        L = (beam_width if beam_width is not None
             else beam.default_beam_width(rr, g.degree, seeds.shape[1],
                                          n_ex))
        L = max(L, rr, seeds.shape[1], rr + n_ex)
        vs = visited_size
        if vs is None:
            vs = (beam.default_visited_size(L, g.degree)
                  if hop_backend == "pallas" else 0)
        state = beam.beam_search(
            g, store, queries, seeds, k=rr, eps=eps, beam_width=L,
            max_hops=beam.default_max_hops(L), metric=metric,
            exclude=excl_local, expand_width=expand_width,
            visited_size=vs, hop_backend=hop_backend)
        lids, ldists = beam.extract(state, rr, dedup=vs > 0)
        gids = jnp.where(lids == INVALID, INVALID, lids * n_shards + shard)
        dists, ids = topk_merge_allgather(ldists, gids, rr, shard_axis)
        if quantized:
            ids, dists = _exact_rerank_owned(
                vecs, queries, ids, k=k, metric=metric,
                n_shards=n_shards, shard=shard, shard_axis=shard_axis)
        return ids, dists

    bspec = P(batch_axes, None)
    shspec3 = P(shard_axis, None, None)
    shspec1 = P(shard_axis)

    in_specs = [shspec3, shspec3]
    if quantized:
        in_specs += [shspec3, P(shard_axis, None)]
        if codec == "pq":                 # (S, m_sub, 256, dsub) codebooks
            in_specs += [P(shard_axis, None, None, None)]
    in_specs += [shspec1, shspec1, bspec]
    if exclude_width > 0:
        in_specs += [P(batch_axes, None)]

    def body(*a):
        books = None
        if quantized and codec == "pq":
            adj, vecs, codes, scales, books, n, seed, queries = a[:8]
            rest = a[8:]
        elif quantized:
            adj, vecs, codes, scales, n, seed, queries = a[:7]
            rest = a[7:]
        else:
            adj, vecs, n, seed, queries = a[:5]
            codes = scales = None
            rest = a[5:]
        exclude = rest[0] if rest else None
        return local(adj, vecs, codes, scales, books, n, seed, queries,
                     exclude)

    def f(*args):
        return shard_map(
            body, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(bspec, bspec), check_vma=False,
        )(*args)

    return f


@functools.lru_cache(maxsize=64)
def _jitted_search(mesh: Mesh, **config) -> Callable:
    """One jitted sharded-search program per (mesh, configuration); jax
    then compiles it once per operand shape, so repeated ``search`` calls
    reuse the compiled program instead of retracing a fresh closure."""
    return jax.jit(make_sharded_search(mesh, **config))


def _exact_rerank_owned(vecs, queries, ids, *, k, metric, n_shards, shard,
                        shard_axis):
    """Exact rerank of merged global ids inside shard_map: each shard
    scores the rows it owns against its float store; pmin over the shard
    axis fills the unowned lanes; the exact top-k wins."""
    from repro.core.distances import get_metric

    own = (ids != INVALID) & ((ids % n_shards) == shard)
    rows = jnp.where(own, ids // n_shards, 0)
    ed = get_metric(metric).pair(queries[:, None, :],
                                 vecs[rows].astype(jnp.float32))
    ed = jnp.where(own, ed, jnp.inf)
    ed = jax.lax.pmin(ed, shard_axis)
    ed = jnp.where(ids == INVALID, jnp.inf, ed)
    order = jnp.argsort(ed, axis=1, stable=True)[:, :k]
    out_ids = jnp.take_along_axis(ids, order, axis=1)
    out_d = jnp.take_along_axis(ed, order, axis=1)
    out_ids = jnp.where(jnp.isinf(out_d), INVALID, out_ids)
    return out_ids, out_d


# ---------------------------------------------------------------------------
# host-side container
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ShardedDEG:
    """S independently built sub-DEGs + the stacked device arrays.

    ``quantize()`` attaches per-shard compressed stores (codes calibrated
    per shard from its live rows); ``search`` then runs the two-stage
    protocol of :func:`make_sharded_search` (quantized traversal, exact
    rerank after the all-gather merge)."""

    shards: list                     # list[DEGIndex]
    adjacency: Array                 # (S, Ns, d)
    vectors: Array                   # (S, Ns, m)
    n: Array                         # (S,)
    seeds: Array                     # (S,) per-shard medoid
    params: DEGParams
    codec: str = "float32"
    codes: Optional[Array] = None    # (S, Ns, m) — compressed rows
    scales: Optional[Array] = None   # (S, m) — per-shard sq8 scales
    codebooks: Optional[Array] = None  # (S, m_sub, 256, dsub) — pq books

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def n_total(self) -> int:
        return int(np.asarray(self.n).sum())

    def quantize(self, codec: str) -> "ShardedDEG":
        """Post-training: encode every shard's store under ``codec``
        (per-shard calibration over its live rows)."""
        from repro.quant import codec as qc

        if codec not in qc.CODECS:
            raise ValueError(f"unknown codec {codec!r} "
                             f"(have {sorted(qc.CODECS)})")
        if codec == "float32":
            return dataclasses.replace(self, codec=codec, codes=None,
                                       scales=None, codebooks=None)
        S, Ns, m = self.vectors.shape
        n_host = np.asarray(self.n)
        vecs = np.asarray(self.vectors)
        if codec == "pq":
            from repro.quant import pq as pqm

            m_sub, dsub = pqm.n_subspaces(m), pqm.subspace_dim(m)
            codes = np.zeros((S, Ns, m_sub), dtype=np.uint8)
            books = np.zeros((S, m_sub, pqm.PQ_K, dsub), dtype=np.float32)
            for s in range(S):
                books[s] = pqm.fit(vecs[s], int(n_host[s]), seed=s)
                codes[s] = np.asarray(pqm.encode(jnp.asarray(vecs[s]),
                                                 jnp.asarray(books[s])))
            return dataclasses.replace(
                self, codec=codec, codes=jnp.asarray(codes),
                scales=jnp.ones((S, m), jnp.float32),
                codebooks=jnp.asarray(books))
        codes = np.zeros((S, Ns, m),
                         dtype={"fp16": np.float16, "sq8": np.int8}[codec])
        scales = np.ones((S, m), dtype=np.float32)
        for s in range(S):
            sc = qc.calibrate_sq8_scale(jnp.asarray(vecs[s]), n_host[s]) \
                if codec == "sq8" else jnp.ones((m,), jnp.float32)
            scales[s] = np.asarray(sc)
            codes[s] = np.asarray(qc.encode(codec, jnp.asarray(vecs[s]), sc))
        return dataclasses.replace(self, codec=codec,
                                   codes=jnp.asarray(codes),
                                   scales=jnp.asarray(scales),
                                   codebooks=None)

    def memory_stats(self) -> dict:
        """Per-shard traversal-store bytes (live rows) under the attached
        codec vs the exact float32 store."""
        from repro.quant import codec as qc

        m = self.vectors.shape[2]
        per_shard = np.asarray(self.n)
        exact = sum(qc.store_bytes("float32", int(ns), m) for ns in per_shard)
        b = sum(qc.store_bytes(self.codec, int(ns), m) for ns in per_shard)
        return {"n": int(per_shard.sum()), "dim": m, "codec": self.codec,
                "exact_bytes": exact, "store_bytes": b,
                "ratio": exact / b if b else 0.0}

    def place(self, mesh: Mesh) -> "ShardedDEG":
        """Put every stacked shard array on ``mesh``, split along its
        leading shard dimension over the ``"model"`` axis (shard s on the
        devices of mesh column s), so no one device holds them all.
        Arrays already placed so are left as they are."""
        def put(x):
            if x is None:
                return None
            spec = P("model", *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))

        for name in ("adjacency", "vectors", "n", "seeds", "codes",
                     "scales", "codebooks"):
            setattr(self, name, put(getattr(self, name)))
        return self

    def search(self, mesh: Mesh, queries: np.ndarray, k: int,
               eps: float = 0.1, batch_axes="data",
               rerank_k: int = 0, expand_width: Optional[int] = None,
               visited_size: Optional[int] = None,
               hop_backend: Optional[str] = None,
               beam_width: Optional[int] = None) -> tuple:
        f = _jitted_search(
            mesh, k=k, eps=float(eps), beam_width=beam_width,
            metric=self.params.metric,
            batch_axes=batch_axes, codec=self.codec, rerank_k=rerank_k,
            expand_width=(self.params.expand_width if expand_width is None
                          else expand_width),
            visited_size=(self.params.visited_size if visited_size is None
                          else visited_size),
            hop_backend=(self.params.hop_backend if hop_backend is None
                         else hop_backend))
        self.place(mesh)
        args = [self.adjacency, self.vectors]
        if self.codec != "float32":
            args += [self.codes, self.scales]
            if self.codec == "pq":
                args += [self.codebooks]
        args += [self.n, self.seeds, jnp.asarray(queries)]
        with set_mesh(mesh):
            ids, dists = f(*args)
        return np.asarray(ids), np.asarray(dists)

    def refine(self, iterations: int, seed: Optional[int] = None) -> int:
        """Shard-local continuous refinement (Alg. 5): each sub-DEG runs
        ``iterations`` of the batched refine path independently (sub-DEGs
        share no edges, so shard-local surgery is exact, not approximate),
        then the stacked device adjacency is refreshed from the builders.
        Returns the total number of improved edges."""
        improved = 0
        for s, sh in enumerate(self.shards):
            improved += sh.refine(
                iterations, seed=None if seed is None else seed + s)
        if improved:
            S, ns, d = self.adjacency.shape
            adj = np.full((S, ns, d), INVALID, dtype=np.int32)
            for s, sh in enumerate(self.shards):
                adj[s, : sh.n] = sh.builder.adjacency[: sh.n]
            self.adjacency = jnp.asarray(adj)
        return improved

    # -- persistence (persist/sharded.py owns the format) ------------------
    def save(self, path) -> None:
        """Snapshot every sub-DEG (full persist sections) behind one
        manifest; ``ShardedDEG.load`` restores exactly, or onto a different
        shard count via reshard-on-restore."""
        from repro.persist import save_sharded

        save_sharded(self, path)

    @classmethod
    def load(cls, path, n_shards: Optional[int] = None,
             wave_size: int = 8) -> "ShardedDEG":
        from repro.persist import load_sharded

        return load_sharded(path, n_shards=n_shards, wave_size=wave_size)

    def drop_shard(self, idx: int) -> "ShardedDEG":
        """Simulate losing one model shard: its sub-DEG serves nothing.
        (n=0 disables every vertex: recall degrades by ~1/S, service
        continues — the preemption-tolerance posture of DESIGN.md §4.)"""
        n = np.asarray(self.n).copy()
        n[idx] = 0
        return dataclasses.replace(self, n=jnp.asarray(n))


def build_sharded_deg(vectors: np.ndarray, n_shards: int,
                      params: Optional[DEGParams] = None,
                      wave_size: int = 8,
                      refine_iterations: int = 0,
                      codec: str = "float32") -> ShardedDEG:
    """Round-robin partition + per-shard incremental DEG build.
    ``codec`` != "float32" attaches post-training quantized shard stores;
    :meth:`ShardedDEG.place` (which ``search`` calls) spreads the stacked
    arrays over a mesh."""
    params = params or DEGParams()
    vectors = np.asarray(vectors, dtype=np.float32)
    N, m = vectors.shape
    shards, id_rows = [], []
    for s in range(n_shards):
        rows = vectors[s::n_shards]
        idx = DEGIndex(m, params, capacity=rows.shape[0])
        idx.add(rows, wave_size=wave_size)
        if refine_iterations:
            idx.refine(refine_iterations)
        shards.append(idx)
    ns = max(sh.n for sh in shards)
    d = params.degree
    adj = np.full((n_shards, ns, d), INVALID, dtype=np.int32)
    vecs = np.zeros((n_shards, ns, m), dtype=np.float32)
    seeds = np.zeros((n_shards,), dtype=np.int32)
    n_arr = np.zeros((n_shards,), dtype=np.int32)
    for s, sh in enumerate(shards):
        adj[s, : sh.n] = sh.builder.adjacency[: sh.n]
        vecs[s, : sh.n] = sh.vectors[: sh.n]
        n_arr[s] = sh.n
        seeds[s] = sh.medoid()       # cached per-shard medoid entry
    sd = ShardedDEG(shards=shards, adjacency=jnp.asarray(adj),
                    vectors=jnp.asarray(vecs), n=jnp.asarray(n_arr),
                    seeds=jnp.asarray(seeds), params=params)
    return sd.quantize(codec) if codec != "float32" else sd
