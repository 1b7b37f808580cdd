"""Batched RangeSearch (paper Algorithm 1) — thin jitted driver over the
beam engine.

The actual search loop lives in :mod:`repro.core.beam` (see ARCHITECTURE.md,
"Multi-expansion beam layering"): a lock-step beam over ``B`` query lanes
inside one ``jax.lax.while_loop``, where each hop gathers the ``E * d``
neighbors of the ``expand_width`` closest unchecked beam entries, dedups
them (beam broadcast, or the O(probes) visited filter of
``core/visited.py``), scores them (jnp by default; ``backend="pallas"``
runs the ``gather_dist`` kernel, ``hop_backend="pallas"`` the
``kernels/fused_hop`` path), and folds them into the distance-sorted beam
with the ``beam_merge`` bitonic partial merge (bit-identical to, and
cheaper than, the seed's full ``(B, L+d)`` argsort per hop).

This module keeps the public query API: :func:`range_search` resolves the
beam-width/hop-budget defaults and jits the engine program;
:func:`search_graph` adds the shared-medoid-seed convenience.  All other
layers (build, optimize, delete, distributed, serving) drive the same
engine — either through :func:`range_search` or directly via
``beam.beam_search`` inside their own jitted programs.

Exploration queries (paper Sec. 6.7) are supported natively: seeds can be
graph vertices and an ``exclude`` list removes already-seen vertices from
the *result list* (and from the radius ``r``) while still allowing
navigation through them — exactly the browsing protocol the paper
describes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import beam
from .beam import neighbor_distances_jnp as _neighbor_distances_jnp  # noqa: F401  (re-export)
from .distances import get_metric
from .graph import DEGraph, INVALID

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SearchResult:
    ids: Array      # (B, k) int32, INVALID-padded
    dists: Array    # (B, k) float32, inf-padded
    hops: Array     # (B,) int32 — number of expanded vertices
    evals: Array    # (B,) int32 — number of distance evaluations (|C| analogue)
    # (B,) float32 visited-table occupancy in [0, 1], or None when the
    # search ran the beam-broadcast dedup (no visited set).  Saturation
    # near 1.0 means dropped inserts — duplicate expansions and wasted
    # evals — which the query log records per query (obs/querylog.py).
    # One cheap reduction over state already on device: free telemetry.
    visited_frac: Optional[Array] = None
    # () int32 — the beam loop's trip count (BeamState.trips): each trip
    # runs all B lanes, finished and padded ones included
    trips: Optional[Array] = None


def exact_rerank(exact_vectors: Array, queries: Array, cand_ids: Array,
                 *, k: int, metric: str = "l2") -> tuple[Array, Array]:
    """Stage two of the quantized search: exactly re-score INVALID-padded
    candidate ids against the float store and return the exact top-k.

    One gather of ``rerank_k`` rows per query — the only touch of the exact
    store on the whole query path (the beam itself traversed compressed
    rows).  Stable sort keeps ties deterministic.
    """
    metric_obj = get_metric(metric)
    safe = jnp.where(cand_ids == INVALID, 0, cand_ids)
    d = metric_obj.pair(queries[:, None, :],
                        exact_vectors[safe].astype(jnp.float32))
    d = jnp.where(cand_ids == INVALID, jnp.inf, d)
    order = jnp.argsort(d, axis=1, stable=True)[:, :k]
    out_ids = jnp.take_along_axis(cand_ids, order, axis=1)
    out_d = jnp.take_along_axis(d, order, axis=1)
    out_ids = jnp.where(jnp.isinf(out_d), INVALID, out_ids)
    return out_ids, out_d


@functools.partial(
    jax.jit,
    static_argnames=("k", "beam_width", "max_hops", "metric", "backend",
                     "merge_backend", "rerank_k", "expand_width",
                     "visited_size", "hop_backend"),
)
def range_search(
    graph: DEGraph,
    vectors: Array,
    queries: Array,
    seed_ids: Array,
    *,
    k: int,
    eps: float = 0.1,
    beam_width: Optional[int] = None,
    max_hops: int = 0,
    metric: str = "l2",
    exclude: Optional[Array] = None,
    backend: str = "jnp",
    merge_backend: str = "jnp",
    rerank_k: int = 0,
    exact_vectors: Optional[Array] = None,
    expand_width: int = 1,
    visited_size: Optional[int] = None,
    hop_backend: str = "jnp",
    hop_budget: Optional[Array] = None,
) -> SearchResult:
    """Approximate k-NN for a batch of queries.

    Args:
      graph: the DEG to search.
      vectors: (capacity, m) float — the indexed points (rows >= graph.n
        unused) — or a :class:`repro.quant.VectorStore` view of them (the
        beam then traverses compressed distances).
      queries: (B, m) float.
      seed_ids: (B, S) int32 seed vertices, INVALID-padded.
      k: result count.
      eps: range-search slack factor (Alg. 1).
      beam_width: beam length L (defaults to a heuristic >= k).
      max_hops: safety bound on loop iterations (0 -> auto).
      exclude: optional (B, X) int32 vertices excluded from results (still
        traversable) — the exploration protocol.
      backend: distance backend ("jnp" | "pallas" fused gather_dist /
        gather_dist_q per the store codec).
      merge_backend: per-hop beam merge ("jnp" bitonic | "pallas" kernel |
        "argsort" seed semantics) — all bit-identical.
      rerank_k: two-stage search — take this many beam candidates and
        re-score them exactly against ``exact_vectors`` (requires
        ``rerank_k >= k``).  0 disables the second stage: results carry the
        store's (possibly compressed) distances.
      exact_vectors: (capacity, m) float32 exact rows for the rerank stage.
      expand_width: E — beam entries expanded per lane per hop
        (multi-expansion; 1 = the seed engine, bit for bit).
      visited_size: per-lane visited hash-set slots (power of two).  None
        auto-sizes: ``beam.default_visited_size`` when the fused hop
        kernel is requested (which needs the filter), else 0 — the
        beam-broadcast dedup, which benchmarks/search_pareto.py measures
        faster than the hash ops for the jnp hop on CPU.  Pass an explicit
        size to force the filter (e.g. the "visited" sweep variant).
      hop_backend: "jnp" composed hop | "pallas" fused hop kernel
        (``kernels/fused_hop``: adjacency gather -> visited filter ->
        vector gather -> distance -> compaction in one kernel).
      hop_budget: optional (B,) int32 per-lane expansion caps — the
        serving layer's deadline early-extract: a budget-exhausted lane
        stops hopping and returns its best-so-far beam (a traced operand,
        so every budget value shares one compiled program; ``None`` keeps
        the unbudgeted golden program).
    """
    n_ex = exclude.shape[1] if exclude is not None else 0
    L = (beam_width if beam_width is not None
         else beam.default_beam_width(k, graph.degree, seed_ids.shape[1],
                                      n_ex))
    L = max(L, k, seed_ids.shape[1])
    if exclude is not None:
        L = max(L, k + n_ex)
    if rerank_k:
        if rerank_k < k:
            raise ValueError(f"rerank_k={rerank_k} must be >= k={k}")
        if exact_vectors is None:
            raise ValueError("rerank_k > 0 requires exact_vectors")
        L = max(L, rerank_k + n_ex)   # room for rerank_k non-excluded hits
    if max_hops <= 0:
        max_hops = beam.default_max_hops(L)
    if visited_size is None:
        visited_size = (beam.default_visited_size(L, graph.degree)
                        if hop_backend == "pallas" else 0)
    # dropped visited inserts can (rarely) duplicate a beam entry; the
    # dedup in extract is the result-level guarantee
    dedup = visited_size > 0

    state = beam.beam_search(
        graph, vectors, queries, seed_ids, k=k, eps=eps, beam_width=L,
        max_hops=max_hops, metric=metric, exclude=exclude, backend=backend,
        merge_backend=merge_backend, expand_width=expand_width,
        visited_size=visited_size, hop_backend=hop_backend,
        hop_budget=hop_budget)
    if rerank_k:
        cand_ids, _ = beam.extract(state, rerank_k, dedup=dedup)
        out_ids, out_d = exact_rerank(exact_vectors, queries, cand_ids,
                                      k=k, metric=metric)
        evals = state.evals + (cand_ids != INVALID).sum(axis=1,
                                                        dtype=jnp.int32)
    else:
        out_ids, out_d = beam.extract(state, k, dedup=dedup)
        evals = state.evals
    visited_frac = None
    if state.visited is not None:
        visited_frac = jnp.mean((state.visited != INVALID)
                                .astype(jnp.float32), axis=1)
    return SearchResult(ids=out_ids, dists=out_d, hops=state.hops,
                        evals=evals, visited_frac=visited_frac,
                        trips=state.trips)


def medoid_seed(vectors: Array, n: int) -> int:
    """Approximate median vertex (paper Sec. 5.4 uses it as the search seed).

    One device reduction per call — ``DEGIndex`` caches the result and
    invalidates it on vector mutation (add/remove), so hot query paths
    do not pay this repeatedly.
    """
    mean = jnp.mean(vectors[:n], axis=0, keepdims=True)
    d = jnp.linalg.norm(vectors[:n] - mean, axis=1)
    return int(jnp.argmin(d))


def search_graph(graph: DEGraph, vectors: Array, queries: Array, *,
                 k: int, eps: float = 0.1, seed: Optional[int] = None,
                 beam_width: Optional[int] = None, max_hops: int = 0,
                 metric: str = "l2", exclude: Optional[Array] = None,
                 backend: str = "jnp", merge_backend: str = "jnp",
                 rerank_k: int = 0, exact_vectors: Optional[Array] = None,
                 expand_width: int = 1, visited_size: Optional[int] = None,
                 hop_backend: str = "jnp") -> SearchResult:
    """Convenience wrapper: single shared seed (median vertex by default),
    otherwise the full :func:`range_search` signature passed through
    verbatim.

    ``vectors`` doubles as the seed-medoid source, so when a
    :class:`~repro.quant.VectorStore` is searched with ``rerank_k``, pass
    the float rows via ``exact_vectors`` and an explicit ``seed``."""
    if seed is None:
        seed = medoid_seed(vectors, int(graph.n))
    B = queries.shape[0]
    seeds = jnp.full((B, 1), seed, dtype=jnp.int32)
    return range_search(graph, vectors, queries, seeds, k=k, eps=eps,
                        beam_width=beam_width, max_hops=max_hops,
                        metric=metric, exclude=exclude, backend=backend,
                        merge_backend=merge_backend, rerank_k=rerank_k,
                        exact_vectors=exact_vectors,
                        expand_width=expand_width,
                        visited_size=visited_size, hop_backend=hop_backend)
