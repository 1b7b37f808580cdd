"""Exact serial-scan baseline (paper uses FAISS serial scan, Sec. 6.3).

Ground truth for every recall computation, and the reference the ``l2_topk``
Pallas kernel is validated against.  With an ``admissible`` mask it is the
ground truth of a filtered search (``core/tags.py``): the k nearest of the
rows each query admits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..distances import exact_knn, exact_knn_batched, get_metric


def filtered_knn(queries, vectors, k: int, admissible, metric: str = "l2"):
    """Exact k-NN of each query among the rows its ``admissible`` (Q, n)
    bool mask admits: (dists, ids), each (Q, k), nearest first, ids -1
    past the last admitted row.  Plain ``jax.numpy`` in float32, the
    products at ``Precision.HIGHEST`` (``Metric.cross``)."""
    q = jnp.asarray(np.atleast_2d(queries), jnp.float32)
    d = get_metric(metric).cross(q, jnp.asarray(vectors, jnp.float32))
    neg, ids = jax.lax.top_k(-jnp.where(jnp.asarray(admissible), d, jnp.inf),
                             k)
    d = -np.asarray(neg)
    return d, np.where(np.isinf(d), -1, np.asarray(ids))


class BruteForceIndex:
    def __init__(self, vectors: np.ndarray, metric: str = "l2"):
        self.vectors = np.asarray(vectors, dtype=np.float32)
        self.metric = metric

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    def search(self, queries: np.ndarray, k: int, tile: int = 8192,
               backend: str = "jnp"):
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        if backend == "pallas" and self.metric == "l2":
            from repro.kernels.l2_topk import ops as l2_ops

            d, i = l2_ops.l2_topk(queries, self.vectors, k)
            return np.asarray(d), np.asarray(i)
        if self.n <= tile:
            d, i = exact_knn(queries, self.vectors, k, self.metric)
            return np.asarray(d), np.asarray(i)
        return exact_knn_batched(queries, self.vectors, k, self.metric, tile)
