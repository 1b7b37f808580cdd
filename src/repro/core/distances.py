"""Distance functions for DEG.

The paper (Sec. 2.1) defines DEG over a generic metric ``delta``. Everything in
``repro.core`` goes through this registry so the graph works for any of the
supported metrics.  Note that edge *weights* store the actual metric value
(not e.g. squared L2): the edge-optimization gains (Sec. 5.3) are *sums* of
distances, which are only meaningful in the true metric.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

_METRICS: dict[str, "Metric"] = {}


class Metric:
    """A distance with pointwise, one-to-many and many-to-many forms."""

    def __init__(self, name: str, pair: Callable, needs_norms: bool):
        self.name = name
        self._pair = pair
        self.needs_norms = needs_norms
        _METRICS[name] = self

    def pair(self, x: Array, y: Array) -> Array:
        """delta(x, y) for x: (..., m), y: (..., m) broadcast together."""
        return self._pair(x, y)

    def one_to_many(self, q: Array, xs: Array) -> Array:
        """delta(q, xs[i]): q (m,), xs (n, m) -> (n,)."""
        return self._pair(q[None, :], xs)

    def cross(self, qs: Array, xs: Array) -> Array:
        """Full distance matrix: qs (b, m), xs (n, m) -> (b, n).

        Written MXU-style (one big matmul + rank-1 corrections) because this is
        the compute hot spot of every ANNS system; the Pallas kernel
        ``repro.kernels.l2_topk`` implements the tiled fused version.  The
        matmuls run at ``Precision.HIGHEST``: this is the exact brute force
        every recall number is judged against, and a TPU's default f32
        matmul is a single bf16 pass.
        """
        if self.name == "l2":
            qn = jnp.sum(qs * qs, axis=-1, keepdims=True)  # (b, 1)
            xn = jnp.sum(xs * xs, axis=-1)                 # (n,)
            sq = qn - 2.0 * _dot_t(qs, xs) + xn[None, :]
            return jnp.sqrt(jnp.maximum(sq, 0.0))
        if self.name == "sqeuclidean":
            qn = jnp.sum(qs * qs, axis=-1, keepdims=True)
            xn = jnp.sum(xs * xs, axis=-1)
            return jnp.maximum(qn - 2.0 * _dot_t(qs, xs) + xn[None, :], 0.0)
        if self.name == "ip":
            return -_dot_t(qs, xs)
        if self.name == "cos":
            qs_n = qs / jnp.maximum(jnp.linalg.norm(qs, axis=-1, keepdims=True), 1e-12)
            xs_n = xs / jnp.maximum(jnp.linalg.norm(xs, axis=-1, keepdims=True), 1e-12)
            return 1.0 - _dot_t(qs_n, xs_n)
        raise NotImplementedError(self.name)


def _dot_t(a, b):
    """a @ b.T in full f32 precision on every backend."""
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def _l2(x, y):
    d = x - y
    return jnp.sqrt(jnp.maximum(jnp.sum(d * d, axis=-1), 0.0))


def _sql2(x, y):
    d = x - y
    return jnp.maximum(jnp.sum(d * d, axis=-1), 0.0)


def _ip(x, y):
    return -jnp.sum(x * y, axis=-1)


def _cos(x, y):
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    yn = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
    return 1.0 - jnp.sum(xn * yn, axis=-1)


L2 = Metric("l2", _l2, needs_norms=True)
SQEUCLIDEAN = Metric("sqeuclidean", _sql2, needs_norms=True)
IP = Metric("ip", _ip, needs_norms=False)
COS = Metric("cos", _cos, needs_norms=False)


def get_metric(name: str) -> Metric:
    try:
        return _METRICS[name]
    except KeyError:
        raise ValueError(f"unknown metric {name!r}; have {sorted(_METRICS)}") from None


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def exact_knn(queries: Array, base: Array, k: int, metric: str = "l2"):
    """Exact k-NN (ground truth / serial-scan baseline). Returns (dists, ids)."""
    m = get_metric(metric)
    dmat = m.cross(queries, base)
    neg_d, ids = jax.lax.top_k(-dmat, k)
    return -neg_d, ids


def exact_knn_batched(queries, base, k, metric="l2", tile: int = 8192):
    """Tiled exact k-NN for large bases: bounds the (b, n) matrix to (b, tile)."""
    import numpy as np

    n = base.shape[0]
    best_d = None
    best_i = None
    for lo in range(0, n, tile):
        hi = min(lo + tile, n)
        d, i = exact_knn(queries, base[lo:hi], min(k, hi - lo), metric)
        i = i + lo
        if best_d is None:
            best_d, best_i = np.asarray(d), np.asarray(i)
        else:
            cat_d = np.concatenate([best_d, np.asarray(d)], axis=1)
            cat_i = np.concatenate([best_i, np.asarray(i)], axis=1)
            order = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
            best_d = np.take_along_axis(cat_d, order, axis=1)
            best_i = np.take_along_axis(cat_i, order, axis=1)
    return best_d, best_i
