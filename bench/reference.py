"""Plain references the benchmark judges the program against.

Nothing here imports the program.  Two references:

* :func:`brute_force` — exact k-NN over float32 rows, in blocks, with the
  matrix products at ``Precision.HIGHEST`` (a TPU's default float32 product
  is one bfloat16 pass).  :func:`brute_force_control` is the same search one
  precision step below, three bfloat16 passes (what ``Precision.HIGH`` does
  on a TPU), written out so that it computes the same on every backend; the
  correctness check must fail it.  Both take the configuration's
  ``metric`` (:data:`METRICS`) and, where a kind restricts its requests to
  some rows (a filter, the rows live at a step), their ``admissible`` rows.
* :func:`table1_violations` — the paper's Table-1 guarantees of a DEG in
  plain numpy: every vertex has exactly ``d`` distinct neighbours other than
  itself, every edge is undirected, and the graph is connected.
* :func:`weight_change` — how much a change of a graph's edges changed its
  total edge weight (the sum of its edges' l2 lengths, which the DEG's
  refinement lowers), in float64 from the rows themselves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 1024
ROW_BLOCK = 8192

#: the distances, as the program defines them: ``l2`` the euclidean
#: distance, ``ip`` minus the inner product, ``cos`` one minus the cosine
#: (norms floored at 1e-12)
METRICS = ("l2", "ip", "cos")


def _dot_highest(q, x):
    return jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)


def _dot_bf16x3(q, x):
    """q @ x.T from bfloat16 halves: hi*hi + hi*lo + lo*hi, float32 sums.

    The halves are rounded with ``reduce_precision``, which no compiler
    may elide, and multiplied exactly (a product of two bfloat16 values
    fits a float32)."""
    def split(a):
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(a - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (qh, ql), (xh, xl) = split(q), split(x)
    return _dot_highest(qh, xh) + _dot_highest(qh, xl) + _dot_highest(ql, xh)


_DOTS = {"highest": _dot_highest, "bf16x3": _dot_bf16x3}


def _unit(a):
    return a / jnp.maximum(jnp.linalg.norm(a, axis=1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("k", "precision", "metric"))
def _block_topk(q, x, x_valid, admit=None, *, k: int, precision: str,
                metric: str):
    """The ``k`` nearest rows of the block ``x`` to each query of ``q``;
    rows not ``x_valid``, or (with ``admit``, (Q, rows) bool) not admitted
    for a query, are left out."""
    dot = _DOTS[precision]
    if metric == "l2":      # squared until the top k
        d = jnp.maximum(jnp.sum(q * q, axis=1, keepdims=True)
                        - 2.0 * dot(q, x) + jnp.sum(x * x, axis=1)[None, :],
                        0.0)
    elif metric == "ip":
        d = -dot(q, x)
    else:
        d = 1.0 - dot(_unit(q), _unit(x))
    ok = x_valid[None, :] if admit is None else x_valid[None, :] & admit
    d = jnp.where(ok, d, jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return (jnp.sqrt(-neg) if metric == "l2" else -neg), ids


def _knn(queries: np.ndarray, base: np.ndarray, k: int, precision: str,
         metric: str, admissible) -> tuple[np.ndarray, np.ndarray]:
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; have {METRICS}")
    queries = np.asarray(queries, np.float32)
    base = np.asarray(base, np.float32)
    n, m = base.shape
    rb = min(ROW_BLOCK, n)
    qb = min(QUERY_BLOCK, len(queries))
    out_d = np.empty((len(queries), k), np.float32)
    out_i = np.empty((len(queries), k), np.int64)
    admitted = np.zeros(len(queries), np.int64)
    blocks = []
    for lo in range(0, n, rb):
        x = np.zeros((rb, m), np.float32)
        x[: min(rb, n - lo)] = base[lo: lo + rb]
        valid = np.arange(rb) < n - lo
        blocks.append((lo, jnp.asarray(x), jnp.asarray(valid)))
    kk = min(k, rb)
    for q0 in range(0, len(queries), qb):
        q = np.zeros((qb, m), np.float32)
        q[: min(qb, len(queries) - q0)] = queries[q0: q0 + qb]
        qd = jnp.asarray(q)
        rows = slice(q0, min(q0 + qb, len(queries)))
        nq = rows.stop - rows.start
        cand_d, cand_i = [], []
        for lo, x, valid in blocks:
            admit = None
            if admissible is not None:
                # one (query block, row block) of the restriction at a time
                nv = min(rb, n - lo)
                mask = np.zeros((qb, rb), bool)
                mask[:nq, :nv] = admissible(np.arange(q0, rows.stop)[:, None],
                                            np.arange(lo, lo + nv)[None, :])
                admitted[rows] += mask[:nq].sum(axis=1)
                admit = jnp.asarray(mask)
            d, i = _block_topk(qd, x, valid, admit, k=kk, precision=precision,
                               metric=metric)
            cand_d.append(np.asarray(d))
            cand_i.append(np.asarray(i) + lo)
        cd = np.concatenate(cand_d, axis=1)
        ci = np.concatenate(cand_i, axis=1)
        order = np.argsort(cd, axis=1, kind="stable")[:, :k]
        out_d[rows] = np.take_along_axis(cd, order, axis=1)[:nq]
        out_i[rows] = np.take_along_axis(ci, order, axis=1)[:nq]
    if admissible is not None and (admitted < k).any():
        short = np.flatnonzero(admitted < k)
        raise ValueError(f"{short.size} requests admit fewer than k={k} rows "
                         f"(request {short[0]}: {admitted[short[0]]})")
    return out_d, out_i


def brute_force(queries, base, k: int, admissible=None, metric: str = "l2"
                ) -> tuple[np.ndarray, np.ndarray]:
    """Exact k-NN: (distances, ids), each (Q, k), nearest first.

    ``admissible``, where the requests are restricted to some rows, is a
    predicate ``admissible(requests, rows) -> bool`` over request indices
    (rows of ``queries``) and row ids that broadcast together; it is asked
    one (query block, row block) at a time, so no (Q, n) array is made.
    Every request must admit at least ``k`` rows."""
    return _knn(queries, base, k, "highest", metric, admissible)


def brute_force_control(queries, base, k: int, admissible=None,
                        metric: str = "l2") -> tuple[np.ndarray, np.ndarray]:
    """The same search in three bfloat16 passes: the control."""
    return _knn(queries, base, k, "bf16x3", metric, admissible)


def all_but(vertex):
    """The admissible rows "every row but ``vertex[r]``" of each request ``r``
    (exploration: a query never returns its own vertex)."""
    vertex = np.asarray(vertex)
    return lambda r, x: x != vertex[r]


def exact_distances(queries: np.ndarray, base: np.ndarray,
                    ids: np.ndarray, metric: str = "l2") -> np.ndarray:
    """float64 distance of each query to each of its ids (Q, k), under
    ``metric``; an id outside the base reads NaN."""
    ids = np.asarray(ids)
    ok = (ids >= 0) & (ids < len(base))
    rows = np.asarray(base, np.float64)[np.where(ok, ids, 0)]
    q = np.asarray(queries, np.float64)[:, None, :]
    if metric == "l2":
        d = np.sqrt(np.sum((rows - q) ** 2, axis=2))
    elif metric == "ip":
        d = -np.sum(rows * q, axis=2)
    elif metric == "cos":
        norm = (np.maximum(np.linalg.norm(rows, axis=2), 1e-12)
                * np.maximum(np.linalg.norm(q, axis=2), 1e-12))
        d = 1.0 - np.sum(rows * q, axis=2) / norm
    else:
        raise ValueError(f"unknown metric {metric!r}; have {METRICS}")
    return np.where(ok, d, np.nan)


def table1_violations(adjacency: np.ndarray, degree: int) -> dict:
    """Counts of Table-1 breaches of an (n, d) adjacency; all 0 is sound.

    ``degree``: vertices whose row is not d ids in [0, n); ``self``: self
    loops; ``dup``: repeated neighbours; ``asym``: directed entries u->v
    with no v->u; ``unreached``: vertices not connected to vertex 0."""
    adj = np.asarray(adjacency, np.int64)
    n, d = adj.shape
    out = {"degree": 0, "self": 0, "dup": 0, "asym": 0, "unreached": 0}
    if n == 0:
        return out
    in_range = (adj >= 0) & (adj < n)
    out["degree"] = int(((in_range.sum(axis=1) != degree)
                         | (d != degree)).sum())
    u = np.broadcast_to(np.arange(n)[:, None], adj.shape)[in_range]
    v = adj[in_range]
    out["self"] = int((u == v).sum())
    srt = np.sort(np.where(in_range, adj, -1 - np.arange(d)), axis=1)
    out["dup"] = int((srt[:, 1:] == srt[:, :-1]).sum())
    fwd = np.sort(u * n + v)
    rev = v * n + u
    pos = np.clip(np.searchsorted(fwd, rev), 0, len(fwd) - 1)
    out["asym"] = int((fwd[pos] != rev).sum()) if len(fwd) else 0
    seen = np.zeros(n, bool)
    seen[0] = True
    frontier = np.array([0])
    while frontier.size:
        nb = adj[frontier][in_range[frontier]]
        nb = np.unique(nb[~seen[nb]])
        seen[nb] = True
        frontier = nb
    out["unreached"] = int((~seen).sum())
    return out


def weight_change(rows: np.ndarray, changed: np.ndarray, before: np.ndarray,
                  after: np.ndarray) -> float:
    """Total edge weight after a change of the graph minus before it.

    ``changed`` are the vertices whose adjacency rows differ, ``before``
    and ``after`` those rows; ids outside ``[0, len(rows))`` are empty
    slots.  An edge that changed changes the rows of both its ends, so
    each is counted twice and the sum is halved; edges that stayed cancel.
    """
    changed = np.asarray(changed, np.int64)
    if changed.size == 0:
        return 0.0
    x = np.asarray(rows, np.float64)

    def total(adj):
        adj = np.asarray(adj, np.int64)
        ok = (adj >= 0) & (adj < len(x))
        d = np.linalg.norm(x[np.where(ok, adj, 0)] - x[changed][:, None, :],
                           axis=2)
        return float(np.where(ok, d, 0.0).sum())

    return 0.5 * (total(after) - total(before))
