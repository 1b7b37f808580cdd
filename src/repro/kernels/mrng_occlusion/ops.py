"""Public dispatch for the gather + distance + MRNG-occlusion primitive.

The inner decision of DEG construction (Alg. 2/3) and continuous
refinement (Alg. 5) is the *lune test*: a candidate edge (v, c) at
distance ``delta`` is occluded by a vertex ``u`` adjacent to ``c`` iff

    delta > max(d(v, u), w(c, u))

i.e. ``u`` lies inside the lune of the candidate edge.  Answering it for a
batch of candidates needs, per candidate, the distances from the query
vertex to every neighbor of the candidate: a gather of ``K * d`` vector
rows per query.  Both the extension path (candidates = search results,
query = the new vertex) and the refinement path (candidates = a vertex's
own neighbors, cand_d = its edge weights) consume the same program.

Backends:

* ``"jnp"``    — the pure-jnp oracle (any metric); the default inside the
                 jitted construction programs.
* ``"pallas"`` — l2 / sqeuclidean: the ``gather_dist`` kernel scores the
                 ``K * d`` gathered rows of each query in VMEM (the
                 ``(B, K, d, m)`` tensor never reaches HBM); the compare
                 is one elementwise op on its output.  Out-of-range ids
                 are clamped (INVALID = -1 slots are masked by the
                 caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_gather
from repro.kernels.gather_dist.gather_dist import gather_dist_pallas
from repro.kernels.gather_dist.ops import padded_queries

from .ref import mrng_occlusion_ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("metric", "backend", "interpret"))
def mrng_occlusion(vectors: jax.Array, nbr_ids: jax.Array,
                   queries: jax.Array, cand_dists: jax.Array,
                   nbr_weights: jax.Array, *, metric: str = "l2",
                   backend: str = "jnp", interpret: bool | None = None):
    """-> (nbr_dist (B, K, d) f32, occl (B, K, d) bool).  ``occl[b, i, j]``
    answers: does neighbor j of candidate i occlude the candidate edge
    (lune test, Alg. 2)?  Callers mask INVALID id lanes themselves."""
    if backend == "jnp":
        return mrng_occlusion_ref(vectors, nbr_ids, queries, cand_dists,
                                  nbr_weights, metric=metric)
    if backend != "pallas":
        raise ValueError(f"unknown mrng_occlusion backend {backend!r}")
    if metric not in ("l2", "sqeuclidean"):
        raise ValueError(f"the pallas lune test is l2/sqeuclidean only, "
                         f"got metric {metric!r}")
    if interpret is None:
        interpret = _default_interpret()
    N = vectors.shape[0]
    B, K, d = nbr_ids.shape
    chunks = row_gather.lane_chunks(vectors.astype(jnp.float32))
    q = padded_queries(queries, row_gather.LANES * len(chunks))
    safe_ids = jnp.clip(nbr_ids, 0, N - 1).astype(jnp.int32)
    nd = gather_dist_pallas(chunks, safe_ids.reshape(B, K * d), q,
                            squared=(metric == "sqeuclidean"),
                            interpret=interpret).reshape(B, K, d)
    occ = (cand_dists.astype(jnp.float32)[:, :, None]
           > jnp.maximum(nd, nbr_weights.astype(jnp.float32)))
    return nd, occ
