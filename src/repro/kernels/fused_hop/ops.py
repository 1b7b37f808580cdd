"""Public dispatch for the multi-expansion hop.

One hop of the multi-expansion beam engine (``core/beam.py``,
``expand_width=E``) is, per query lane: gather the adjacency rows of the E
selected vertices, drop neighbors already visited or repeated within the
hop, gather the surviving vector rows, score them against the query, and
keep those inside the range radius, compacted to the front.

Backends:

* ``"jnp"``    — the pure-jnp oracle (``ref.fused_hop_ref``); also the
                 documentation of the hop semantics.
* ``"pallas"`` — the filter and compaction halves of the oracle around the
                 ``gather_dist`` kernel: the surviving rows are copied
                 HBM->VMEM and scored there, and filtered neighbors cost
                 no copy at all (their ids reach the kernel negative).
                 Ids, counts and the compaction order are the oracle's;
                 distances agree to rounding.

A ``visited=None`` call runs without the visited filter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_gather
from repro.kernels.gather_dist.gather_dist import gather_dist_pallas
from repro.kernels.gather_dist.ops import padded_queries

from .ref import compact_hop, fused_hop_ref, hop_candidates


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("squared", "backend", "interpret"))
def fused_hop(adjacency: jax.Array, vectors: jax.Array, sel_ids: jax.Array,
              queries: jax.Array, dmax: jax.Array,
              visited: jax.Array | None = None, *, n_valid: jax.Array,
              squared: bool = False, backend: str = "jnp",
              interpret: bool | None = None):
    """One multi-expansion hop for B lanes — see ``ref.fused_hop_ref`` for
    the argument/return contract."""
    dmax = jnp.asarray(dmax, jnp.float32)
    if backend == "jnp":
        return fused_hop_ref(adjacency, vectors, sel_ids, queries, dmax,
                             visited, n_valid=n_valid, squared=squared)
    if backend != "pallas":
        raise ValueError(f"unknown fused_hop backend {backend!r}")
    if interpret is None:
        interpret = _default_interpret()
    flat, vmask, scored = hop_candidates(adjacency, sel_ids, visited,
                                         n_valid=n_valid)
    chunks = row_gather.lane_chunks(vectors)
    qt = jnp.bfloat16 if vectors.dtype == jnp.bfloat16 else jnp.float32
    q = padded_queries(queries, row_gather.LANES * len(chunks), qt)
    ids = jnp.where(scored, flat, -1).astype(jnp.int32)
    nd = gather_dist_pallas(chunks, ids, q, squared=squared,
                            interpret=interpret)
    return compact_hop(flat, vmask, scored, nd, dmax)
