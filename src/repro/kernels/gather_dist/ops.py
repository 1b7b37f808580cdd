"""Public wrapper for the gather+distance kernel: clamps out-of-range ids
(INVALID = -1 slots are masked by the caller) and lays the table out as
the 128-lane 32-bit chunks the kernel's row copies need."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_gather

from .gather_dist import gather_dist_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def padded_queries(queries: jax.Array, m_pad: int, dtype=jnp.float32):
    """(B, m) queries -> (B, m_pad) f32, rounded through ``dtype`` first
    (a bf16 store scores its queries in bf16, as it always has)."""
    q = queries.astype(dtype).astype(jnp.float32)
    return jnp.pad(q, ((0, 0), (0, m_pad - q.shape[1])))


@functools.partial(jax.jit, static_argnames=("squared", "interpret"))
def gather_dist(vectors: jax.Array, ids: jax.Array, queries: jax.Array, *,
                squared: bool = False, interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    N = vectors.shape[0]
    chunks = row_gather.lane_chunks(vectors)
    qt = jnp.bfloat16 if vectors.dtype == jnp.bfloat16 else jnp.float32
    q = padded_queries(queries, row_gather.LANES * len(chunks), qt)
    safe_ids = jnp.clip(ids, 0, N - 1).astype(jnp.int32)
    return gather_dist_pallas(chunks, safe_ids, q, squared=squared,
                              interpret=interpret)
