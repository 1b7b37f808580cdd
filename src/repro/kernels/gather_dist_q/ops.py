"""Public wrapper for the gather+dequant+distance path: the SQ8 store runs
the ``gather_dist`` kernel with its per-dimension scale, so the dequantized
float32 rows exist only in VMEM.  Clamps out-of-range ids (INVALID = -1
slots are masked by the caller) and pads the feature dim to the 128-lane
boundary (zero code x zero scale x zero query padding contributes nothing
to the distance)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import row_gather
from repro.kernels.gather_dist.gather_dist import gather_dist_pallas
from repro.kernels.gather_dist.ops import padded_queries


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("squared", "interpret"))
def gather_dist_q(codes: jax.Array, scale: jax.Array, ids: jax.Array,
                  queries: jax.Array, *, squared: bool = False,
                  interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    N = codes.shape[0]
    chunks = row_gather.lane_chunks(codes.astype(jnp.int8))
    M = row_gather.LANES * len(chunks)
    s = jnp.pad(scale.astype(jnp.float32), (0, M - scale.shape[0]))[None, :]
    q = padded_queries(queries, M)
    safe_ids = jnp.clip(ids, 0, N - 1).astype(jnp.int32)
    return gather_dist_pallas(chunks, safe_ids, q, s, squared=squared,
                              interpret=interpret)
