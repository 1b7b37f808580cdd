"""Public wrapper for the PQ gather + LUT-ADC distance kernel: clamps
out-of-range ids (INVALID = -1 slots are masked by the caller), lane-pads
the code rows to :data:`SUBSPACE_LANES` and the flattened codebook /
query to the 128-lane boundary, and builds the 0/1 subspace selector the
in-kernel LUT matmul contracts against.  All padding is
zero-contributing: padded query/codebook lanes difference to 0, and
selector columns past ``m_sub`` are zero so padded code lanes (code 0)
read a LUT column that is identically 0."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pq_adc import PQ_K, SUBSPACE_LANES, pq_adc_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=())
def padded_operands(codes: jax.Array, codebooks: jax.Array,
                    queries: jax.Array):
    """Natural operands -> the kernel's padded layout.

    codes (N, m_sub) uint8, codebooks (m_sub, 256, dsub) f32, queries
    (B, dim) f32 -> (codes (N, S) uint8, cb2 (256, mp) f32, sel (mp, S)
    f32, queries (B, mp) f32) with ``cb2[c, s*dsub+k] = codebooks[s, c, k]``
    and ``sel[s*dsub+k, s] = 1``.  Exposed so the exact-parity tests can
    feed the jnp oracle the very operands the kernel sees.
    """
    N, m_sub = codes.shape
    cb = jnp.asarray(codebooks, jnp.float32)
    ms, K, dsub = cb.shape
    if ms != m_sub or K != PQ_K:
        raise ValueError(f"codes/codebooks disagree: codes m_sub={m_sub}, "
                         f"codebooks {cb.shape}")
    S = SUBSPACE_LANES
    if m_sub > S:
        raise ValueError(f"m_sub={m_sub} exceeds the kernel's {S} "
                         "subspace lanes")
    dim = m_sub * dsub
    pad_m = (-dim) % 128
    mp = dim + pad_m
    c = jnp.pad(codes.astype(jnp.uint8), ((0, 0), (0, S - m_sub)))
    cb2 = jnp.pad(jnp.transpose(cb, (1, 0, 2)).reshape(K, dim),
                  ((0, 0), (0, pad_m)))
    lane = jnp.arange(mp)
    sel = ((lane[:, None] // dsub == jnp.arange(S)[None, :])
           & (lane < dim)[:, None]).astype(jnp.float32)
    q = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, pad_m)))
    return c, cb2, sel, q


@functools.partial(jax.jit, static_argnames=("squared", "interpret"))
def pq_adc(codes: jax.Array, codebooks: jax.Array, ids: jax.Array,
           queries: jax.Array, *, squared: bool = False,
           interpret: bool | None = None):
    """codes (N, m_sub) uint8, codebooks (m_sub, 256, dsub) f32, ids (B, d)
    int32, queries (B, dim) f32 -> (B, d) f32 ADC l2 distances."""
    if interpret is None:
        interpret = _default_interpret()
    N = codes.shape[0]
    c, cb2, sel, q = padded_operands(codes, codebooks, queries)
    safe_ids = jnp.clip(ids, 0, N - 1).astype(jnp.int32)
    return pq_adc_pallas(c.astype(jnp.int32), cb2, sel, safe_ids, q,
                         squared=squared, interpret=interpret)
