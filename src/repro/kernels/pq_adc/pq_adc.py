"""Fused PQ code-gather + LUT-ADC distance Pallas TPU kernel.

The product-quantized sibling of ``kernels/gather_dist_q``: one hop of the
DEG range search over a PQ store needs ``dist(q_b, decode(codes[ids[b,j]]))``
for ``j < d``.  Decoding in XLA would materialize the gathered ``(B, d, m)``
float32 tensor — 4 * dsub x the code bytes — before reducing.  Asymmetric
distance computation never decodes: for l2,

    ||q - decode(x)||^2 = sum_s ||q_s - C[s, code_s(x)]||^2,

so a per-query ``(256, S)`` table of squared sub-distances (S = padded
subspace lanes) built ONCE in VMEM turns every gathered code row into
table lookups + adds.  The HBM traffic per hop is one 128-lane code row
per neighbor plus the query row, independent of the vector dimension.
(The code bytes travel widened to 32 bits: ``row_gather`` explains why a
single-row copy needs 32-bit, 128-lane rows.)

grid = (B,): step ``b`` copies the ``d`` code rows of query ``b``
HBM->VMEM (``row_gather``: one manual DMA per row, all in flight
together), builds the query's LUT in VMEM, then one-hot-selects each row's
``m_sub`` LUT entries and writes the ``d`` distances as row ``b`` of the
output (queries and output stay resident in VMEM for the whole grid).

Operand layout (prepared by ``ops.padded_operands``): codes are lane-padded
to ``(N, S)`` and widened to int32 for the row copies (pad code 0 is
harmless — see below); the codebooks
arrive transposed/flattened as ``cb2 (256, mp)`` with
``cb2[c, s*dsub + k] = C[s, c, k]`` so the LUT build is one elementwise
square plus one ``(256, mp) @ (mp, S)`` MXU matmul against the 0/1
subspace-selector ``sel (mp, S)``; selector columns ``s >= m_sub`` are
zero, so LUT columns for padded code lanes are identically 0 and padded
lanes contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import row_gather

#: subspace-lane width of the padded code rows / LUT (one VREG of lanes);
#: bounds m_sub — dsub = 8 supports stores up to 1024 dims
SUBSPACE_LANES = 128

#: centroids per subspace (uint8 code byte)
PQ_K = 256


def _kernel(ids_ref, q_ref, cb2_ref, sel_ref, codes_hbm, out_ref, lut_ref,
            buf, sem, *, squared: bool):
    b = pl.program_id(0)
    C = out_ref.shape[1]
    row_gather.gather(ids_ref, b, C, (codes_hbm,), (buf,), sem)
    diff = cb2_ref[...] - q_ref[pl.ds(b, 1), :]                 # (256, mp)
    lut_ref[...] = jnp.dot(diff * diff, sel_ref[...],
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    code_iota = jax.lax.broadcasted_iota(jnp.int32, lut_ref.shape, 0)

    def score(j, acc):
        hit = code_iota == buf[pl.ds(j, 1), :]                  # (256, S)
        d2 = jnp.sum(jnp.where(hit, lut_ref[...], 0.0), axis=(0, 1),
                     keepdims=True)
        return jnp.where(lane == j, jnp.maximum(d2, 0.0), acc)

    d2 = jax.lax.fori_loop(0, C, score, jnp.zeros((1, C), jnp.float32))
    out_ref[pl.ds(b, 1), :] = d2 if squared else jnp.sqrt(d2)


@functools.partial(jax.jit, static_argnames=("squared", "interpret"))
def pq_adc_pallas(codes: jax.Array, cb2: jax.Array, sel: jax.Array,
                  ids: jax.Array, queries: jax.Array, *,
                  squared: bool, interpret: bool):
    """codes (N, S) int32 code bytes, cb2 (256, mp) f32, sel (mp, S) f32,
    ids (B, d) int32 in [0, N), queries (B, mp) f32 -> (B, d) f32
    distances."""
    N, S = codes.shape
    K, mp = cb2.shape
    B, d = ids.shape
    whole = lambda shape: pl.BlockSpec(shape, lambda b, ids: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[whole((B, mp)), whole((K, mp)), whole((mp, S)),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=whole((B, d)),
        scratch_shapes=[pltpu.VMEM((K, S), jnp.float32)]
        + row_gather.row_scratch(d, (codes,), codes.dtype),
    )
    kernel = functools.partial(_kernel, squared=squared)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, d), jnp.float32),
        interpret=interpret,
    )(ids, queries, cb2, sel, codes)
