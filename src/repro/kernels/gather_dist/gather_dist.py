"""Fused neighbor-gather + distance Pallas TPU kernel.

One hop of the DEG range search needs ``dist(q_b, vectors[ids[b, j]])`` for
``j < C`` — a random gather of ``C`` rows per query followed by a
reduction.  In plain XLA this materializes the gathered ``(B, C, m)``
tensor in HBM; here the rows land in VMEM and never leave it.

grid = (B,): step ``b`` copies the ``C`` rows of query ``b`` HBM->VMEM
(``row_gather.gather``: one manual DMA per row, all in flight together;
the ids are scalar-prefetched into SMEM so they can address the copies),
scores them against the query row in one vectorized pass, and writes the
``C`` distances as row ``b`` of the output.  Queries and output stay
resident in VMEM for the whole grid.  Rows with a negative id are not
copied; their output lanes are unspecified and the caller masks them.

The same kernel serves the SQ8 store (``scale`` multiplies the int8 codes
after the copy — the dequantized float32 rows exist only in VMEM), the
fused multi-expansion hop (``kernels/fused_hop``) and the MRNG lune test
(``kernels/mrng_occlusion``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import row_gather


def _kernel(ids_ref, q_ref, *refs, n_chunks: int, scaled: bool,
            squared: bool):
    chunks = refs[:n_chunks]
    scale_ref = refs[n_chunks] if scaled else None
    out_ref = refs[n_chunks + scaled]
    bufs = refs[n_chunks + scaled + 1:-1]
    sem = refs[-1]
    b = pl.program_id(0)
    C = out_ref.shape[1]
    row_gather.gather(ids_ref, b, C, chunks, bufs, sem)
    g = row_gather.rows(bufs).astype(jnp.float32)          # (R, M)
    if scaled:
        g = g * scale_ref[...]
    diff = g - q_ref[pl.ds(b, 1), :].astype(jnp.float32)
    d2 = jnp.maximum(jnp.sum(diff * diff, axis=1, keepdims=True), 0.0)
    dist = d2 if squared else jnp.sqrt(d2)                 # (R, 1)
    out_ref[pl.ds(b, 1), :] = row_gather.column_to_row(dist, C)


@functools.partial(jax.jit, static_argnames=("squared", "interpret"))
def gather_dist_pallas(chunks: tuple, ids: jax.Array, queries: jax.Array,
                       scale: jax.Array | None = None, *, squared: bool,
                       interpret: bool):
    """chunks: ``row_gather.lane_chunks`` of the (N, m) table; ids (B, C)
    int32 in [0, N) (negative = skip); queries (B, M) f32 with
    M = 128 * len(chunks); scale (1, M) f32 or None -> (B, C) f32."""
    B, C = ids.shape
    M = queries.shape[1]
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    whole = lambda shape: pl.BlockSpec(shape, lambda b, ids: (0, 0))
    in_specs = [whole((B, M))] + [any_spec] * len(chunks)
    operands = [queries, *chunks]
    if scale is not None:
        in_specs.append(whole((1, M)))
        operands.append(scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=in_specs,
        out_specs=whole((B, C)),
        scratch_shapes=row_gather.row_scratch(C, chunks, chunks[0].dtype),
    )
    kernel = functools.partial(_kernel, n_chunks=len(chunks),
                               scaled=scale is not None, squared=squared)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=interpret,
    )(ids, *operands)
