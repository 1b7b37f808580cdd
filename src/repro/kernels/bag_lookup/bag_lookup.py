"""EmbeddingBag (gather-reduce) Pallas TPU kernel.

JAX has no native ``nn.EmbeddingBag``; the recsys substrate implements it as
``take + segment_sum`` (see repro.models.embedding_bag).  This kernel is the
fused TPU version for the *fixed-fields* layout used by DLRM/DCN-style
models: ``out[b] = sum_f w[b,f] * table[idx[b,f]]``.

Like gather_dist, the table rows are DMA'd HBM->VMEM via a scalar-prefetched
index map; the accumulation lives in the revisited output block (grid is
(B, F) with F innermost, so out[i] stays resident in VMEM across the F
steps — one init at f==0, one accumulate per field, no HBM round-trips).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, tab_ref, w_ref, out_ref):
    f = pl.program_id(1)
    w = w_ref[0, pl.dslice(f, 1)].astype(jnp.float32)    # (1,)
    row = tab_ref[0, :].astype(jnp.float32) * w          # (E,)

    @pl.when(f == 0)
    def _init():
        out_ref[0, :] = row

    @pl.when(f != 0)
    def _acc():
        out_ref[0, :] = out_ref[0, :] + row


@functools.partial(jax.jit, static_argnames=("interpret",))
def bag_lookup_pallas(table: jax.Array, ids: jax.Array, weights: jax.Array, *,
                      interpret: bool):
    """table (V, E), ids (B, F) int32, weights (B, F) -> (B, E) weighted sum."""
    V, E = table.shape
    B, F = ids.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, F),
        in_specs=[
            pl.BlockSpec((1, E), lambda i, f, ids: (ids[i, f], 0)),
            pl.BlockSpec((1, F), lambda i, f, ids: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, E), lambda i, f, ids: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, E), jnp.float32),
        interpret=interpret,
    )(ids, table, weights)
