"""Data-dependent row gathers from HBM, shared by the gather kernels.

Every distance kernel of the search and build paths (``gather_dist``,
``gather_dist_q``, ``pq_adc``, and through ``gather_dist`` the fused hop
and the MRNG occlusion test) needs rows ``table[ids[b, j]]`` of a table
that lives in HBM.  A BlockSpec cannot express that gather on a TPU: a
``(1, m)`` block breaks the ``(8, 128)`` block rule, and a squeezed
leading block dimension is refused for rank-2 arrays too.  Rows are
therefore copied by hand, one ``make_async_copy`` per row, all started
before the first wait so the copies of one query overlap.

Mosaic accepts a single-row DMA source only where a row is contiguous in
HBM: a 32-bit array exactly 128 lanes wide.  :func:`lane_chunks` splits a
table into such ``(N, 128)`` column chunks (widening 8- and 16-bit
element types to 32 bits), the kernel copies each chunk of a row into
its own ``(R, 128)`` VMEM buffer, and :func:`rows` joins the chunks back
along the lanes.  At ``m <= 128`` with a 32-bit table the chunking is a
no-op; wider or narrower tables pay one loop-invariant copy per program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def round_up(v: int, mult: int) -> int:
    return (v + mult - 1) // mult * mult


def lane_chunks(table: jax.Array) -> tuple[jax.Array, ...]:
    """(N, m) table -> ``ceil(m / 128)`` contiguous (N, 128) 32-bit chunks
    (zero-padded lanes; floats widen to f32, integers to int32 — both
    exact, so distances computed from the chunks are unchanged)."""
    if jnp.issubdtype(table.dtype, jnp.floating):
        table = table.astype(jnp.float32)
    else:
        table = table.astype(jnp.int32)
    m = table.shape[1]
    table = jnp.pad(table, ((0, 0), (0, round_up(m, LANES) - m)))
    return tuple(table[:, c:c + LANES]
                 for c in range(0, table.shape[1], LANES))


def row_scratch(n_rows: int, chunks, dtype) -> list:
    """VMEM landing buffers for ``n_rows`` gathered rows (one per chunk)
    plus the DMA semaphore all their copies signal."""
    r = round_up(n_rows, 8)
    return ([pltpu.VMEM((r, LANES), dtype) for _ in chunks]
            + [pltpu.SemaphoreType.DMA(())])


def gather(ids_ref, b, n_rows: int, chunks_hbm, bufs, sem) -> None:
    """Copy row ``ids_ref[b, j]`` of every chunk into row ``j`` of its
    buffer, for ``j < n_rows``.  Negative ids are skipped (their buffer
    rows keep stale data; callers mask those lanes).  All copies start
    before the first wait; they share one semaphore, and as every copy
    moves the same number of bytes, one wait per copy drains it."""

    def copies(j):
        row = ids_ref[b, j]
        return row, [pltpu.make_async_copy(src.at[pl.ds(row, 1), :],
                                           buf.at[pl.ds(j, 1), :], sem)
                     for src, buf in zip(chunks_hbm, bufs)]

    def start(j, carry):
        row, cps = copies(j)

        @pl.when(row >= 0)
        def _():
            for cp in cps:
                cp.start()

        return carry

    def wait(j, carry):
        row, cps = copies(j)

        @pl.when(row >= 0)
        def _():
            for cp in cps:
                cp.wait()

        return carry

    jax.lax.fori_loop(0, n_rows, start, 0)
    jax.lax.fori_loop(0, n_rows, wait, 0)


def rows(bufs) -> jax.Array:
    """The gathered rows, chunks joined back along the lanes: (R, 128 k)."""
    parts = [buf[...] for buf in bufs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def column_to_row(col: jax.Array, width: int) -> jax.Array:
    """(R, 1) column -> (1, width) row holding ``col[:width]``.

    Each 128-lane block of the row is a masked sublane sum over a
    diagonal of the matching column block: every output lane adds one
    value to zeros, so the move is exact."""
    parts = []
    for c0 in range(0, width, LANES):
        w = min(LANES, width - c0)
        blk = col[c0:c0 + round_up(w, 8)]
        sub = jax.lax.broadcasted_iota(jnp.int32, (blk.shape[0], w), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (blk.shape[0], w), 1)
        parts.append(jnp.sum(jnp.where(sub == lane, blk, 0.0), axis=0,
                             keepdims=True))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
