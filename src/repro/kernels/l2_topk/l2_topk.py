"""Fused L2-distance + streaming top-k Pallas TPU kernel.

The brute-force scan of an ANN system is one big ``(B, m) x (m, N)`` matmul
(the ``|q|^2 - 2 q.x + |x|^2`` expansion) followed by a row-wise top-k.  A
naive implementation materializes the full ``(B, N)`` distance matrix in HBM
(N can be 10^6+); this kernel keeps a running per-query top-k in VMEM scratch
and never writes the matrix out:

* grid = (B/TB, N/TN), N innermost so the scratch accumulates across tiles;
* the query tile (TB, m) and base tile (TN, m) live in VMEM; the distance
  tile is one MXU matmul (2*TB*TN*m FLOPs) plus rank-1 corrections;
* the top-k merge is k extraction steps of pure VPU ops (min / where /
  broadcasted_iota one-hots — no in-kernel sort or scatter, both of which
  are TPU-hostile).

VMEM budget at defaults (TB=8, TN=512, m<=1024, fp32):
  base tile 2 MB + query tile 32 KB + scratch ~ (TB*(K+TN)) -> well under
  the ~16 MB/core budget; TN can be raised to 2048 for small m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_LIMIT = jnp.float32(3.0e38)


def _extract_topk(cand_d, cand_i, k: int):
    """k extraction steps over (TB, C) candidates, TPU-safe (no sort/scatter).

    Returns (TB, k) best distances/ids, ascending."""
    TB, C = cand_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (TB, C), 1)

    def step(t, carry):
        bd, bi, cd = carry
        pos = jnp.argmin(cd, axis=1)                      # (TB,)
        sel = col == pos[:, None]                         # one-hot (TB, C)
        val = jnp.min(cd, axis=1)                         # (TB,)
        vid = jnp.max(jnp.where(sel, cand_i, -1), axis=1)
        tcol = jax.lax.broadcasted_iota(jnp.int32, bd.shape, 1) == t
        bd = jnp.where(tcol, val[:, None], bd)
        bi = jnp.where(tcol, vid[:, None], bi)
        cd = jnp.where(sel, jnp.inf, cd)
        return bd, bi, cd

    bd0 = jnp.full((TB, k), jnp.inf, cand_d.dtype)
    bi0 = jnp.full((TB, k), -1, jnp.int32)
    bd, bi, _ = jax.lax.fori_loop(0, k, step, (bd0, bi0, cand_d))
    return bd, bi


def _kernel(q_ref, x_ref, od_ref, oi_ref, sd_ref, si_ref, *, k: int,
            tn: int, squared: bool):
    n_idx = pl.program_id(1)
    n_tiles = pl.num_programs(1)

    @pl.when(n_idx == 0)
    def _init():
        sd_ref[...] = jnp.full_like(sd_ref, jnp.inf)
        si_ref[...] = jnp.full_like(si_ref, -1)

    q = q_ref[...].astype(jnp.float32)                    # (TB, m)
    x = x_ref[...].astype(jnp.float32)                    # (TN, m)
    qn = jnp.sum(q * q, axis=1, keepdims=True)            # (TB, 1)
    xn = jnp.sum(x * x, axis=1)                           # (TN,)
    dot = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    d2 = jnp.maximum(qn - 2.0 * dot + xn[None, :], 0.0)   # (TB, TN)
    dist = d2 if squared else jnp.sqrt(d2)

    gids = n_idx * tn + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    cand_d = jnp.concatenate([sd_ref[...], dist], axis=1)
    cand_i = jnp.concatenate([si_ref[...], gids], axis=1)
    bd, bi = _extract_topk(cand_d, cand_i, k)
    sd_ref[...] = bd
    si_ref[...] = bi

    @pl.when(n_idx == n_tiles - 1)
    def _flush():
        od_ref[...] = sd_ref[...]
        oi_ref[...] = si_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "tb", "tn", "squared", "interpret"),
)
def l2_topk_pallas(queries: jax.Array, base: jax.Array, k: int, *,
                   tb: int = 8, tn: int = 512, squared: bool = False,
                   interpret: bool):
    """queries (B, m), base (N, m) -> (dists (B, k), ids (B, k)).

    B must be a multiple of tb and N of tn (ops.py pads)."""
    B, m = queries.shape
    N, _ = base.shape
    assert B % tb == 0 and N % tn == 0, (B, tb, N, tn)
    grid = (B // tb, N // tn)
    kernel = functools.partial(_kernel, k=k, tn=tn, squared=squared)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, m), lambda i, n: (i, 0)),
            pl.BlockSpec((tn, m), lambda i, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, k), lambda i, n: (i, 0)),
            pl.BlockSpec((tb, k), lambda i, n: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tb, k), jnp.float32),
            pltpu.VMEM((tb, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, base)
