"""Collective building blocks used by the distributed index and recsys.

Everything here is written for ``jax.shard_map`` over the production mesh
(launch/mesh.py) so the communication schedule is explicit and shows up
verbatim in the dry-run HLO for the roofline analysis.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

Array = jax.Array


# ---------------------------------------------------------------------------
# distributed exact top-k merge
# ---------------------------------------------------------------------------
def topk_merge_allgather(local_vals: Array, local_ids: Array, k: int,
                         axis_name) -> tuple[Array, Array]:
    """Inside shard_map: each shard holds (B, k) local top-k candidates with
    *global* ids; all-gather along ``axis_name`` and re-select top-k.

    Collective volume per query: shards * k * 8 bytes — the tiny merge path
    that makes sharded ANN search collective-light (DESIGN.md §4).
    """
    vals = jax.lax.all_gather(local_vals, axis_name, axis=1, tiled=True)
    ids = jax.lax.all_gather(local_ids, axis_name, axis=1, tiled=True)
    top, pos = jax.lax.top_k(-vals, k)          # distances: smaller is better
    return -top, jnp.take_along_axis(ids, pos, axis=1)


def sharded_brute_topk(mesh: Mesh, *, k: int, shard_axes: Sequence[str],
                       batch_axes=None, metric: str = "ip") -> Callable:
    """Returns f(queries (B, m), db (N, m)) -> (vals (B, k), ids (B, k)):
    DB rows sharded over ``shard_axes``; local scoring + exact global merge.

    ``metric='ip'`` scores by inner product (descending); ``'l2'`` by
    euclidean distance (ascending). Used by retrieval_cand and as the
    serial-scan baseline at scale.
    """
    shard_axes = tuple(shard_axes)
    q_spec = P(batch_axes, None)
    db_spec = P(shard_axes, None)

    def local(q, db):
        if metric == "ip":
            scores = -(q @ db.T)                # negate: unify to "smaller"
        else:
            q2 = jnp.sum(q * q, 1, keepdims=True)
            d2 = jnp.sum(db * db, 1)
            scores = q2 + d2[None, :] - 2.0 * (q @ db.T)
        n_local = db.shape[0]
        kk = min(k, n_local)
        neg, pos = jax.lax.top_k(-scores, kk)
        # global row ids: offset by this shard's position along shard_axes
        idx = jax.lax.axis_index(shard_axes)
        ids = pos + idx * n_local
        vals, ids = topk_merge_allgather(-neg, ids, k, shard_axes)
        if metric == "ip":
            vals = -vals
        return vals, ids

    f = shard_map(local, mesh=mesh, in_specs=(q_spec, db_spec),
                  out_specs=(q_spec, q_spec), check_vma=False)
    return f


# ---------------------------------------------------------------------------
# gradient compression (int8 all-reduce path)
# ---------------------------------------------------------------------------
def int8_compress(x: Array) -> tuple[Array, Array]:
    """Per-tensor symmetric int8 quantization: returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def int8_decompress(q: Array, scale: Array, dtype=jnp.float32) -> Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compressed_psum(x: Array, axis_name) -> Array:
    """All-reduce with int8 payload: agree on a *global* scale (scalar pmax
    — per-shard scales cannot be mixed after the sum), quantize, psum the
    int8 payload (as int32 to avoid overflow at >127 shards), dequantize.
    ~4x wire-bytes reduction on the DP all-reduce path for the cost of one
    extra scalar collective."""
    amax = jax.lax.pmax(jnp.max(jnp.abs(x)).astype(jnp.float32), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale


def make_compressed_grad_allreduce(mesh: Mesh, dp_axis) -> Callable:
    """tree -> tree: int8-compressed mean-all-reduce over the DP axes.

    Drop-in for the implicit GSPMD gradient all-reduce when gradients are
    computed per-shard inside shard_map (launch/train.py --compress-grads).
    """

    def reduce_tree(grads):
        def one(g):
            s = compressed_psum(g, dp_axis)
            n = jax.lax.psum(jnp.ones((), jnp.float32), dp_axis)
            return (s / n).astype(g.dtype)

        return jax.tree.map(one, grads)

    return reduce_tree


# ---------------------------------------------------------------------------
# sharded embedding lookup factory (recsys hot path)
# ---------------------------------------------------------------------------
def make_sharded_lookup(mesh: Mesh, *, table_axis: str = "model",
                        batch_axes=None) -> Callable:
    """Returns lookup(table, ids) for a row-sharded table under jit.

    table: (V, E) sharded P(table_axis, None); ids: (B, ...) global rows,
    sharded over ``batch_axes``.  Each shard resolves local hits and psums
    over the table axis (models/embedding_bag.sharded_embedding_lookup).
    """
    from repro.models.embedding_bag import sharded_embedding_lookup

    def local(table, ids):
        n_local = table.shape[0]
        shard = jax.lax.axis_index(table_axis)
        offset = shard * n_local
        return sharded_embedding_lookup(table, ids, offset, (table_axis,))

    def lookup(table, ids):
        f = shard_map(
            local, mesh=mesh,
            in_specs=(P(table_axis, None), P(batch_axes, *([None] * (ids.ndim - 1)))),
            out_specs=P(batch_axes, *([None] * ids.ndim)),
            check_vma=False)
        return f(table, ids)

    return lookup
