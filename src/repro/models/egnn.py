"""E(n)-Equivariant Graph Neural Network (Satorras et al., arXiv:2102.09844).

Message passing is implemented with ``jax.ops.segment_sum`` over an
edge-index array — the JAX-native scatter formulation the assignment
prescribes (no sparse formats needed).  Three input layouts map to the four
assigned shapes:

* ``egnn_forward``        — one (possibly huge) graph: nodes (N, F),
  coords (N, 3), edges (2, E).  Used by full_graph_sm / ogb_products and by
  the *sampled* minibatch_lg subgraphs (the sampler in data/graphs.py emits
  exactly this layout, padded to static shape).
* ``egnn_forward_batched``— vmapped over a batch of small dense graphs
  (molecule shape).

Equivariance: coordinate updates are linear combinations of relative
positions, so rotating/translating inputs rotates/translates outputs —
asserted as a property test (tests/test_models_egnn.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .layers import abs_mlp_tower, abs_p, apply_mlp_tower, mlp_tower

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EGNNConfig:
    name: str = "egnn"
    n_layers: int = 4
    d_hidden: int = 64
    d_feat: int = 1433          # input feature dim (dataset-dependent)
    d_edge: int = 0             # optional edge attribute dim
    n_classes: int = 40
    coord_agg: str = "mean"
    dtype: object = jnp.float32
    # SPMD: constrain node arrays (h, x) to this sharding at every layer
    # boundary (a §Perf variant: 256-way node sharding instead of DP-only).
    node_shard_axes: object = None


def abstract_params(cfg: EGNNConfig) -> dict:
    h = cfg.d_hidden
    msg_in = 2 * h + 1 + cfg.d_edge
    layer = {
        "phi_e": abs_mlp_tower([msg_in, h, h]),
        "phi_x": abs_mlp_tower([h, h, 1]),
        "phi_h": abs_mlp_tower([2 * h, h, h]),
    }
    return {
        "encoder": abs_p(cfg.d_feat, h),
        "layers": jax.tree.map(
            lambda s: abs_p(cfg.n_layers, *s.shape), layer),
        "decoder": abs_mlp_tower([h, h, cfg.n_classes]),
    }


def init_params(key: jax.Array, cfg: EGNNConfig) -> dict:
    from .layers import dense_init

    h = cfg.d_hidden
    msg_in = 2 * h + 1 + cfg.d_edge
    keys = jax.random.split(key, cfg.n_layers * 3 + 2)
    layers = []
    for i in range(cfg.n_layers):
        layers.append({
            "phi_e": mlp_tower(keys[3 * i], [msg_in, h, h]),
            "phi_x": mlp_tower(keys[3 * i + 1], [h, h, 1]),
            "phi_h": mlp_tower(keys[3 * i + 2], [2 * h, h, h]),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "encoder": dense_init(keys[-2], (cfg.d_feat, h)),
        "layers": stacked,
        "decoder": mlp_tower(keys[-1], [h, h, cfg.n_classes]),
    }


def _egnn_layer(lp: dict, h: Array, x: Array, edges: Array,
                edge_attr, n_nodes: int, edge_valid, cfg: EGNNConfig):
    """h (N, F), x (N, 3), edges (2, E) int32 (src, dst)."""

    def _agg_wsc(t):
        # Pin scatter outputs to the node sharding: GSPMD then emits a
        # reduce-scatter of the per-edge-shard partial aggregates instead of
        # a full all-reduce (§Perf EGNN iteration 3).
        if cfg.node_shard_axes is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, jax.sharding.PartitionSpec(tuple(cfg.node_shard_axes),
                                          *([None] * (t.ndim - 1))))

    src, dst = edges[0], edges[1]
    hv_s = jnp.take(h, src, axis=0)
    hv_d = jnp.take(h, dst, axis=0)
    xs = jnp.take(x, src, axis=0)
    xd = jnp.take(x, dst, axis=0)
    rel = xd - xs                                          # (E, 3)
    d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
    feats = [hv_d, hv_s, d2]
    if edge_attr is not None:
        feats.append(edge_attr)
    m = apply_mlp_tower(lp["phi_e"], jnp.concatenate(feats, axis=-1),
                        act=jax.nn.silu, final_act=jax.nn.silu)   # (E, F)
    if edge_valid is not None:
        m = m * edge_valid[:, None].astype(m.dtype)
    # coordinate update (equivariant): x_d += agg_e rel * phi_x(m)
    cw = apply_mlp_tower(lp["phi_x"], m, act=jax.nn.silu)          # (E, 1)
    if edge_valid is not None:
        cw = cw * edge_valid[:, None].astype(cw.dtype)
    coord_msg = _agg_wsc(jax.ops.segment_sum(rel * cw, dst,
                                             num_segments=n_nodes))
    if cfg.coord_agg == "mean":
        deg = _agg_wsc(jax.ops.segment_sum(
            jnp.ones_like(cw[:, 0]) if edge_valid is None
            else edge_valid.astype(cw.dtype), dst, num_segments=n_nodes))
        coord_msg = coord_msg / jnp.maximum(deg[:, None], 1.0)
    x = x + coord_msg
    agg = _agg_wsc(jax.ops.segment_sum(m, dst,
                                       num_segments=n_nodes))     # (N, F)
    upd = apply_mlp_tower(lp["phi_h"], jnp.concatenate([h, agg], -1),
                          act=jax.nn.silu)
    return h + upd, x


def _node_wsc(t: Array, cfg: EGNNConfig) -> Array:
    if cfg.node_shard_axes is None:
        return t
    spec = jax.sharding.PartitionSpec(tuple(cfg.node_shard_axes),
                                      *([None] * (t.ndim - 1)))
    return jax.lax.with_sharding_constraint(t, spec)


def egnn_forward(params: dict, feats: Array, coords: Array, edges: Array,
                 cfg: EGNNConfig, edge_attr=None, edge_valid=None):
    """Returns (node logits (N, n_classes), final coords (N, 3))."""
    n_nodes = feats.shape[0]
    h = feats.astype(cfg.dtype) @ params["encoder"].astype(cfg.dtype)
    x = coords.astype(cfg.dtype)

    def body(carry, lp):
        h, x = carry
        h, x = _node_wsc(h, cfg), _node_wsc(x, cfg)
        h, x = _egnn_layer(lp, h, x, edges, edge_attr, n_nodes, edge_valid,
                           cfg)
        return (h, x), None

    (h, x), _ = jax.lax.scan(body, (h, x), params["layers"])
    logits = apply_mlp_tower(params["decoder"], h, act=jax.nn.silu)
    return logits.astype(jnp.float32), x


def egnn_forward_batched(params, feats, coords, edges, cfg: EGNNConfig,
                         edge_valid=None):
    """feats (B, N, F), coords (B, N, 3), edges (B, 2, E)."""
    fn = lambda f, c, e, ev: egnn_forward(params, f, c, e, cfg,
                                          edge_valid=ev)
    if edge_valid is None:
        edge_valid = jnp.ones(edges[:, 0].shape, bool)
    return jax.vmap(fn)(feats, coords, edges, edge_valid)


# ---------------------------------------------------------------------------
# shard_map version: dst-partitioned edges (EXPERIMENTS.md §Perf, EGNN it. 4)
# ---------------------------------------------------------------------------
def make_sharded_loss(cfg: EGNNConfig, mesh, shard_axes) -> "Callable":
    """Locality-aware distributed EGNN loss.

    GSPMD lowers ``segment_sum`` over sharded edges into a full-size scatter
    + ALL-REDUCE of the node arrays per layer — it has no reduce-scatter
    strategy for scatters, and no way to exploit edge locality.  This
    shard_map version imposes a *data-layout contract* instead: device ``s``
    owns node rows ``[s*Nl, (s+1)*Nl)`` and exactly the edges whose dst lies
    in that range (``data.graphs.partition_edges_by_dst``).  Then every
    scatter is local, and the only collective is one all-gather of the
    (bf16) node arrays per layer for the src-side halo — whose transpose in
    backward is a reduce-scatter.  Wire bytes: one AG per layer vs. two+
    f32 ARs.
    """
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    axes = tuple(shard_axes)

    def local(p, feats, coords, edges, ev, labels):
        Nl = feats.shape[0]
        idx = jax.lax.axis_index(axes)
        src, dst = edges[0], edges[1]
        dst_local = jnp.clip(dst - idx * Nl, 0, Nl - 1)
        evf = ev.astype(cfg.dtype)
        h = feats.astype(cfg.dtype) @ p["encoder"].astype(cfg.dtype)
        x = coords.astype(cfg.dtype)

        def body(carry, lp):
            h, x = carry
            h_full = jax.lax.all_gather(h, axes, axis=0, tiled=True)
            x_full = jax.lax.all_gather(x, axes, axis=0, tiled=True)
            hv_s = jnp.take(h_full, src, axis=0)
            hv_d = jnp.take(h_full, dst, axis=0)
            rel = jnp.take(x_full, dst, axis=0) - jnp.take(x_full, src,
                                                           axis=0)
            d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
            m = apply_mlp_tower(lp["phi_e"],
                                jnp.concatenate([hv_d, hv_s, d2], -1),
                                act=jax.nn.silu, final_act=jax.nn.silu)
            m = m * evf[:, None]
            cw = apply_mlp_tower(lp["phi_x"], m, act=jax.nn.silu)
            cw = cw * evf[:, None]
            coord_msg = jax.ops.segment_sum(rel * cw, dst_local,
                                            num_segments=Nl)     # LOCAL
            if cfg.coord_agg == "mean":
                deg = jax.ops.segment_sum(evf, dst_local, num_segments=Nl)
                coord_msg = coord_msg / jnp.maximum(deg[:, None], 1.0)
            x = x + coord_msg
            agg = jax.ops.segment_sum(m, dst_local, num_segments=Nl)
            h = h + apply_mlp_tower(lp["phi_h"],
                                    jnp.concatenate([h, agg], -1),
                                    act=jax.nn.silu)
            return (h, x), None

        (h, x), _ = jax.lax.scan(body, (h, x), p["layers"])
        logits = apply_mlp_tower(p["decoder"], h,
                                 act=jax.nn.silu).astype(jnp.float32)
        mask = labels >= 0
        safe = jnp.clip(labels, 0, cfg.n_classes - 1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        mf = mask.astype(jnp.float32)
        num = jax.lax.psum(jnp.sum((lse - true) * mf), axes)
        den = jax.lax.psum(jnp.sum(mf), axes)
        loss = num / jnp.maximum(den, 1.0)
        return loss

    def loss_fn(params, batch):
        pspec = jax.tree.map(lambda _: P(), params)
        f = shard_map(
            local, mesh=mesh,
            in_specs=(pspec, P(axes, None), P(axes, None), P(None, axes),
                      P(axes), P(axes)),
            out_specs=P(), check_vma=False)
        loss = f(params, batch["feats"], batch["coords"], batch["edges"],
                 batch["edge_valid"], batch["labels"])
        return loss, {"nll": loss}

    return loss_fn


def loss_fn(params, batch, cfg: EGNNConfig):
    """Node classification cross-entropy (full-graph or sampled)."""
    if batch["feats"].ndim == 3:
        logits, _ = egnn_forward_batched(params, batch["feats"],
                                         batch["coords"], batch["edges"], cfg,
                                         batch.get("edge_valid"))
        logits = jnp.mean(logits, axis=1)        # graph-level: mean pool
    else:
        logits, _ = egnn_forward(params, batch["feats"], batch["coords"],
                                 batch["edges"], cfg,
                                 edge_valid=batch.get("edge_valid"))
    labels = batch["labels"]
    mask = (labels >= 0)
    safe = jnp.clip(labels, 0, cfg.n_classes - 1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    m = mask.astype(jnp.float32)
    loss = jnp.sum((lse - true) * m) / jnp.maximum(jnp.sum(m), 1.0)
    return loss, {"nll": loss}


def node_embeddings(params, feats, coords, edges, cfg: EGNNConfig,
                    edge_valid=None):
    """Penultimate node embeddings — what DEG indexes for molecule retrieval."""
    n_nodes = feats.shape[0]
    h = feats.astype(cfg.dtype) @ params["encoder"].astype(cfg.dtype)
    x = coords.astype(cfg.dtype)

    def body(carry, lp):
        h, x = carry
        h, x = _egnn_layer(lp, h, x, edges, None, n_nodes, edge_valid, cfg)
        return (h, x), None

    (h, _), _ = jax.lax.scan(body, (h, x), params["layers"])
    return h.astype(jnp.float32)
