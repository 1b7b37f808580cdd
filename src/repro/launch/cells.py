"""Cell programs: one lowerable (step fn, abstract inputs, shardings) per
(architecture x input-shape x mesh) dry-run cell.

``build_cell(arch, shape, mesh)`` returns a :class:`CellProgram` whose
``lower()`` produces ``jax.stages.Lowered`` for the production mesh —
*every* array input is a ``jax.ShapeDtypeStruct`` (no allocation), which is
what lets the 91 GB DLRM table or the 141 B-param Mixtral lower on a CPU
container.

Shape policy: dims that must divide the mesh are padded here exactly the way
the data pipeline pads them at runtime (edge lists to the device count with
an ``edge_valid`` mask, node counts to the DP axes, recsys tables to the
"model" axis).  Padding constants are part of the cell metadata so the
roofline analysis can discount them.

Beyond the 40 assigned cells, the ``deg-ann`` pseudo-architecture lowers the
paper's own technique at production scale: the sharded-DEG search step
(distributed/index.py) over a 16.7M-vector index.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.distributed import sharding as SH
from repro.distributed.collectives import make_sharded_lookup, sharded_brute_topk
from repro.launch.mesh import batch_axes as mesh_batch_axes

Array = jax.Array


def sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _pad_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@dataclasses.dataclass
class CellProgram:
    arch: str
    shape: str
    kind: str
    fn: Callable
    args: tuple                   # abstract arg pytrees (ShapeDtypeStructs)
    in_specs: tuple               # PartitionSpec pytrees (or None = auto)
    out_specs: Any                # PartitionSpec pytree or None = auto
    donate: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)

    def jitted(self, mesh: Mesh):
        def shard(tree):
            if tree is None:
                return None
            return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))

        return jax.jit(
            self.fn,
            in_shardings=tuple(shard(s) for s in self.in_specs),
            out_shardings=shard(self.out_specs),
            donate_argnums=self.donate)

    def lower(self, mesh: Mesh):
        from jax import set_mesh

        with set_mesh(mesh):
            return self.jitted(mesh).lower(*self.args)


# ===========================================================================
# LM family
# ===========================================================================
def _lm_cfg(spec, mesh: Mesh, seq_shard: bool = False):
    """Full config adapted to the mesh: activation-batch constraints (and
    optionally sequence parallelism), MoE dispatch groups = DP shards."""
    cfg = spec.model
    dp = mesh_batch_axes(mesh)
    cfg = dataclasses.replace(
        cfg, act_batch_axes=dp,
        act_seq_axis="model" if seq_shard else None)
    if cfg.moe is not None:
        g = int(np.prod([mesh.shape[a] for a in dp]))
        cfg = dataclasses.replace(
            cfg, moe_groups=g, moe_shard_axes=dp,
            moe=dataclasses.replace(cfg.moe, shard_hidden=True))
    return cfg


def _lm_train(spec, cell, mesh: Mesh, *, seq_shard=False,
              microbatches=1) -> CellProgram:
    from repro.models import transformer as T
    from repro.train.optimizer import adamw
    from repro.train.steps import make_train_step

    cfg = _lm_cfg(spec, mesh, seq_shard=seq_shard)
    B, S = cell["global_batch"], cell["seq_len"]
    params = T.abstract_params(cfg)
    opt = adamw(1e-4, weight_decay=0.1)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {"tokens": sds(B, S, dtype=jnp.int32),
             "labels": sds(B, S, dtype=jnp.int32)}
    step = make_train_step(lambda p, b: T.loss_fn(p, b, cfg), opt, jit=False,
                           microbatches=microbatches)
    pspec = SH.lm_param_specs(cfg, mesh)
    ospec = SH.opt_state_specs(pspec, opt_state)
    bspec = SH.lm_batch_specs(mesh)
    mspec = {"loss": P(), "nll": P(), "aux": P()}
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=step,
        args=(params, opt_state, batch),
        in_specs=(pspec, ospec, bspec),
        out_specs=((pspec, ospec), mspec),
        donate=(0, 1),
        meta={"family": "lm", "tokens": B * S, "cfg": cfg})


def _lm_prefill(spec, cell, mesh: Mesh, *, seq_shard=False) -> CellProgram:
    from repro.models import transformer as T

    cfg = _lm_cfg(spec, mesh, seq_shard=seq_shard)
    B, S = cell["global_batch"], cell["seq_len"]
    params = T.abstract_params(cfg)
    tokens = sds(B, S, dtype=jnp.int32)
    fn = functools.partial(_prefill_fn, cfg=cfg, max_len=S)
    pspec = SH.lm_param_specs(cfg, mesh)
    bspec = P(SH.dp_axes(mesh), None)
    cspec = SH.lm_cache_specs(cfg, mesh, B)
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=fn,
        args=(params, tokens),
        in_specs=(pspec, bspec),
        out_specs=(P(SH.dp_axes(mesh), None), cspec),
        meta={"family": "lm", "tokens": B * S, "cfg": cfg})


def _prefill_fn(params, tokens, *, cfg, max_len):
    from repro.models import transformer as T

    return T.serve_prefill(params, tokens, cfg, max_len=max_len)


def _lm_decode(spec, cell, mesh: Mesh, *, seq_shard=False) -> CellProgram:
    from repro.models import transformer as T

    cfg = _lm_cfg(spec, mesh)
    B, S = cell["global_batch"], cell["seq_len"]
    dp = mesh_batch_axes(mesh)
    dp_n = int(np.prod([mesh.shape[a] for a in dp]))
    if B % dp_n != 0:               # long_500k: batch 1 is unshardable
        cfg = dataclasses.replace(cfg, act_batch_axes=None)
    if cfg.moe is not None and B % cfg.moe_groups != 0:
        cfg = dataclasses.replace(cfg, moe_groups=1, moe_shard_axes=None)
    params = T.abstract_params(cfg)
    cache = T.abstract_cache(cfg, B, S)
    token = sds(B, 1, dtype=jnp.int32)
    fn = functools.partial(_decode_fn, cfg=cfg)
    pspec = SH.lm_param_specs(cfg, mesh)
    cspec = SH.lm_cache_specs(cfg, mesh, B)
    bspec = P(SH._maybe(B, mesh, SH.dp_axes(mesh)), None)
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=fn,
        args=(params, cache, token),
        in_specs=(pspec, cspec, bspec),
        out_specs=(P(SH._maybe(B, mesh, SH.dp_axes(mesh)), None), cspec),
        donate=(1,),
        meta={"family": "lm", "tokens": B, "context": S, "cfg": cfg})


def _decode_fn(params, cache, token, *, cfg):
    from repro.models import transformer as T

    return T.serve_decode_step(params, cache, token, cfg)


# ===========================================================================
# EGNN family
# ===========================================================================
def _egnn_train_full(spec, cell, mesh: Mesh, *, gnn_bf16=False,
                     gnn_node_all_axes=False,
                     gnn_halo=False) -> CellProgram:
    from repro.models import egnn as E
    from repro.train.optimizer import adamw
    from repro.train.steps import make_train_step

    cfg = spec.model_for(cell.name)
    if gnn_bf16:
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    node_axes_cfg = tuple(mesh.axis_names) if gnn_node_all_axes else None
    if node_axes_cfg is not None:
        cfg = dataclasses.replace(cfg, node_shard_axes=node_axes_cfg)
    dev = int(np.prod(mesh.devices.shape))
    dp = SH.dp_axes(mesh)
    dp_n = int(np.prod([mesh.shape[a] for a in
                        ((dp,) if isinstance(dp, str) else dp)]))
    if cell.kind == "minibatch":
        from repro.data.graphs import subgraph_shapes

        n_nodes, n_edges = subgraph_shapes(cell["batch_nodes"],
                                           cell["fanouts"])
    else:
        n_nodes, n_edges = cell["n_nodes"], cell["n_edges"]
    n_pad = _pad_up(n_nodes, dev if gnn_node_all_axes else dp_n)
    e_pad = _pad_up(n_edges, dev)
    params = E.abstract_params(cfg)
    opt = adamw(1e-3)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {
        "feats": sds(n_pad, cfg.d_feat),
        "coords": sds(n_pad, 3),
        "edges": sds(2, e_pad, dtype=jnp.int32),
        "edge_valid": sds(e_pad, dtype=jnp.bool_),
        "labels": sds(n_pad, dtype=jnp.int32),
    }
    if gnn_halo:
        loss = E.make_sharded_loss(cfg, mesh, tuple(mesh.axis_names))
    else:
        loss = lambda p, b: E.loss_fn(p, b, cfg)
    step = make_train_step(loss, opt, jit=False)
    pspec = jax.tree.map(lambda _: P(), params)
    ospec = SH.opt_state_specs(pspec, opt_state)
    edge_ax = tuple(mesh.axis_names)
    node_ax = edge_ax if gnn_node_all_axes else dp
    bspec = {
        "feats": P(node_ax, None), "coords": P(node_ax, None),
        "edges": P(None, edge_ax), "edge_valid": P(edge_ax),
        "labels": P(node_ax),
    }
    mspec = {"loss": P(), "nll": P()}
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=step,
        args=(params, opt_state, batch),
        in_specs=(pspec, ospec, bspec),
        out_specs=((pspec, ospec), mspec),
        donate=(0, 1),
        meta={"family": "gnn", "cfg": cfg, "n_nodes": n_nodes,
              "n_edges": n_edges, "n_nodes_pad": n_pad, "n_edges_pad": e_pad})


def _egnn_train_molecule(spec, cell, mesh: Mesh) -> CellProgram:
    from repro.models import egnn as E
    from repro.train.optimizer import adamw
    from repro.train.steps import make_train_step

    cfg = spec.model_for(cell.name)
    B, n, e = cell["batch"], cell["n_nodes"], cell["n_edges"]
    params = E.abstract_params(cfg)
    opt = adamw(1e-3)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {
        "feats": sds(B, n, cfg.d_feat),
        "coords": sds(B, n, 3),
        "edges": sds(B, 2, e, dtype=jnp.int32),
        "edge_valid": sds(B, e, dtype=jnp.bool_),
        "labels": sds(B, dtype=jnp.int32),
    }
    step = make_train_step(lambda p, b: E.loss_fn(p, b, cfg), opt, jit=False)
    pspec = jax.tree.map(lambda _: P(), params)
    ospec = SH.opt_state_specs(pspec, opt_state)
    dp = SH.dp_axes(mesh)
    bspec = {"feats": P(dp, None, None), "coords": P(dp, None, None),
             "edges": P(dp, None, None), "edge_valid": P(dp, None),
             "labels": P(dp)}
    mspec = {"loss": P(), "nll": P()}
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=step,
        args=(params, opt_state, batch),
        in_specs=(pspec, ospec, bspec),
        out_specs=((pspec, ospec), mspec),
        donate=(0, 1),
        meta={"family": "gnn", "cfg": cfg, "batch": B})


# ===========================================================================
# RecSys family
# ===========================================================================
def _recsys_cfg(spec, mesh: Mesh):
    import dataclasses as dc

    return dc.replace(spec.model, table_pad_to=int(mesh.shape["model"]))


def _recsys_batch_abs(cfg, B: int) -> dict:
    b = {"sparse": sds(B, cfg.n_sparse, dtype=jnp.int32),
         "label": sds(B)}
    if cfg.n_dense:
        b["dense"] = sds(B, cfg.n_dense)
    if cfg.kind == "din":
        b["hist"] = sds(B, cfg.seq_len, dtype=jnp.int32)
    return b


def _recsys_train(spec, cell, mesh: Mesh) -> CellProgram:
    from repro.models import recsys as R
    from repro.train.optimizer import adamw, partitioned, sgd
    from repro.train.steps import make_train_step

    cfg = _recsys_cfg(spec, mesh)
    B = cell["batch"]
    params = R.abstract_params(cfg)
    # MLPerf DLRM optimizer split: stateless SGD on the embedding tables
    # (no moments for 100M+ rows), AdamW on the dense towers.
    label = lambda path, leaf: (
        "embed" if path and getattr(path[0], "key", None) in ("table", "fm_w")
        else "dense")
    opt = partitioned(label, {"embed": sgd(0.05), "dense": adamw(1e-3)})
    opt_state = jax.eval_shape(opt.init, params)
    batch = _recsys_batch_abs(cfg, B)
    dp = SH.dp_axes(mesh)
    lookup = make_sharded_lookup(mesh, table_axis="model", batch_axes=dp)
    step = make_train_step(
        lambda p, b: R.loss_fn(p, b, cfg, lookup_fn=lookup), opt, jit=False)
    pspec = SH.recsys_param_specs(cfg, mesh)
    ospec = SH.opt_state_specs(pspec, opt_state)
    bspec = SH.recsys_batch_specs(cfg, mesh, B)
    mspec = {"loss": P(), "bce": P()}
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=step,
        args=(params, opt_state, batch),
        in_specs=(pspec, ospec, bspec),
        out_specs=((pspec, ospec), mspec),
        donate=(0, 1),
        meta={"family": "recsys", "cfg": cfg, "batch": B})


def _recsys_serve(spec, cell, mesh: Mesh) -> CellProgram:
    from repro.models import recsys as R

    cfg = _recsys_cfg(spec, mesh)
    B = cell["batch"]
    params = R.abstract_params(cfg)
    batch = _recsys_batch_abs(cfg, B)
    del batch["label"]
    dp = SH.dp_axes(mesh)
    lookup = make_sharded_lookup(mesh, table_axis="model", batch_axes=dp)
    fn = functools.partial(_recsys_fwd, cfg=cfg, lookup=lookup)
    pspec = SH.recsys_param_specs(cfg, mesh)
    bspec = SH.recsys_batch_specs(cfg, mesh, B)
    del bspec["label"]
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=fn,
        args=(params, batch),
        in_specs=(pspec, bspec),
        out_specs=P(dp),
        meta={"family": "recsys", "cfg": cfg, "batch": B})


def _recsys_fwd(params, batch, *, cfg, lookup):
    from repro.models import recsys as R

    return R.forward(params, batch, cfg, lookup_fn=lookup)


def _recsys_retrieval(spec, cell, mesh: Mesh) -> CellProgram:
    from repro.models import recsys as R

    cfg = _recsys_cfg(spec, mesh)
    B, N = cell["batch"], cell["n_candidates"]
    dp = SH.dp_axes(mesh)
    dp_t = (dp,) if isinstance(dp, str) else dp
    dp_n = int(np.prod([mesh.shape[a] for a in dp_t]))
    N_pad = _pad_up(N, dp_n)
    params = R.abstract_params(cfg)
    batch = _recsys_batch_abs(cfg, B)
    del batch["label"]
    cands = sds(N_pad, cfg.embed_dim)
    lookup = make_sharded_lookup(mesh, table_axis="model", batch_axes=None)
    scorer = sharded_brute_topk(mesh, k=100, shard_axes=dp_t,
                                batch_axes=None, metric="ip")
    fn = functools.partial(_retrieval_fn, cfg=cfg, lookup=lookup,
                           scorer=scorer)
    pspec = SH.recsys_param_specs(cfg, mesh)
    bspec = SH.recsys_batch_specs(cfg, mesh, B)
    del bspec["label"]
    bspec = jax.tree.map(lambda s: P(*([None] * len(s))), bspec,
                         is_leaf=lambda x: isinstance(x, P))
    return CellProgram(
        arch=spec.name, shape=cell.name, kind=cell.kind, fn=fn,
        args=(params, batch, cands),
        in_specs=(pspec, bspec, P(dp, None)),
        out_specs=(P(None, None), P(None, None)),
        meta={"family": "recsys", "cfg": cfg, "batch": B,
              "n_candidates": N, "n_candidates_pad": N_pad})


def _retrieval_fn(params, batch, candidates, *, cfg, lookup, scorer):
    from repro.models import recsys as R

    u = R.user_embedding(params, batch, cfg, lookup_fn=lookup)
    return scorer(u, candidates)


# ===========================================================================
# DEG (the paper's technique at production scale — extra cells)
# ===========================================================================
DEG_CELLS = {
    # 16.7M vectors (2^24), dim 128, degree 30, sharded over "model".
    # est_hops: expected search length at 1M vectors/shard, from the
    # benchmarks.scalability log-fit (see EXPERIMENTS.md §Roofline) — the
    # compiled loop bound is max_hops (a worst case), so the roofline
    # rescales the search while-loop with this measured estimate.
    "search_16m": dict(n_total=1 << 24, dim=128, degree=30, batch=4096,
                       k=10, beam=64, kind="deg_search", est_hops=48),
    "explore_16m": dict(n_total=1 << 24, dim=128, degree=30, batch=4096,
                        k=100, beam=128, kind="deg_explore", exclude=16,
                        est_hops=130),
    "build_wave_16m": dict(n_total=1 << 24, dim=128, degree=30, batch=4096,
                           k=60, beam=90, kind="deg_search", est_hops=90),
}


def _deg_cell(shape_name: str, mesh: Mesh, *,
              deg_bf16=False) -> CellProgram:
    from repro.distributed.index import make_sharded_search

    c = DEG_CELLS[shape_name]
    S = int(mesh.shape["model"])
    Ns = c["n_total"] // S
    dp = SH.dp_axes(mesh)
    excl = c.get("exclude", 0)
    fn = make_sharded_search(mesh, k=c["k"], eps=0.1, beam_width=c["beam"],
                             batch_axes=dp, exclude_width=excl)
    vdt = jnp.bfloat16 if deg_bf16 else jnp.float32
    args = [
        sds(S, Ns, c["degree"], dtype=jnp.int32),     # adjacency
        sds(S, Ns, c["dim"], dtype=vdt),              # vectors
        sds(S, dtype=jnp.int32),                      # n
        sds(S, dtype=jnp.int32),                      # seeds
        sds(c["batch"], c["dim"], dtype=vdt),         # queries
    ]
    in_specs = [P("model", None, None), P("model", None, None), P("model"),
                P("model"), P(dp, None)]
    if excl:
        args.append(sds(c["batch"], excl, dtype=jnp.int32))
        in_specs.append(P(dp, None))
    return CellProgram(
        arch="deg-ann", shape=shape_name, kind=c["kind"], fn=fn,
        args=tuple(args), in_specs=tuple(in_specs),
        out_specs=(P(dp, None), P(dp, None)),
        meta={"family": "deg", **c, "n_shards": S, "n_per_shard": Ns})


# ===========================================================================
# dispatch + perf-iteration variants (EXPERIMENTS.md §Perf)
# ===========================================================================
# Each variant is a named, orthogonal change applied on top of the
# paper-faithful/baseline cell; the dry-run re-lowers and the roofline diff
# is the measurement.
VARIANTS = {
    "": {},
    # LM: sequence parallelism — layer-boundary activations sharded over
    # ("model",) on the seq dim; GSPMD turns per-layer TP all-reduces into
    # reduce-scatter/all-gather pairs and shards the norms + saved
    # activations.
    "seqpar": {"seq_shard": True},
    # EGNN: bf16 features/messages (halves HBM + collective payloads).
    "bf16msgs": {"gnn_bf16": True},
    # EGNN: shard node arrays over every mesh axis (256-way) instead of the
    # DP axes only — node-MLP compute and the aggregate all-reduce shrink.
    "nodeshard": {"gnn_node_all_axes": True},
    # EGNN: both.
    "bf16msgs+nodeshard": {"gnn_bf16": True, "gnn_node_all_axes": True},
    # EGNN: dst-partitioned edges + shard_map (local scatters, one halo
    # all-gather per layer; see models.egnn.make_sharded_loss).
    "halo": {"gnn_bf16": True, "gnn_node_all_axes": True, "gnn_halo": True},
    # DEG: bf16 vector payload (halves the gather traffic that dominates).
    "bf16vecs": {"deg_bf16": True},
    # LM train: gradient accumulation over 4 microbatches (live-activation
    # memory /4; XLA overlaps each microbatch's backward with the previous
    # one's gradient collectives on real hardware — straggler hiding).
    "microbatch4": {"microbatches": 4},
    "seqpar+microbatch4": {"seq_shard": True, "microbatches": 4},
    # DEG: bf16 + wider per-hop fanout batching (beam merge via top_k).
    "bf16vecs+topk": {"deg_bf16": True},
}


def build_cell(arch: str, shape: str, mesh: Mesh,
               variant: str = "") -> CellProgram:
    opts = VARIANTS[variant]
    if arch == "deg-ann":
        return _deg_cell(shape, mesh, **opts)
    spec = get_arch(arch)
    cell = spec.cell(shape)
    if shape in spec.skip:
        raise SkippedCell(spec.skip[shape])
    if spec.family == "lm":
        if cell.kind == "train":
            return _lm_train(spec, cell, mesh, **opts)
        if cell.kind == "prefill":
            return _lm_prefill(spec, cell, mesh, **opts)
        if cell.kind in ("decode", "long_decode"):
            return _lm_decode(spec, cell, mesh, **opts)
    if spec.family == "gnn":
        if cell.kind == "molecule":
            return _egnn_train_molecule(spec, cell, mesh)
        return _egnn_train_full(spec, cell, mesh, **opts)
    if spec.family == "recsys":
        if cell.kind == "recsys_train":
            return _recsys_train(spec, cell, mesh)
        if cell.kind == "recsys_serve":
            return _recsys_serve(spec, cell, mesh)
        if cell.kind == "retrieval":
            return _recsys_retrieval(spec, cell, mesh)
    raise ValueError(f"no cell builder for {arch}/{shape} ({cell.kind})")


class SkippedCell(Exception):
    """Raised for assigned cells documented as inapplicable (spec.skip)."""
