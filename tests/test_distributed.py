"""Distributed-layer tests on an 8-device debug mesh (CPU host devices).

Run in a dedicated process: conftest must NOT set the device-count flag
globally, so this module sets it in a subprocess-safe way — pytest-forked
is unavailable, so we rely on this file being imported before jax
initializes devices elsewhere.  pytest runs files in alphabetical order;
``jax.devices()`` may already be locked to 1 device, in which case these
tests self-skip.
"""
import os
import sys

import numpy as np
import pytest

# Only effective if jax is not yet initialized in this process.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

if jax.device_count() < 8:
    pytest.skip("needs 8 host devices (jax already initialized)",
                allow_module_level=True)

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from jax import set_mesh  # noqa: E402
from repro.core.build import DEGParams  # noqa: E402
from repro.distributed.collectives import (  # noqa: E402
    compressed_psum, int8_compress, int8_decompress, make_sharded_lookup,
    sharded_brute_topk)
from repro.distributed.index import build_sharded_deg  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh()


@pytest.fixture(scope="module")
def mesh3():
    return make_debug_mesh(multi_pod=True)


def test_sharded_lookup_matches_gather(mesh):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, size=(10, 5)).astype(np.int32))
    lookup = make_sharded_lookup(mesh)
    with set_mesh(mesh):
        out = jax.jit(lookup)(table, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(table)[np.asarray(ids)], rtol=1e-6)


def test_sharded_brute_topk_exact(mesh):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(6, 12)).astype(np.float32))
    db = jnp.asarray(rng.normal(size=(80, 12)).astype(np.float32))
    f = sharded_brute_topk(mesh, k=7, shard_axes=("data", "model"),
                           metric="l2")
    with set_mesh(mesh):
        vals, ids = jax.jit(f)(q, db)
    d2 = ((np.asarray(q)[:, None] - np.asarray(db)[None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :7]
    assert (np.sort(np.asarray(ids), 1) == np.sort(gt, 1)).all()


def test_int8_compression_roundtrip():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
    q, s = int8_compress(x)
    back = int8_decompress(q, s)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=0.02)


def test_compressed_psum_global_scale_agreement(mesh):
    """The pmax agreement path: shards with wildly different magnitudes
    must agree on ONE global scale before quantizing — so every device
    produces bitwise-identical output and the error is bounded by the
    *global* amax, not the per-shard ones."""
    from jax import shard_map

    n_dev = 4
    rng = np.random.default_rng(11)
    # shard 0 dominates: per-shard scales would disagree by ~1000x
    mags = np.array([1000.0, 1.0, 0.01, 1.0], np.float32)
    x = (mags[:, None] * rng.normal(size=(n_dev, 64))).astype(np.float32)

    def f(xs):
        return compressed_psum(xs, ("data", "model"))

    g = shard_map(f, mesh=mesh, in_specs=P(("data", "model"), None),
                  out_specs=P(("data", "model"), None), check_vma=False)
    with set_mesh(mesh):
        out = np.asarray(jax.jit(g)(jnp.asarray(x)))
    # agreement: all devices computed the identical dequantized sum
    assert (out == out[0][None, :]).all()
    # error bound from the GLOBAL scale (amax over all shards)
    amax = float(np.abs(x).max())
    want = x.sum(0)
    assert np.abs(out[0] - want).max() <= n_dev * amax / 127 + 1e-6
    # the small shards' contribution is quantized to the global grid, not
    # dropped: a zero-input roundtrip stays exactly zero
    with set_mesh(mesh):
        zero = np.asarray(jax.jit(g)(jnp.zeros((n_dev, 64), jnp.float32)))
    assert (zero == 0).all()


def test_compressed_grad_allreduce_tree(mesh):
    """make_compressed_grad_allreduce: tree-structured int8 mean-allreduce
    matches the exact per-leaf mean within the global-scale bound and
    preserves leaf dtypes."""
    from jax import shard_map
    from repro.distributed.collectives import make_compressed_grad_allreduce

    n_dev = 4
    rng = np.random.default_rng(12)
    grads = {
        "w": jnp.asarray(rng.normal(size=(n_dev, 8, 4)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(n_dev, 16)).astype(np.float32)),
    }
    reduce_tree = make_compressed_grad_allreduce(mesh, ("data", "model"))
    g = shard_map(
        reduce_tree, mesh=mesh,
        in_specs=({k: P(("data", "model"), *([None] * (v.ndim - 1)))
                   for k, v in grads.items()},),
        out_specs={k: P(("data", "model"), *([None] * (v.ndim - 1)))
                   for k, v in grads.items()},
        check_vma=False)
    with set_mesh(mesh):
        out = jax.jit(g)(grads)
    for k, v in grads.items():
        got = np.asarray(out[k])
        assert got.dtype == np.float32
        want = np.asarray(v).mean(0, keepdims=True)
        amax = float(np.abs(np.asarray(v)).max())
        tol = amax / 127 + 1e-6          # mean divides the n_dev factor out
        assert np.abs(got - np.broadcast_to(want, got.shape)).max() <= tol


def test_compressed_psum_approximates_sum(mesh):
    from jax import shard_map

    n_dev = 4                       # the 2x2 debug mesh
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n_dev, 32)).astype(np.float32))

    def f(xs):
        return compressed_psum(xs, ("data", "model"))

    g = shard_map(f, mesh=mesh, in_specs=P(("data", "model"), None),
                  out_specs=P(("data", "model"), None), check_vma=False)
    with set_mesh(mesh):
        out = jax.jit(g)(x)     # one row per device -> psum = column sums
    want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True),
                           (n_dev, 32))
    # int8 with a global scale: error <= n_dev * amax/127
    amax = float(np.abs(np.asarray(x)).max())
    np.testing.assert_allclose(np.asarray(out), want,
                               atol=n_dev * amax / 127 + 1e-6)


def test_sharded_deg_recall_and_shard_loss(mesh):
    rng = np.random.default_rng(4)
    vecs = rng.normal(size=(600, 16)).astype(np.float32)
    sd = build_sharded_deg(vecs, 2, DEGParams(degree=8, k_ext=16),
                           wave_size=8)
    qs = vecs[:64] + 0.01 * rng.normal(size=(64, 16)).astype(np.float32)
    ids, dists = sd.search(mesh, qs, k=5)
    d2 = ((qs[:, None] - vecs[None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :5]
    rec = np.mean([len(set(ids[i]) & set(gt[i])) / 5 for i in range(64)])
    assert rec > 0.8
    # losing a shard: service continues, only that shard's ids disappear
    ids2, _ = sd.drop_shard(0).search(mesh, qs, k=5)
    assert (np.asarray(ids2) % 2 == 1).all()
    rec2 = np.mean([len(set(ids2[i]) & set(gt[i])) / 5 for i in range(64)])
    assert 0.3 < rec2 < rec


def test_sharded_deg_quantized_two_stage(mesh):
    """SQ8 shard-local traversal + exact rerank AFTER topk_merge_allgather:
    recall holds within 1% of the float path and the returned distances are
    the exact float distances of the returned ids."""
    rng = np.random.default_rng(13)
    vecs = rng.normal(size=(600, 16)).astype(np.float32)
    sd = build_sharded_deg(vecs, 2, DEGParams(degree=8, k_ext=16),
                           wave_size=8)
    qs = vecs[:48] + 0.01 * rng.normal(size=(48, 16)).astype(np.float32)
    d2 = ((qs[:, None] - vecs[None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :5]

    ids_f, _ = sd.search(mesh, qs, k=5)
    rec_f = np.mean([len(set(ids_f[i]) & set(gt[i])) / 5 for i in range(48)])

    sq = sd.quantize("sq8")
    assert sq.memory_stats()["ratio"] >= 3.5
    ids_q, dists_q = sq.search(mesh, qs, k=5, rerank_k=20)
    rec_q = np.mean([len(set(ids_q[i]) & set(gt[i])) / 5 for i in range(48)])
    assert rec_q >= rec_f - 0.01
    # exact-rerank invariant: reported distances == float distances
    for i in range(48):
        valid = ids_q[i] >= 0
        np.testing.assert_allclose(
            dists_q[i][valid], np.sqrt(d2[i][ids_q[i][valid]]), rtol=1e-5)
    # shard loss still degrades gracefully on the quantized path
    ids_d, _ = sq.drop_shard(0).search(mesh, qs, k=5, rerank_k=20)
    assert (ids_d % 2 == 1).all()


def test_sharded_deg_pq_two_stage(mesh):
    """PQ shard-local ADC traversal + exact rerank: per-shard codebooks
    ride the shard axis into the mapped search, and the exact-rerank
    invariant (reported distances == float distances) still holds."""
    rng = np.random.default_rng(13)
    vecs = rng.normal(size=(600, 16)).astype(np.float32)
    sd = build_sharded_deg(vecs, 2, DEGParams(degree=8, k_ext=16),
                           wave_size=8)
    qs = vecs[:48] + 0.01 * rng.normal(size=(48, 16)).astype(np.float32)
    d2 = ((qs[:, None] - vecs[None]) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :5]

    ids_f, _ = sd.search(mesh, qs, k=5)
    rec_f = np.mean([len(set(ids_f[i]) & set(gt[i])) / 5 for i in range(48)])

    pq = sd.quantize("pq")
    assert pq.codebooks is not None
    assert pq.codebooks.shape[0] == 2          # one codebook per shard
    ids_q, dists_q = pq.search(mesh, qs, k=5, rerank_k=40)
    rec_q = np.mean([len(set(ids_q[i]) & set(gt[i])) / 5 for i in range(48)])
    assert rec_q >= rec_f - 0.05
    for i in range(48):
        valid = ids_q[i] >= 0
        np.testing.assert_allclose(
            dists_q[i][valid], np.sqrt(d2[i][ids_q[i][valid]]), rtol=1e-5)


def test_lm_sharded_train_step_runs(mesh):
    """End-to-end: reduced LM config, real data, production sharding rules,
    one jitted train step executed on the 2x2 debug mesh."""
    import dataclasses

    from repro.configs import get_arch
    from repro.distributed import sharding as SH
    from repro.models import transformer as T
    from repro.train.optimizer import adamw
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(get_arch("granite-3-2b").reduced(),
                              act_batch_axes=("data",))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw(1e-3)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, size=(8, 17)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    pspec = SH.lm_param_specs(cfg, mesh)
    ospec = SH.opt_state_specs(pspec, opt_state)
    bspec = SH.lm_batch_specs(mesh)
    mspec = {"loss": P(), "nll": P(), "aux": P()}
    step = make_train_step(lambda p, b: T.loss_fn(p, b, cfg), opt,
                           jit=False)

    def shard(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda x: isinstance(x, P))

    with set_mesh(mesh):
        jstep = jax.jit(step, in_shardings=(shard(pspec), shard(ospec),
                                            shard(bspec)),
                        out_shardings=((shard(pspec), shard(ospec)),
                                       shard(mspec)))
        (p2, s2), m = jstep(params, opt_state, batch)
    assert np.isfinite(float(m["loss"]))
    # params actually sharded per the rule
    leaf = p2["layers"]["wq"]
    assert leaf.sharding.spec == pspec["layers"]["wq"]


def test_multipod_mesh_axes(mesh3):
    assert mesh3.axis_names == ("pod", "data", "model")
    from repro.launch.mesh import batch_axes

    assert batch_axes(mesh3) == ("pod", "data")
