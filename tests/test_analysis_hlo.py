"""HLO cost-parser tests: a tiny jitted program with a known scan structure,
plus synthetic-text unit checks for the trip/slice accounting."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import HloCost, analyze_text, shape_bytes
from repro.analysis.roofline import Roofline, from_costs


def test_shape_bytes():
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[10]{0}") == 20
    assert shape_bytes("(s32[], f32[2,2]{1,0})") == 4 + 16
    assert shape_bytes("pred[7]{0}") == 7


def test_scan_trip_scaling():
    """FLOPs of a scanned matmul must be counted trip times."""
    L, N = 8, 64
    w = jnp.ones((L, N, N), jnp.float32)
    x0 = jnp.ones((N, N), jnp.float32)

    def f(w, x):
        def body(c, wi):
            return wi @ c, None

        y, _ = jax.lax.scan(body, x, w)
        return y

    text = jax.jit(f).lower(w, x0).compile().as_text()
    cost = analyze_text(text)
    want = L * 2 * N ** 3
    assert 0.8 * want <= cost["flops"] <= 1.5 * want
    assert any(d["trip"] == L for d in cost["while_detail"])


def test_unrolled_matmul_flops():
    N = 32
    a = jnp.ones((N, N), jnp.float32)
    text = jax.jit(lambda a: a @ a).lower(a).compile().as_text()
    cost = analyze_text(text)
    assert 0.9 * 2 * N ** 3 <= cost["flops"] <= 1.2 * 2 * N ** 3


def test_trip_override():
    N = 16

    def f(x):
        def cond(c):
            return jnp.sum(c[1]) > 0          # data-dependent

        def body(c):
            i, x = c
            return (i + 1, x @ x)

        return jax.lax.while_loop(cond, body, (0, x))

    text = jax.jit(f).lower(jnp.ones((N, N))).compile().as_text()
    hc = HloCost(text)
    rep = hc.entry_cost()
    bodies = [w["body"] for w in rep.while_detail]
    assert bodies
    hc2 = HloCost(text, trip_overrides={bodies[0]: 50})
    rep2 = hc2.entry_cost()
    assert rep2.flops >= 40 * max(rep.flops / max(rep.while_detail[0]["trip"], 1), 1)


def test_roofline_terms():
    r = from_costs(flops=197e12, hbm_bytes=819e9, collective_bytes=0.0,
                   model_flops=197e12, devices=1)
    assert abs(r.t_comp - 1.0) < 1e-9
    assert abs(r.t_mem - 1.0) < 1e-9
    assert r.bottleneck in ("compute", "memory")
    assert abs(r.useful_ratio - 1.0) < 1e-9


def test_collective_bytes_counted():
    """psum inside shard_map must show up as all-reduce bytes."""
    import os

    if jax.device_count() < 2:
        import pytest

        pytest.skip("needs >=2 devices")
    from jax.sharding import PartitionSpec as P

    from jax import set_mesh, shard_map

    mesh = jax.make_mesh((jax.device_count(),), ("x",))
    x = jnp.ones((jax.device_count() * 4, 8), jnp.float32)

    def f(xs):
        return jax.lax.psum(xs, "x")

    g = shard_map(f, mesh=mesh, in_specs=P("x", None),
                  out_specs=P("x", None), check_vma=False)
    with set_mesh(mesh):
        text = jax.jit(g).lower(x).compile().as_text()
    cost = analyze_text(text)
    assert cost["collective_bytes"] > 0
    assert "all-reduce" in cost["per_collective"]
