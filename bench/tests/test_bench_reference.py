"""The plain references: brute force against float64 numpy, and the
Table-1 check against graphs with known faults."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference


def circulant(n: int, d: int) -> np.ndarray:
    """An even-regular, undirected, connected graph: i ~ i +- 1..d/2."""
    offs = np.concatenate([np.arange(1, d // 2 + 1),
                           -np.arange(1, d // 2 + 1)])
    return (np.arange(n)[:, None] + offs[None, :]) % n


def test_brute_force_agrees_with_float64_numpy():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(3000, 48)).astype(np.float32)
    q = rng.normal(size=(70, 48)).astype(np.float32)
    d, ids = reference.brute_force(q, base, 10)
    full = np.sqrt(((q.astype(np.float64)[:, None, :]
                     - base.astype(np.float64)[None]) ** 2).sum(-1))
    want = np.argsort(full, axis=1)[:, :10]
    assert (ids == want).all()
    np.testing.assert_allclose(d, np.take_along_axis(full, want, 1),
                               rtol=1e-5)


def test_control_is_a_lower_precision_of_the_same_search():
    rng = np.random.default_rng(1)
    base = (5 + rng.normal(size=(2000, 64))).astype(np.float32)
    q = (5 + rng.normal(size=(40, 64))).astype(np.float32)
    d, ids = reference.brute_force(q, base, 10)
    dc, idc = reference.brute_force_control(q, base, 10)
    exact = reference.exact_distances(q, base, idc)
    assert np.abs(dc - exact).max() > np.abs(
        d - reference.exact_distances(q, base, ids)).max()
    assert (ids[:, 0] == idc[:, 0]).mean() > 0.9


def test_table1_passes_a_sound_graph():
    assert sum(reference.table1_violations(circulant(50, 6), 6).values()) \
        == 0


def test_table1_flags_one_half_edge_removed():
    adj = circulant(50, 6)
    adj[7, 2] = -1                 # 7 -> x gone, x -> 7 kept
    v = reference.table1_violations(adj, 6)
    assert v["degree"] == 1 and v["asym"] == 1


@pytest.mark.parametrize("n", [40, 41])
def test_table1_flags_a_disconnected_graph(n):
    half = n // 2
    adj = np.concatenate([circulant(half, 6),
                          circulant(n - half, 6) + half])
    v = reference.table1_violations(adj, 6)
    assert v["unreached"] == n - half
    assert v["degree"] == v["asym"] == v["self"] == v["dup"] == 0


def test_table1_flags_self_loops_and_duplicates():
    adj = circulant(30, 4)
    adj[3, 0] = 3
    adj[5, 1] = adj[5, 0]
    v = reference.table1_violations(adj, 4)
    assert v["self"] == 1 and v["dup"] == 1


def _total_weight(x, adj):
    x = x.astype(np.float64)
    return sum(np.linalg.norm(x[u] - x[v])
               for u in range(len(adj)) for v in adj[u] if v >= 0) / 2


def test_weight_change_is_the_change_of_the_total_edge_weight():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(30, 8)).astype(np.float32)
    before = circulant(30, 4)
    after = before.copy()
    # swap the edges 0-1 and 10-11 for 0-10 and 1-11, as a refinement does
    after[0, 0], after[10, 0] = 10, 0
    after[1, 2], after[11, 2] = 11, 1
    changed = np.flatnonzero((before != after).any(axis=1))
    got = reference.weight_change(x, changed, before[changed],
                                  after[changed])
    want = _total_weight(x, after) - _total_weight(x, before)
    assert abs(got - want) < 1e-9 * max(1.0, abs(want))
    assert reference.weight_change(x, changed[:0], before[:0],
                                   after[:0]) == 0.0


def test_refine_idle_counts_calls_that_did_not_lower_the_weight():
    from bench import judge

    x = np.arange(12, dtype=np.float32)[:, None] * np.ones((1, 2))
    adj = circulant(12, 2)                      # a ring: edges of length 1
    longer = adj.copy()
    longer[[0, 1, 2, 3], :] = [[2, 11], [3, 0], [0, 1], [1, 4]]
    ch = np.array([0, 1, 2, 3])
    lowered = (ch, longer[ch], adj[ch])
    raised = (ch, adj[ch], longer[ch])
    idle = (ch[:0], adj[:0], adj[:0])
    assert judge.refine_idle(x, [lowered, lowered]) == 0.0
    assert judge.refine_idle(x, [lowered, idle, raised, lowered]) == 0.5
    assert judge.refine_idle(x, []) == 1.0


@pytest.mark.parametrize("metric", reference.METRICS)
def test_admissible_rows_are_a_search_of_the_admitted_subset(monkeypatch,
                                                            metric):
    """Across block edges: small blocks, a partial last block of rows and
    of queries."""
    monkeypatch.setattr(reference, "ROW_BLOCK", 64)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    rng = np.random.default_rng(3)
    base = rng.normal(size=(1000, 24)).astype(np.float32)
    q = rng.normal(size=(70, 24)).astype(np.float32)
    tags = rng.integers(0, 3, len(base))
    qtags = rng.integers(0, 3, len(q))
    d, ids = reference.brute_force(q, base, 10,
                                   lambda r, x: tags[x] == qtags[r], metric)
    for t in range(3):
        rows, sub = np.flatnonzero(tags == t), qtags == t
        ds, si = reference.brute_force(q[sub], base[rows], 10, metric=metric)
        assert (ids[sub] == rows[si]).all()
        np.testing.assert_allclose(d[sub], ds, rtol=1e-6, atol=1e-6)


def test_a_request_that_admits_fewer_than_k_rows_is_refused():
    rng = np.random.default_rng(4)
    base = rng.normal(size=(50, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="fewer than k"):
        reference.brute_force(base[:3], base, 10,
                              lambda r, x: (x < 5) | (r > 0))


def _parent_block_topk(q, x, x_valid, admit=None, *, k, precision,
                       metric="l2"):
    """The block search as it was before restrictions and metrics."""
    return _parent_jit(q, x, x_valid, k=k, precision=precision)


def _parent(q, x, x_valid, *, k, precision):
    sq = (jnp.sum(q * q, axis=1, keepdims=True)
          - 2.0 * reference._DOTS[precision](q, x)
          + jnp.sum(x * x, axis=1)[None, :])
    sq = jnp.where(x_valid[None, :], jnp.maximum(sq, 0.0), jnp.inf)
    neg, ids = jax.lax.top_k(-sq, k)
    return jnp.sqrt(-neg), ids


_parent_jit = jax.jit(_parent, static_argnames=("k", "precision"))


@pytest.mark.parametrize("search", [reference.brute_force,
                                    reference.brute_force_control])
def test_no_restriction_is_the_unrestricted_search_bit_for_bit(monkeypatch,
                                                               search):
    monkeypatch.setattr(reference, "ROW_BLOCK", 256)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    rng = np.random.default_rng(5)
    base = (3 + rng.normal(size=(1500, 40))).astype(np.float32)
    q = (3 + rng.normal(size=(45, 40))).astype(np.float32)
    d, ids = search(q, base, 10)
    monkeypatch.setattr(reference, "_block_topk", _parent_block_topk)
    d0, ids0 = search(q, base, 10)
    assert d.dtype == d0.dtype and (d.view(np.uint32)
                                    == d0.view(np.uint32)).all()
    assert (ids == ids0).all()


@pytest.mark.parametrize("metric", reference.METRICS)
def test_each_metric_is_the_programs_distance(metric):
    """The same distances as the program's own (``core/distances.py``),
    in float64, and the brute force finds the nearest by them."""
    from repro.core.distances import get_metric

    rng = np.random.default_rng(6)
    base = rng.normal(size=(600, 32)).astype(np.float32)
    q = rng.normal(size=(20, 32)).astype(np.float32)
    full = reference.exact_distances(
        q, base, np.broadcast_to(np.arange(len(base)), (len(q), len(base))),
        metric)
    np.testing.assert_allclose(
        full, np.asarray(get_metric(metric).cross(q, base)), rtol=1e-5,
        atol=1e-5)
    d, ids = reference.brute_force(q, base, 10, metric=metric)
    assert (ids == np.argsort(full, axis=1, kind="stable")[:, :10]).all()
    np.testing.assert_allclose(d, np.take_along_axis(full, ids, 1),
                               rtol=1e-5, atol=1e-5)
