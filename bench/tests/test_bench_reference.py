"""The plain references: brute force against float64 numpy, and the
Table-1 check against graphs with known faults."""
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
