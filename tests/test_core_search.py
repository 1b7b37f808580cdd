"""Tests for the batched range search (Algorithm 1)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DEGParams, build_deg, exact_knn, medoid_seed,
                        range_search, recall_at_k)
from repro.core.graph import INVALID
from repro.data import make_dataset


@pytest.fixture(scope="module")
def small_index():
    base, queries = make_dataset("gaussian", 800, 30, 16, seed=7)
    p = DEGParams(degree=8, k_ext=16, eps_ext=0.3, k_opt=8)
    idx = build_deg(base, p, wave_size=32)
    return base, queries, idx


def test_high_recall(small_index):
    base, queries, idx = small_index
    _, ti = exact_knn(queries, base, 10)
    res = idx.search(queries, k=10, eps=0.2, beam_width=64)
    assert recall_at_k(np.asarray(res.ids), np.asarray(ti)) >= 0.9


def test_no_duplicates_in_results(small_index):
    _, queries, idx = small_index
    res = idx.search(queries, k=10, eps=0.2)
    ids = np.asarray(res.ids)
    for row in ids:
        valid = row[row != INVALID]
        assert len(set(valid.tolist())) == len(valid)


def test_results_sorted_by_distance(small_index):
    _, queries, idx = small_index
    res = idx.search(queries, k=10, eps=0.2)
    d = np.asarray(res.dists)
    assert (np.diff(d, axis=1) >= -1e-6).all()


def test_distances_are_true_metric(small_index):
    base, queries, idx = small_index
    res = idx.search(queries, k=5, eps=0.2)
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    for qi in range(5):
        for j in range(5):
            v = ids[qi, j]
            if v == INVALID:
                continue
            true = np.linalg.norm(idx.vectors[v] - np.asarray(queries[qi]))
            assert dists[qi, j] == pytest.approx(true, rel=1e-4, abs=1e-4)


def test_beam_width_monotone_recall(small_index):
    """Wider beam (the ef knob) must not reduce recall on average."""
    base, queries, idx = small_index
    _, ti = exact_knn(queries, base, 10)
    recalls = []
    for L in (12, 32, 96):
        res = idx.search(queries, k=10, eps=0.2, beam_width=L)
        recalls.append(recall_at_k(np.asarray(res.ids), np.asarray(ti)))
    assert recalls[0] <= recalls[1] + 0.05
    assert recalls[1] <= recalls[2] + 0.05
    assert recalls[-1] >= 0.9


def test_invalid_seeds_handled(small_index):
    base, queries, idx = small_index
    g = idx.frozen()
    seeds = jnp.asarray(
        np.array([[0, INVALID, 0], [INVALID, 3, 3]], dtype=np.int32))
    res = range_search(g, idx._dev_vectors, jnp.asarray(queries[:2]), seeds,
                       k=5, eps=0.2)
    assert np.asarray(res.ids).shape == (2, 5)
    assert (np.asarray(res.ids)[:, 0] != INVALID).all()


def test_exploration_excludes_seed(small_index):
    base, queries, idx = small_index
    seeds = [3, 50, 200]
    res = idx.explore(seeds, k=10)
    ids = np.asarray(res.ids)
    for row, s in zip(ids, seeds):
        assert s not in row.tolist()
    # exploration from an indexed vertex should find its true neighbors well:
    # seed == query means the approach phase is free (paper Sec. 6.7)
    _, ti = exact_knn(idx.vectors[seeds], base, 11)
    true_wo_self = np.asarray(ti)[:, 1:]
    rec = recall_at_k(ids, true_wo_self)
    assert rec >= 0.8


def test_exploration_exclude_list(small_index):
    base, queries, idx = small_index
    _, ti = exact_knn(idx.vectors[[10]], base, 6)
    banned = np.asarray(ti)[:, 1:4]      # ban 3 nearest
    res = idx.explore([10], k=5, exclude=banned)
    ids = set(np.asarray(res.ids)[0].tolist())
    for b in banned[0]:
        assert int(b) not in ids


def test_medoid_seed(small_index):
    base, _, idx = small_index
    s = medoid_seed(idx._dev_vectors, idx.n)
    assert 0 <= s < idx.n


def test_hops_and_evals_reported(small_index):
    _, queries, idx = small_index
    res = idx.search(queries, k=10, eps=0.2)
    assert (np.asarray(res.hops) > 0).all()
    assert (np.asarray(res.evals) >= np.asarray(res.hops)).all()


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 8), eps=st.floats(0.0, 0.5), b=st.integers(1, 4))
def test_search_shapes_property(small_index, k, eps, b):
    base, queries, idx = small_index
    res = idx.search(queries[:b], k=k, eps=eps)
    assert np.asarray(res.ids).shape == (b, k)
    assert np.asarray(res.dists).shape == (b, k)
    d = np.asarray(res.dists)
    assert not np.isnan(d).any()


def test_exploration_excluded_traversed_not_returned(small_index):
    """Exploration protocol (paper Sec. 6.7): excluded vertices must never
    appear in results, yet navigation still passes THROUGH them — excluding
    the seed's entire neighborhood must not wall off the rest of the graph."""
    base, _, idx = small_index
    v = 17
    ring = [int(u) for u in idx.builder.neighbors(v)]     # all d neighbors
    excl = np.asarray([[v] + ring], np.int32)
    res = idx.search_batch(base[v][None], np.asarray([[v]], np.int32), excl,
                           k=8, eps=0.2)
    ids = [int(x) for x in np.asarray(res.ids)[0] if x != INVALID]
    banned = set([v] + ring)
    assert ids, "exploration returned nothing"
    assert not (set(ids) & banned)        # never in results ...
    # ... but traversal went through the ring: every returned vertex is
    # outside the seed's immediate neighborhood, i.e. >= 2 hops away, and
    # the lane did expand vertices
    assert int(np.asarray(res.hops)[0]) >= 2
    # the results must be *good* despite the exclusion: close to the
    # exact nearest non-banned vertices
    d = np.linalg.norm(base[: idx.n] - base[v], axis=1)
    d[list(banned)] = np.inf
    best = float(np.sort(d)[0])
    assert float(np.asarray(res.dists)[0, 0]) <= best * 1.5


def test_extract_stable_on_duplicate_distances():
    """Tie-determinism (satellite): ``beam.extract`` must resolve duplicate
    distances by beam position (stable sort), exactly like
    ``search.exact_rerank`` — not by whatever a non-stable argsort does."""
    import jax.numpy as jnp

    from repro.core import beam

    ids = jnp.asarray([[5, 3, 9, 2], [8, 1, 4, 6]], jnp.int32)
    dists = jnp.asarray([[1.0, 1.0, 1.0, 2.0], [0.5, 0.5, 0.5, 0.5]],
                        jnp.float32)
    st = beam.BeamState(ids=ids, dists=dists,
                        checked=jnp.ones((2, 4), bool),
                        excluded=jnp.zeros((2, 4), bool),
                        hops=jnp.zeros((2,), jnp.int32),
                        evals=jnp.zeros((2,), jnp.int32))
    out_ids, out_d = beam.extract(st, 3)
    np.testing.assert_array_equal(np.asarray(out_ids),
                                  [[5, 3, 9], [8, 1, 4]])
    # and it agrees with exact_rerank's stable tie order on the same data
    from repro.core.search import exact_rerank

    vecs = jnp.zeros((10, 4), jnp.float32)        # all-equal -> all ties
    r_ids, _ = exact_rerank(vecs, jnp.zeros((2, 4)), ids, k=3)
    np.testing.assert_array_equal(np.asarray(out_ids), np.asarray(r_ids))


def test_extract_dedup_keeps_first_occurrence():
    import jax.numpy as jnp

    from repro.core import beam

    st = beam.BeamState(
        ids=jnp.asarray([[7, 7, 3, 7]], jnp.int32),
        dists=jnp.asarray([[1.0, 1.0, 2.0, 3.0]], jnp.float32),
        checked=jnp.ones((1, 4), bool), excluded=jnp.zeros((1, 4), bool),
        hops=jnp.zeros((1,), jnp.int32), evals=jnp.zeros((1,), jnp.int32))
    out_ids, out_d = beam.extract(st, 3, dedup=True)
    np.testing.assert_array_equal(np.asarray(out_ids), [[7, 3, INVALID]])
    assert np.isinf(np.asarray(out_d)[0, 2])


def test_search_graph_full_plumbing(small_index):
    """search_graph forwards the complete range_search signature
    (satellite): exclude, merge_backend, rerank_k/exact_vectors, engine
    knobs — none silently dropped."""
    import jax.numpy as jnp

    from repro.core.search import search_graph

    base, queries, idx = small_index
    g = idx.frozen()
    vecs = idx._dev_vectors
    qs = jnp.asarray(queries[:6])

    # exclude: banned vertices never in results
    banned = np.asarray(idx.search(queries[:6], k=3, eps=0.2).ids)
    res = search_graph(g, vecs, qs, k=5, eps=0.2,
                       exclude=jnp.asarray(banned))
    for row, b in zip(np.asarray(res.ids), banned):
        assert not (set(row.tolist()) & set(b.tolist()))

    # merge_backend (argsort = seed semantics) must be honored and agree
    res_a = search_graph(g, vecs, qs, k=5, eps=0.2,
                         merge_backend="argsort")
    res_j = search_graph(g, vecs, qs, k=5, eps=0.2)
    np.testing.assert_array_equal(np.asarray(res_a.ids),
                                  np.asarray(res_j.ids))

    # rerank_k + exact_vectors: two-stage over the sq8 store returns
    # exact float distances
    store = idx.store_for("sq8")
    res_q = search_graph(g, store, qs, k=5, eps=0.2, seed=idx.medoid(),
                         rerank_k=20, exact_vectors=vecs)
    ids = np.asarray(res_q.ids)
    d = np.asarray(res_q.dists)
    for qi in range(ids.shape[0]):
        for j in range(ids.shape[1]):
            if ids[qi, j] == INVALID:
                continue
            true = np.linalg.norm(idx.vectors[ids[qi, j]]
                                  - np.asarray(queries[qi]))
            assert d[qi, j] == pytest.approx(true, rel=1e-4, abs=1e-4)

    # engine knobs reach the beam engine (E>1 runs and matches range_search)
    from repro.core import range_search as _rs

    res_e = search_graph(g, vecs, qs, k=5, eps=0.2, seed=0, expand_width=2)
    seeds = jnp.zeros((6, 1), jnp.int32)
    ref = _rs(g, vecs, qs, seeds, k=5, eps=0.2, expand_width=2)
    np.testing.assert_array_equal(np.asarray(res_e.ids),
                                  np.asarray(ref.ids))


def test_medoid_seed_cached_and_invalidated(small_index):
    """DEGIndex caches the medoid entry vertex and recomputes only after
    the vector set changes (satellite: no device reduction per query)."""
    base, _, idx = small_index
    m0 = idx.medoid()
    assert idx._medoid is not None
    assert idx.medoid() == m0            # cache hit, same value
    # mutation invalidates
    rng = np.random.default_rng(0)
    idx.add(rng.normal(size=(4, base.shape[1])).astype(np.float32),
            wave_size=4)
    assert idx._medoid is None
    m1 = idx.medoid()
    assert 0 <= m1 < idx.n


def _table_case(path, tmp_path):
    """An index after one of the paths that make or replace its device
    table, with a metrics registry attached before that path ran; returns
    (index, the searchable view, its table)."""
    from repro.core import DEGIndex
    from repro.obs.metrics import MetricsRegistry

    base, _ = make_dataset("gaussian", 300, 1, 16, seed=11)
    p = DEGParams(degree=8, k_ext=16, eps_ext=0.3, k_opt=8)
    idx = DEGIndex(16, p, capacity=512)
    idx.metrics = MetricsRegistry()
    idx.add(base[:240], wave_size=16)
    if path == "grow":
        idx.grow(1024)
    elif path == "publish":
        idx.enable_publishing()
        idx.add(base[240:], wave_size=16)
        idx.publish()
        view = idx.acquire_view()
        idx.release_view(view)
        return idx, view, view.vectors
    elif path == "load":
        idx.save(tmp_path / "idx.npz")
        idx = DEGIndex.load(tmp_path / "idx.npz")
        idx.metrics = MetricsRegistry()
        idx.add(base[240:], wave_size=16)
    return idx, idx, idx._dev_vectors


@pytest.mark.parametrize("path", ["add", "grow", "publish", "load"])
def test_vector_table_keeps_search_layout(path, tmp_path):
    """Every path that makes or replaces the device table leaves it in
    the row-major search format, without a relayout, and the searches
    over it answer as over a plain device copy of the same rows."""
    from repro.core.search import TABLE_LAYOUT
    from repro.obs.metrics import VECTOR_TABLE_RELAYOUTS_TOTAL

    idx, view, table = _table_case(path, tmp_path)
    assert table.format.layout.major_to_minor == TABLE_LAYOUT.major_to_minor
    assert idx.metrics.counter(VECTOR_TABLE_RELAYOUTS_TOTAL).value == 0
    rng = np.random.default_rng(5)
    q = rng.normal(size=(6, 16)).astype(np.float32)
    seeds = rng.integers(0, view.n, size=(6, 2)).astype(np.int32)
    got = view.search_batch(q, seeds, k=10, eps=0.2)
    graph = view.graph if path == "publish" else idx.frozen()
    want = range_search(graph, jnp.asarray(np.asarray(table)),
                        jnp.asarray(q), jnp.asarray(seeds), k=10, eps=0.2)
    for a, b in ((got.ids, want.ids), (got.dists, want.dists),
                 (got.hops, want.hops), (got.evals, want.evals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropped_table_layout_is_replaced_and_counted():
    """A table that lost the search layout is re-placed at the next row
    write, keeps its rows, and is counted once."""
    import jax
    from jax.experimental.layout import Format, Layout

    from repro.core import DEGIndex
    from repro.core.search import TABLE_LAYOUT
    from repro.obs.metrics import (MetricsRegistry,
                                   VECTOR_TABLE_RELAYOUTS_TOTAL)

    base, _ = make_dataset("gaussian", 120, 1, 16, seed=12)
    idx = DEGIndex(16, DEGParams(degree=8, k_ext=16), capacity=256)
    idx.metrics = MetricsRegistry()
    idx.add(base[:100], wave_size=16)
    t = idx._dev_vectors
    idx._dev_vectors = jax.device_put(
        t, Format(Layout(major_to_minor=(1, 0)), t.sharding))
    idx.add(base[100:], wave_size=16)
    assert (idx._dev_vectors.format.layout.major_to_minor
            == TABLE_LAYOUT.major_to_minor)
    assert idx.metrics.counter(VECTOR_TABLE_RELAYOUTS_TOTAL).value == 1
    np.testing.assert_array_equal(np.asarray(idx._dev_vectors), idx.vectors)


_CACHED_TABLE_CHILD = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from jax.experimental.layout import Layout
import repro.core.search as search
search.TABLE_LAYOUT = Layout(major_to_minor=(1, 0))
from repro.core import DEGIndex, DEGParams, range_search
from repro.obs.metrics import MetricsRegistry, VECTOR_TABLE_RELAYOUTS_TOTAL
rng = np.random.default_rng(0)
x = rng.normal(size=(120, 16)).astype(np.float32)
idx = DEGIndex(16, DEGParams(degree=8, k_ext=16), capacity=64)
idx.metrics = MetricsRegistry()
idx.add(x[:90], wave_size=16)
idx.enable_publishing()
idx.add(x[90:], wave_size=16)
idx.publish()
view = idx.acquire_view()
q = rng.normal(size=(4, 16)).astype(np.float32)
s = np.zeros((4, 1), np.int32)
got = idx.search_batch(q, s, k=5, eps=0.2)
want = range_search(idx.frozen(), jnp.asarray(x), jnp.asarray(q),
                    jnp.asarray(s), k=5, eps=0.2)
print(json.dumps({
    "layout": list(idx._dev_vectors.format.layout.major_to_minor),
    "relayouts": idx.metrics.counter(VECTOR_TABLE_RELAYOUTS_TOTAL).value,
    "rows": bool(np.array_equal(np.asarray(idx._dev_vectors)[:120], x)),
    "epoch_rows": bool(np.array_equal(np.asarray(view.vectors)[:120], x)),
    "answers": bool(np.array_equal(np.asarray(got.ids), np.asarray(want.ids))
                    and np.array_equal(np.asarray(got.dists),
                                       np.asarray(want.dists)))}))
"""


def test_vector_table_survives_persistent_compile_cache(tmp_path):
    """An executable read back from jax's persistent compilation cache
    marks its outputs with the default layout, whatever layout it writes
    them in (jax 0.9).  Two processes share one cache, with the table
    kept column-major (not the CPU's default) so that the defect would
    show: the second reads the search programs back, and must still hold
    the rows and answer as over a plain table."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    cache = tmp_path / "jax_cache"
    cache.mkdir()
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHED_TABLE_CHILD, str(cache)],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert any(cache.iterdir())
    for run in runs:
        assert run == {"layout": [1, 0], "relayouts": 0.0, "rows": True,
                       "epoch_rows": True, "answers": True}
