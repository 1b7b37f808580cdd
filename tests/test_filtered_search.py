"""Filtered search (core/tags.py): rows carry tags, a request ANDs one or
two of them, and only rows carrying every tag of its filter may answer.

Seeded data (2,000 rows of a 4-d manifold in 16-d, at most 8 tags a row)
against the plain reference, ``brute_force.filtered_knn``: the beam with
a filter operand, the scan route, the filter-free program left as it
was, ``AsyncQueryEngine.submit(filter=...)`` end to end, tags added and
deleted with their rows, and the engine's filter counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines.brute_force import filtered_knn
from repro.core.build import DEGParams, build_deg
from repro.core.graph import INVALID
from repro.core.search import range_search
from repro.core.tags import TagIndex, normalize_filter, tag_rows
from repro.data.synthetic import planted_manifold
from repro.resilience.errors import RequestValidationError
from repro.serving import buckets
from repro.serving.async_engine import AsyncQueryEngine

N, DIM, SLOTS, K = 2000, 16, 8, 10
L = 64
PARAMS = DEGParams(degree=10, k_ext=20, eps_ext=0.2)
#: each tag's share of the rows (tags 0-8); a row carries each on its own
SHARE = [0.5, 0.4, 0.3, 0.08, 0.06, 0.04, 0.009, 0.007, 0.016]
#: selectivity bands (admitted rows / N) and filters in each
BANDS = {
    "10-60%": [(0,), (1,), (2,), (0, 1), (0, 2), (1, 2)],
    "1-10%": [(3,), (4,), (5,), (0, 3), (1, 3), (0, 4)],
    "0.5-1%": [(6,), (7,), (0, 8)],
}
COUNTERS = ("serving_filtered_requests_total",
            "serving_filter_scan_requests_total",
            "serving_filter_rejected_evals_total",
            "serving_filter_scan_rows_total",
            "serving_filter_scan_calls_total",
            "serving_filter_beam_calls_total")


def csr(table: np.ndarray):
    """(rows, tags) bool -> a CSR tag list."""
    rows, tags = np.nonzero(table)
    return (np.concatenate([[0], np.cumsum(np.bincount(rows,
                                                       minlength=len(table)))]),
            tags)


@pytest.fixture(scope="module")
def data():
    x = planted_manifold(N + 64, DIM, intrinsic_dim=4, seed=3)
    rng = np.random.default_rng(7)
    has = rng.random((N, len(SHARE))) < np.asarray(SHARE)
    base, queries = x[:N], x[N:]
    index = build_deg(base, PARAMS, wave_size=16, tags=csr(has),
                      tag_slots=SLOTS)
    return base, queries, has, index


def admits(has: np.ndarray, filt) -> np.ndarray:
    return has[:, list(filt)].all(axis=1)


def filter_operand(filters, B: int) -> np.ndarray:
    out = np.full((B, 2), INVALID, np.int32)
    for i, f in enumerate(filters):
        out[i, : len(f)] = f
    return out


def lanes(queries, band):
    """(queries, filters, admissible (Q, N)) of a band: every filter of the
    band with every query."""
    fs = [f for f in BANDS[band] for _ in range(len(queries) // 4)]
    qs = np.concatenate([queries[: len(queries) // 4]] * len(BANDS[band]))
    return qs, fs


def recall(ids, truth) -> float:
    return float(np.mean([len(set(a[a >= 0]) & set(b[b >= 0]))
                          / max(1, (b >= 0).sum())
                          for a, b in zip(ids, truth)]))


def test_the_bands_hold_their_selectivity(data):
    _, _, has, index = data
    spans = {"10-60%": (0.1, 0.6), "1-10%": (0.01, 0.1),
             "0.5-1%": (0.005, 0.01)}
    for band, fs in BANDS.items():
        lo, hi = spans[band]
        for f in fs:
            share = admits(has, f).mean()
            assert lo <= share < hi, (band, f, share)
            np.testing.assert_array_equal(index.filter_rows(f),
                                          np.flatnonzero(admits(has, f)))


def test_a_tag_list_becomes_sorted_padded_rows():
    rows = tag_rows([0, 3, 3, 5], [7, 2, 7, 1, 0], 4)
    np.testing.assert_array_equal(rows, [[2, 7, -1, -1], [-1] * 4,
                                         [0, 1, -1, -1]])
    with pytest.raises(ValueError):
        tag_rows([0, 5], [1, 2, 3, 4, 5], 4)
    assert normalize_filter([5, 2, 5]) == (2, 5)
    for bad in ((), (1, 2, 3), (-1,)):
        with pytest.raises(ValueError):
            normalize_filter(bad)
    ti = TagIndex(rows[[0, 2]])
    np.testing.assert_array_equal(ti.rows((7,)), [0])
    assert len(ti.rows((2, 1))) == 0
    assert [ti.count(f) for f in ((7,), (2, 7), (2, 7), (1, 2))] == [1, 1, 1, 0]


@pytest.mark.parametrize("band", list(BANDS))
def test_the_beam_returns_only_admitted_rows(data, band):
    _, queries, has, index = data
    qs, fs = lanes(queries, band)
    res = index.search_batch(qs, None, None, k=K, eps=0.2, beam_width=L,
                             filter_tags=filter_operand(fs, len(qs)))
    ids = np.asarray(res.ids)
    for row, f in zip(ids, fs):
        got = row[row != INVALID]
        assert len(set(got)) == len(got)
        assert admits(has, f)[got].all(), (f, got)
    d = np.asarray(res.dists)
    np.testing.assert_array_equal(np.sort(d, axis=1), d)


def test_the_beam_alone_keeps_recall_where_filters_admit_many(data):
    base, queries, has, index = data
    qs, fs = lanes(queries, "10-60%")
    res = index.search_batch(qs, None, None, k=K, eps=0.2, beam_width=L,
                             filter_tags=filter_operand(fs, len(qs)))
    adm = np.stack([admits(has, f) for f in fs])
    _, truth = filtered_knn(qs, base, K, adm)
    assert recall(np.asarray(res.ids), truth) >= 0.9


@pytest.mark.parametrize("lanes_n", [1, 5, 16])
def test_the_scan_equals_the_reference(data, lanes_n):
    base, queries, has, index = data
    fs = ([f for fs in BANDS.values() for f in fs] * 2)[:lanes_n]
    rows = [index.filter_rows(f) for f in fs]
    ops = buckets.pad_scan(list(queries[:lanes_n]), rows, 16, [16, 64], 4)
    res = index.scan_batch(*ops, k=K)
    adm = np.stack([admits(has, f) for f in fs])
    d, truth = filtered_knn(queries[:lanes_n], base, K, adm)
    np.testing.assert_array_equal(np.asarray(res.ids)[:lanes_n], truth)
    np.testing.assert_allclose(np.asarray(res.dists)[:lanes_n], d,
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(res.evals)[:lanes_n],
                                  [len(r) for r in rows])


def test_without_a_filter_the_program_and_its_answers_are_unchanged(data):
    """No tag operand reaches the filter-free program, and a tagged index
    answers an unfiltered search bit for bit as one built without tags."""
    base, queries, has, index = data
    plain = build_deg(base, PARAMS, wave_size=16)
    np.testing.assert_array_equal(np.asarray(plain.frozen().adjacency),
                                  np.asarray(index.frozen().adjacency))
    for kw in ({}, {"filter_tags": None}):
        a = index.search_batch(queries, None, None, k=K, eps=0.2,
                               beam_width=L, **kw)
        b = plain.search_batch(queries, None, None, k=K, eps=0.2,
                               beam_width=L)
        for f in ("ids", "dists", "hops", "evals"):
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)))
        assert a.rejected is None
    q = jnp.asarray(queries)
    seeds = jnp.zeros((len(queries), 1), jnp.int32)
    args = (index.frozen(), index._dev_vectors, q, seeds)

    def hlo(**kw):
        return range_search.lower(*args, k=K, eps=0.2, beam_width=L,
                                  **kw).as_text()

    tag_operand = f"tensor<{index.capacity}x{SLOTS}xi32>"
    assert hlo() == hlo(tags=None, filter_tags=None)
    assert tag_operand not in hlo()
    assert tag_operand in hlo(tags=index.device_tags(), filter_tags=jnp.full(
        (len(queries), 2), INVALID, jnp.int32))


def _engine(index, **kw):
    return AsyncQueryEngine(index, k=K, eps=0.2, beam_width=L, max_batch=16,
                            bucket_floor=8, linger_ms=1.0, deadline_ms=None,
                            **kw)


@pytest.mark.parametrize("band", list(BANDS))
def test_submit_with_a_filter_keeps_recall_in_each_band(data, band):
    base, queries, has, index = data
    qs, fs = lanes(queries, band)
    with _engine(index) as eng:
        futs = [eng.submit(q, filter=f) for q, f in zip(qs, fs)]
        ids = np.stack([f.result(120)[0] for f in futs])
    adm = np.stack([admits(has, f) for f in fs])
    assert all(adm[i][r[r >= 0]].all() for i, r in enumerate(ids))
    _, truth = filtered_knn(qs, base, K, adm)
    assert recall(ids, truth) >= 0.9


@pytest.mark.parametrize("bad", ["few_rows", "three_tags", "negative",
                                 "untagged"])
def test_submit_refuses_a_filter_it_cannot_serve(data, bad):
    base, queries, has, index = data
    if bad == "untagged":
        index, filt = build_deg(base[:200], PARAMS, wave_size=16), (0,)
    else:
        filt = {"few_rows": (6, 7), "three_tags": (0, 1, 2),
                "negative": (-3,)}[bad]
        assert bad != "few_rows" or admits(has, filt).sum() < K
    with _engine(index) as eng:
        with pytest.raises(RequestValidationError):
            eng.submit(queries[0], filter=filt)
        assert eng.stats.invalid == 1


def test_rows_added_with_tags_are_found_under_them():
    x = planted_manifold(660, DIM, intrinsic_dim=4, seed=5)
    has = np.random.default_rng(1).random((600, 3)) < 0.5
    index = build_deg(x[:600], PARAMS, wave_size=16, tags=csr(has),
                      tag_slots=4)
    new = np.zeros((60, 4), bool)
    new[:, 3] = True                   # a tag no earlier row carries
    index.add(x[600:], wave_size=16, tags=csr(new))
    np.testing.assert_array_equal(index.filter_rows((3,)),
                                  np.arange(600, 660))
    res = index.search_batch(x[600:610], None, None, k=K, eps=0.2,
                             beam_width=L,
                             filter_tags=filter_operand([(3,)] * 10, 10))
    ids = np.asarray(res.ids)
    assert ((ids >= 600) & (ids < 660)).all()
    assert (ids[:, 0] == np.arange(600, 610)).all()    # itself, first
    # deletion moves the last row, and its tags, into the freed slot
    index.remove([5])
    assert index.n == 659
    np.testing.assert_array_equal(index.filter_rows((3,)),
                                  np.r_[5, np.arange(600, 659)])


def test_a_published_epoch_serves_filters_as_the_index_does():
    x = planted_manifold(632, DIM, intrinsic_dim=4, seed=6)
    has = np.random.default_rng(2).random((600, 2)) < 0.5
    index = build_deg(x[:600], PARAMS, wave_size=16, tags=csr(has),
                      tag_slots=4)
    op = filter_operand([(0,), (1,), (0, 1)] * 10 + [(0,)] * 2, 32)
    want = index.search_batch(x[600:], None, None, k=K, eps=0.2,
                              beam_width=L, filter_tags=op)
    index.enable_publishing()
    view = index.acquire_view()
    got = view.search_batch(x[600:], None, None, k=K, eps=0.2, beam_width=L,
                            filter_tags=op)
    index.release_view(view)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(view.filter_rows((0, 1)),
                                  np.flatnonzero(has.all(axis=1)))


def test_a_flush_splits_by_the_rows_a_filter_admits(data):
    _, queries, has, index = data
    items = [buckets.BatchItem(query=queries[0]),
             buckets.BatchItem(query=queries[1], filter=(0,)),
             buckets.BatchItem(query=queries[2], filter=(6,))]
    beam, scan, rows = buckets.route(items, index, limit=100)
    assert beam == [0, 1] and scan == [2]
    np.testing.assert_array_equal(rows[0], index.filter_rows((6,)))
    # a scanned request's exclude ids (the quarantine among them) are
    # left out of the rows it reads
    gone = index.filter_rows((6,))[:3]
    items[2].exclude = list(gone)
    _, _, rows = buckets.route(items, index, limit=100)
    np.testing.assert_array_equal(rows[0], index.filter_rows((6,))[3:])
    assert buckets.pad_filter(items[:1], 4) is None
    np.testing.assert_array_equal(buckets.pad_filter(items, 4)[:3],
                                  [[-1, -1], [0, -1], [6, -1]])


@pytest.mark.parametrize("warm", ["warm_filtered", "warmup"])
def test_a_warmed_engine_compiles_nothing_for_filtered_flushes(data, warm):
    """Either warm path compiles every program a filtered flush of any
    size and either route dispatches."""
    from repro.core.search import filtered_scan

    _, queries, _, index = data
    eng = _engine(index, start=False)
    getattr(eng, warm)()
    sizes = range_search._cache_size(), filtered_scan._cache_size()
    eng.start()
    with eng:
        for n in (1, 3, 16):
            futs = [eng.submit(q, filter=f) for q in queries[:n]
                    for f in ((0,), (3,), (6,), (0, 1))]
            for f in futs:
                f.result(120)
        assert eng.metrics.counter(
            "serving_filter_scan_requests_total").value > 0
    assert (range_search._cache_size(), filtered_scan._cache_size()) == sizes


@pytest.fixture(scope="module")
def served(data):
    """The engine's registry after a mix of both routes and a plain
    request, through warmed programs."""
    _, queries, has, index = data
    with _engine(index) as eng:
        eng.warm_filtered()
        fs = [(0,), (3,), (6,), (0, 1), None]
        futs = [eng.submit(q, filter=f)
                for q in queries[:16] for f in fs]
        for f in futs:
            f.result(120)
        limit = eng.scan_limit()
    return eng.metrics, limit, fs


@pytest.mark.parametrize("name", COUNTERS)
def test_each_filter_counter_moves(served, name):
    metrics, limit, fs = served
    assert metrics.counter(name).value > 0, name
    if name == "serving_filtered_requests_total":
        assert metrics.counter(name).value == 16 * 4
    assert 0 < limit <= 1024
