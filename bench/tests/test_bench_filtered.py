"""The filtered configuration's data and judge: the generator's pool holds
each selectivity band and filter arity in its share, the judge's
admissible rows are those the tags give, and an answer outside them is a
bad row."""
import numpy as np
import pytest

from bench import drivers, judge, reference, run, spec

CELL = "yfcc.filtered_closed"


@pytest.fixture(scope="module")
def made():
    cell = spec.cell(spec.load_benchmark(), CELL)
    cfg = spec.config(cell["config"])
    cfg = run._merge(cfg, cfg["rehearsal"])
    mix = spec.mix(cell["traffic"])
    return cfg, mix, spec.generator(cfg["generator"]["kind"]).make(cfg, 9)


def test_the_pool_holds_each_band_and_arity_in_its_share(made):
    cfg, _, c = made
    meta, n = c.meta, cfg["n"]
    bands = cfg["generator"]["bands"]
    per = cfg["query_pool"] // len(bands)
    for j, (lo, hi) in enumerate(bands):
        part = meta["band"] == j
        assert part.sum() == per
        two = meta["filters"][part][:, 1] >= 0
        assert two.sum() == per // 2
        share = meta["admitted"][part] / n
        assert (share >= lo).all() and (share < hi).all()
    assert (meta["admitted"] >= cfg["search"]["k"]).all()
    # rows on the uint8 grid, queries as an encoder emits them
    assert c.base.min() >= 0 and c.base.max() <= 255
    np.testing.assert_array_equal(c.base, np.round(c.base))
    assert (c.pool != np.round(c.pool)).any()
    counts = np.diff(meta["tag_indptr"])
    assert counts.max() <= cfg["tags"]["slots"]
    assert counts.min() >= 1


def test_the_admitted_rows_are_those_the_tags_give(made):
    cfg, _, c = made
    kind = drivers.load_kind("filtered_closed")
    n = len(c.base)
    bits = kind.admitted_bits(c.meta, n)
    rows = np.repeat(np.arange(n), np.diff(c.meta["tag_indptr"]))
    carried = {(int(r), int(t)) for r, t in zip(rows, c.meta["tag_indices"])}
    adm = kind.admissible(bits, np.arange(len(c.pool)))
    for p in (0, len(c.pool) // 2, len(c.pool) - 1):
        want = [x for x in range(n)
                if all((x, int(t)) in carried for t in c.meta["filters"][p]
                       if t >= 0)]
        got = np.flatnonzero(adm(np.full((1, n), p), np.arange(n)[None, :]))
        np.testing.assert_array_equal(got, want)
        assert len(want) == c.meta["admitted"][p]


def test_an_answer_outside_its_filter_is_a_bad_row(made):
    cfg, mix, c = made
    cfg = dict(cfg, control={})
    kind = drivers.load_kind(mix["kind"])
    base, q, truth, adm = kind.control_requests(cfg, mix, 9, 1.0, 40, 0,
                                                spec.BENCH_DIR)
    k = cfg["search"]["k"]
    outside = np.flatnonzero(~adm(np.zeros((1, len(base)), int),
                                  np.arange(len(base))[None, :])[0])
    planted = truth.copy()
    planted[0, -1] = outside[0]
    for ids, bad in ((truth, 0), (planted, 1)):
        d = reference.exact_distances(q, base, ids)
        order = np.argsort(d, axis=1)
        ids, d = (np.take_along_axis(a, order, 1) for a in (ids, d))
        nums = judge.answer_numbers(base, q, ids, d, np.ones(len(q), bool),
                                    truth, k, adm, cfg["metric"])
        assert nums["bad_rows"] == bad
