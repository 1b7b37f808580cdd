"""tagged_manifold: the planted manifold (``planted_manifold.py``) on the
uint8 grid, each row given a bag of tags, and each query of the pool a
filter of one tag or the AND of two, at a selectivity the configuration
states.

The shape of the big-ann-benchmarks NeurIPS'23 filtered track (YFCC-10M:
CLIP image embeddings as uint8, tags from the photos' words, camera, year
and country).  The real set cannot be fetched; what decides the work is
kept:

* rows: the planted manifold, affinely mapped per dimension onto 0..255
  over the base rows and rounded, so every stored value is a uint8;
  queries are rows of the same manifold and map, kept float32 (an
  encoder's output, before any store rounds it);
* tags: each row carries ``tags_min``..``tags_max`` draws (uniform) from
  a vocabulary of ``vocab`` ids with Zipf(``zipf_a``) frequencies, the
  repeats dropped.  Half are "metadata" tags, drawn from the global Zipf
  law and so independent of the vector; half are "content" tags, drawn
  from the Zipf law of the row's nearest of ``anchors`` anchor points,
  each anchor ranking the vocabulary past its first ``shared_head`` ids
  in an order of its own (an affine permutation), and so correlated with
  the vector.  The head, the most frequent words, is every anchor's: it
  is what lets two tags AND to a tenth of the rows or more;
* filters: the pool is split into ``len(bands)`` equal parts, part j
  holding queries whose filter admits a share of the base rows in
  ``[bands[j][0], bands[j][1])`` (at least ``k`` rows); within a part half the filters are
  one tag and half the AND of two, drawn by rejection from the query's
  own tags.  Candidate queries are drawn ``candidates`` times the pool.

``meta``: ``tag_indptr``/``tag_indices``, the base rows' tags as a CSR
list; ``filters`` (pool, 2) int32, -1 where a filter has one tag;
``admitted`` (pool,), the rows each filter admits; ``band`` (pool,).
"""
from __future__ import annotations

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.data import Corpus

HERE = Path(__file__).resolve().parents[1]

#: two-tag filters tried per candidate query, at most
PAIRS_TRIED = 16


def _rows(cfg: dict, seed: int, count: int) -> np.ndarray:
    gen = cfg["generator"]
    return spec.generator("planted_manifold", HERE).planted_manifold(
        seed, count, cfg["dim"], gen["intrinsic_dim"], gen["noise"])


@jax.jit
def _nearest(x, anchors):
    d = (jnp.sum(anchors * anchors, axis=1)[None, :]
         - 2.0 * jnp.matmul(x, anchors.T,
                            precision=jax.lax.Precision.HIGHEST))
    return jnp.argmin(d, axis=1)


def nearest_anchor(x: np.ndarray, anchors: np.ndarray) -> np.ndarray:
    a = jnp.asarray(anchors)
    return np.concatenate([np.asarray(_nearest(jnp.asarray(x[i: i + 16384]),
                                               a))
                           for i in range(0, len(x), 16384)])


def _zipf_ranks(rng, cdf: np.ndarray, shape) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(shape), side="right"),
                      len(cdf) - 1)


def draw_tags(rng, anchor_of: np.ndarray, gen: dict) -> np.ndarray:
    """(rows, tags_max) int64 tags, -1 past each row's distinct ones."""
    vocab = int(gen["vocab"])
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1) ** float(gen["zipf_a"]))
    cdf /= cdf[-1]
    rows, width = len(anchor_of), int(gen["tags_max"])
    m = rng.integers(int(gen["tags_min"]), width + 1, size=rows)
    n_content = m // 2
    n_anchor = int(gen["anchors"])
    # each anchor's order of the vocabulary: rank r -> (r * mul + off) % V,
    # mul prime to V
    mul = rng.integers(1, vocab, size=n_anchor)
    while True:
        bad = np.gcd(mul, vocab) != 1
        if not bad.any():
            break
        mul[bad] = rng.integers(1, vocab, size=int(bad.sum()))
    off = rng.integers(0, vocab, size=n_anchor)
    ranks = _zipf_ranks(rng, cdf, (rows, width))
    col = np.arange(width)[None, :]
    content = np.where(
        ranks < int(gen["shared_head"]), ranks,
        (ranks * mul[anchor_of][:, None] + off[anchor_of][:, None]) % vocab)
    tags = np.where(col < n_content[:, None], content, ranks)
    tags = np.where(col < m[:, None], tags, np.iinfo(np.int64).max)
    tags.sort(axis=1)
    tags[:, 1:][tags[:, 1:] == tags[:, :-1]] = np.iinfo(np.int64).max
    tags.sort(axis=1)
    return np.where(tags == np.iinfo(np.int64).max, -1, tags)


class Postings:
    """Each tag's base rows, and the rows a filter admits (plain numpy)."""

    def __init__(self, table: np.ndarray):
        self.table = table                          # (n, tags_max), -1 pad
        n, w = table.shape
        flat = table.ravel()
        ok = flat >= 0
        order = np.argsort(flat[ok], kind="stable")
        self.rows = np.repeat(np.arange(n), w)[ok][order]
        self.tags, self.start, cnt = np.unique(flat[ok][order],
                                               return_index=True,
                                               return_counts=True)
        self.end = self.start + cnt

    def count(self, tags: np.ndarray) -> np.ndarray:
        i = np.clip(np.searchsorted(self.tags, tags), 0, len(self.tags) - 1)
        return np.where(self.tags[i] == tags, self.end[i] - self.start[i], 0)

    def posting(self, tag: int) -> np.ndarray:
        i = int(np.searchsorted(self.tags, tag))
        if i < len(self.tags) and self.tags[i] == tag:
            return self.rows[self.start[i]: self.end[i]]
        return self.rows[:0]

    def admitted(self, filt) -> np.ndarray:
        """The base rows carrying every tag of ``filt`` (-1 ignored)."""
        tags = sorted((int(t) for t in filt if t >= 0),
                      key=lambda t: len(self.posting(t)))
        rows = self.posting(tags[0])
        for t in tags[1:]:
            rows = rows[(self.table[rows] == t).any(axis=1)]
        return rows


def choose_filters(rng, post: Postings, qtags: np.ndarray, n: int, k: int,
                   bands, pool: int):
    """(chosen candidate queries, their filters (pool, 2), admitted rows,
    band) — each band's part of the pool half one-tag, half two-tag."""
    slots = []
    per = [pool // len(bands) + (j < pool % len(bands))
           for j in range(len(bands))]
    for j, (lo, hi) in enumerate(bands):
        lo_rows = max(math.ceil(lo * n), k)
        hi_rows = math.ceil(hi * n) - 1             # the band is [lo, hi)
        if lo_rows > hi_rows:
            raise ValueError(f"band {lo}-{hi} of {n} rows admits no filter "
                             f"of at least k={k} rows")
        slots += [(j, 1, lo_rows, hi_rows)] * (per[j] - per[j] // 2)
        slots += [(j, 2, lo_rows, hi_rows)] * (per[j] // 2)
    cnt = np.where(qtags >= 0, post.count(qtags), 0)
    used = np.zeros(len(qtags), bool)
    order = rng.permutation(len(qtags))
    out_q, out_f, out_n, out_b = [], [], [], []
    for kind in sorted(set(s[:2] for s in slots), key=lambda s: (-s[1], s[0])):
        j, arity = kind
        need = sum(1 for s in slots if s[:2] == kind)
        _, _, lo_rows, hi_rows = next(s for s in slots if s[:2] == kind)
        for c in order:
            if need == 0:
                break
            if used[c]:
                continue
            own = qtags[c][qtags[c] >= 0]
            c_cnt = cnt[c][qtags[c] >= 0]
            if arity == 1:
                ok = np.flatnonzero((c_cnt >= lo_rows) & (c_cnt <= hi_rows))
                if not len(ok):
                    continue
                t = int(own[ok[rng.integers(len(ok))]])
                filt, got = (t, -1), int(cnt[c][qtags[c] == t][0])
            else:
                big = own[c_cnt >= lo_rows]
                pairs = [(a, b) for i, a in enumerate(big) for b in big[i + 1:]]
                rng.shuffle(pairs)
                filt = None
                for a, b in pairs[:PAIRS_TRIED]:
                    got = len(post.admitted((a, b)))
                    if lo_rows <= got <= hi_rows:
                        filt = (int(a), int(b))
                        break
                if filt is None:
                    continue
            used[c] = True
            need -= 1
            out_q.append(c)
            out_f.append(filt)
            out_n.append(got)
            out_b.append(j)
        if need:
            raise ValueError(f"too few candidate queries for band {j}, "
                             f"{arity}-tag filters ({need} missing)")
    return (np.asarray(out_q), np.asarray(out_f, np.int32),
            np.asarray(out_n), np.asarray(out_b))


def make(cfg: dict, seed: int) -> Corpus:
    gen = cfg["generator"]
    n, pool = cfg["n"], cfg["query_pool"]
    n_cand = int(gen["candidates"]) * pool
    x = _rows(cfg, seed, n + n_cand + int(gen["anchors"]))
    base, cand = x[:n], x[n: n + n_cand]
    lo, hi = base.min(axis=0), base.max(axis=0)
    scale = 255.0 / np.maximum(hi - lo, 1e-12)
    base = np.round((base - lo) * scale).astype(np.float32)
    cand = np.clip((cand - lo) * scale, 0.0, 255.0).astype(np.float32)
    anchors = np.clip((x[n + n_cand:] - lo) * scale, 0.0, 255.0
                      ).astype(np.float32)

    rng = np.random.default_rng([seed, 11])
    tags = draw_tags(rng, nearest_anchor(np.concatenate([base, cand]),
                                         anchors), gen)
    btags, qtags = tags[:n], tags[n:]
    post = Postings(btags)
    q, filters, admitted, band = choose_filters(
        rng, post, qtags, n, cfg["search"]["k"], gen["bands"], pool)
    counts = (btags >= 0).sum(axis=1)
    return Corpus(base, cand[q], {
        "tag_indptr": np.concatenate([[0], np.cumsum(counts)]),
        "tag_indices": btags[btags >= 0],
        "filters": filters, "admitted": admitted, "band": band})
