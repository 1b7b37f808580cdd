"""Differential recall: the full engine-knob grid vs brute force.

Every (codec, expand_width, kernels) combination of the query engine
searches the same seeded index and is held to a pinned recall@10 floor
against ``baselines/brute_force`` — a knob combination can't silently
regress (e.g. a visited-filter bug that only bites the fused hop, or a
rerank path that only bites sq8).  ``kernels="pallas"`` selects each
codec's Pallas route: the fused hop for the exact float32 store, the
distance kernels (``gather_dist`` / ``gather_dist_q`` / ``pq_adc``) for
the compressed ones, which the fused hop refuses.  Off-TPU the kernels run
in interpret mode (``_default_interpret`` of each ``ops``), so the grid
covers both programs everywhere.
"""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from repro.core.baselines.brute_force import BruteForceIndex
from repro.core.build import DEGParams, build_deg
from repro.core.metrics import recall_at_k

pytestmark = pytest.mark.slow

K = 10
#: pinned floors — measured 0.9875 across the whole grid on the seeded
#: dataset; compressed traversal gets a little slack (rerank restores most
#: of it, but codes are lossy).  pq on this corpus (dim 8 -> one 8-dim
#: subspace, 200 rows < 256 centroids) reconstructs near-exactly, so it
#: is held to the float floor.
FLOORS = {"float32": 0.95, "fp16": 0.95, "sq8": 0.92, "pq": 0.95}
GRID = sorted(itertools.product(
    ["float32", "fp16", "sq8", "pq"], [1, 2], ["jnp", "pallas"]))


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(42)
    base = rng.normal(size=(200, 8)).astype(np.float32)
    queries = rng.normal(size=(16, 8)).astype(np.float32)
    idx = build_deg(base, DEGParams(degree=8, k_ext=16), wave_size=8,
                    refine_iterations=50)
    _, gt_ids = BruteForceIndex(base).search(queries, K)
    return idx, queries, np.asarray(gt_ids)


def _search(idx, queries, codec, expand_width, kernels):
    pallas = kernels == "pallas"
    exact = codec == "float32"
    return idx.search(queries, k=K, eps=0.2,
                      quantized=None if exact else codec,
                      expand_width=expand_width,
                      hop_backend="pallas" if pallas and exact else "jnp",
                      backend="pallas" if pallas and not exact else "jnp")


@pytest.mark.parametrize("codec,expand_width,kernels", GRID)
def test_recall_floor(corpus, codec, expand_width, kernels):
    idx, queries, gt = corpus
    res = _search(idx, queries, codec, expand_width, kernels)
    rec = recall_at_k(np.asarray(res.ids), gt)
    assert rec >= FLOORS[codec], (
        f"recall@{K}={rec:.4f} under floor {FLOORS[codec]} for "
        f"codec={codec} E={expand_width} kernels={kernels}")


def test_grid_agrees_with_itself(corpus):
    """E/kernels are engine reshapes, not semantics: within one codec,
    every (E, kernels) combination must return the same result *set*
    quality — their recalls may not diverge by more than one result out
    of k."""
    idx, queries, gt = corpus
    for codec in FLOORS:
        recs = []
        for E, kernels in itertools.product([1, 2], ["jnp", "pallas"]):
            res = _search(idx, queries, codec, E, kernels)
            recs.append(recall_at_k(np.asarray(res.ids), gt))
        assert max(recs) - min(recs) <= 1.0 / K + 1e-9, (codec, recs)
