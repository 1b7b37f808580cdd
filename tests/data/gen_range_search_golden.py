"""Generate the range_search golden fixture.

Run once against the pre-beam-engine (seed) implementation so the refactor
can be checked for bit-identical (ids, dists) on a fixed-seed corpus:

    PYTHONPATH=src python tests/data/gen_range_search_golden.py

The fixture stores the frozen graph + queries + every configuration's
outputs; tests/test_search_golden.py replays them against the live code.

Today's build no longer reproduces the fixture's graph (construction has
changed since it was written), so the graph itself is never regenerated.
When a toolchain legitimately changes float rounding, refresh only the
distances by replaying the three cases on the committed graph:

    PYTHONPATH=src python tests/data/gen_range_search_golden.py --refresh-dists

That mode refuses to write unless the replayed ids, hops and evals are
bit-identical to the committed ones.
"""
from __future__ import annotations

import os

import numpy as np


def build_cases():
    from repro.core.build import DEGParams, build_deg

    rng = np.random.default_rng(1234)
    vecs = rng.normal(size=(300, 24)).astype(np.float32)
    idx = build_deg(vecs, DEGParams(degree=8, k_ext=16), wave_size=4)
    graph = idx.frozen()
    queries = (vecs[:16] + 0.05 * rng.normal(size=(16, 24))).astype(np.float32)
    return run_cases(graph, idx.vectors.copy(), queries)


def run_cases(graph, vectors, queries):
    """The three search configurations on one graph -> fixture arrays."""
    import jax.numpy as jnp

    from repro.core.graph import INVALID
    from repro.core.search import range_search

    out = {
        "adjacency": np.asarray(graph.adjacency),
        "weights": np.asarray(graph.weights),
        "n": np.asarray(graph.n),
        "vectors": vectors,
        "queries": queries,
    }
    dev_vectors = jnp.asarray(vectors)

    # case A: single shared seed, defaults
    seeds_a = np.full((16, 1), 3, dtype=np.int32)
    out["seeds_a"] = seeds_a
    res = range_search(graph, dev_vectors, jnp.asarray(queries),
                       jnp.asarray(seeds_a), k=10, eps=0.1)
    out.update(a_ids=np.asarray(res.ids), a_dists=np.asarray(res.dists),
               a_hops=np.asarray(res.hops), a_evals=np.asarray(res.evals))

    # case B: eps=0, multi-seed with INVALID padding, tight beam
    seeds_b = np.stack([np.array([5, 17, INVALID, 5], np.int32)] * 16)
    seeds_b[::2, 1] = 40
    out["seeds_b"] = seeds_b
    res = range_search(graph, dev_vectors, jnp.asarray(queries),
                       jnp.asarray(seeds_b), k=4, eps=0.0, beam_width=12)
    out.update(b_ids=np.asarray(res.ids), b_dists=np.asarray(res.dists),
               b_hops=np.asarray(res.hops), b_evals=np.asarray(res.evals))

    # case C: exploration — vertex seeds excluded from results
    sv = np.arange(16, dtype=np.int32)
    excl = np.stack([sv, (sv + 7) % int(graph.n),
                     np.full(16, INVALID, np.int32)], axis=1)
    out["seeds_c"] = sv[:, None]
    out["exclude_c"] = excl
    res = range_search(graph, dev_vectors,
                       jnp.asarray(vectors[sv]),
                       jnp.asarray(sv[:, None]), k=6, eps=0.2,
                       exclude=jnp.asarray(excl))
    out.update(c_ids=np.asarray(res.ids), c_dists=np.asarray(res.dists),
               c_hops=np.asarray(res.hops), c_evals=np.asarray(res.evals))
    return out


def refresh_dists(path):
    """Replay the cases on the committed graph; rewrite only ``*_dists``,
    and only if every other replayed output is bit-identical."""
    import jax.numpy as jnp

    from repro.core.graph import DEGraph

    old = dict(np.load(path))
    graph = DEGraph(adjacency=jnp.asarray(old["adjacency"]),
                    weights=jnp.asarray(old["weights"]),
                    n=jnp.asarray(old["n"]))
    new = run_cases(graph, old["vectors"], old["queries"])
    for key in sorted(old):
        if key.endswith("_dists"):
            d = np.abs(new[key] - old[key])[np.isfinite(old[key])]
            print(f"{key}: max |diff| {d.max():.3g}, {int((d > 0).sum())} "
                  f"of {d.size} entries differ")
        elif not np.array_equal(new[key], old[key]):
            raise SystemExit(f"{key} changed: not a rounding-only change, "
                             "refusing to refresh the fixture")
        else:
            print(f"{key}: bit-identical")
    out = dict(old)
    out.update({k: v for k, v in new.items() if k.endswith("_dists")})
    np.savez_compressed(path, **out)
    print(f"wrote {path}: only the *_dists arrays changed")


def main():
    import sys

    path = os.path.join(os.path.dirname(__file__), "range_search_golden.npz")
    if "--refresh-dists" in sys.argv:
        refresh_dists(path)
        return
    out = build_cases()
    np.savez_compressed(path, **out)
    print(f"wrote {path}: " + ", ".join(sorted(out)))


if __name__ == "__main__":
    main()
