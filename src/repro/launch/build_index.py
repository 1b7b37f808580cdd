"""Index-building launcher: ``python -m repro.launch.build_index``.

Builds a DEG over a synthetic dataset (paper Table 3 parameters by default),
optionally runs continuous refinement, reports recall/QPS, and saves the
graph + vectors to an .npz file that serve.py can load.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--degree", type=int, default=20)
    ap.add_argument("--k-ext", type=int, default=40)
    ap.add_argument("--eps-ext", type=float, default=0.3)
    ap.add_argument("--wave", type=int, default=16,
                    help="bulk-build wave size (1 = paper-faithful)")
    ap.add_argument("--refine", type=int, default=0,
                    help="continuous-refinement iterations after build")
    ap.add_argument("--lid", choices=["low", "high"], default="low")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.core.build import DEGParams, build_deg
    from repro.core.distances import exact_knn_batched
    from repro.core.invariants import check_invariants
    from repro.core.metrics import recall_at_k
    from repro.data.synthetic import gaussian_mixture, planted_manifold
    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}")

    gen = gaussian_mixture if args.lid == "low" else planted_manifold
    vecs = gen(args.n + 500, args.dim, seed=args.seed)
    base, queries = vecs[: args.n], vecs[args.n:]

    params = DEGParams(degree=args.degree, k_ext=args.k_ext,
                       eps_ext=args.eps_ext,
                       scheme="C", rng_checks=True)
    t0 = time.time()
    idx = build_deg(base, params, wave_size=args.wave)
    build_s = time.time() - t0
    if args.refine:
        t0 = time.time()
        idx.refine(args.refine, seed=args.seed)
        print(f"refined {args.refine} iterations in {time.time()-t0:.1f}s "
              f"(avg neighbor dist {idx.builder.average_neighbor_distance():.4f})")
    ok, msgs = check_invariants(idx.builder)
    assert ok, msgs
    t0 = time.time()
    res = idx.search(queries, k=10, eps=0.1)
    qps = queries.shape[0] / (time.time() - t0)
    _, gt = exact_knn_batched(queries, base, 10)
    rec = recall_at_k(np.asarray(res.ids), gt)
    print(f"n={args.n} d={args.degree} wave={args.wave}: "
          f"build {build_s:.1f}s, recall@10 {rec:.4f}, {qps:.0f} qps, "
          f"avg-hops {float(np.mean(np.asarray(res.hops))):.1f}")
    if args.out:
        # versioned full-state snapshot (persist/): serve.py warm-starts
        # from this without rebuilding, and the restored index stays mutable
        idx.save(args.out)
        print(f"saved index snapshot to {args.out}")


if __name__ == "__main__":
    main()
