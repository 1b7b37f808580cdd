"""Benchmark orchestrator: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure (Sec. 6-7 + Appendix F/G), plus the
kernel structural benchmarks and the §Roofline aggregation of the dry-run
artifacts.  Emits a CSV (reports/bench.csv) and prints one line per
measurement.  ``--quick`` shrinks every dataset ~4x for smoke use.
"""
from __future__ import annotations

import argparse
import time
import traceback


SECTIONS = [
    ("fig4_qps_recall", "qps_recall"),
    ("fig5_exploration", "exploration"),
    ("table4_build_cost", "build_cost"),
    ("fig6_scalability", "scalability"),
    ("fig7_left_edge_optimization", "edge_optimization"),
    ("fig7_right_degree_sweep", "degree_sweep"),
    ("table12_graph_stats", "graph_stats"),
    ("appG_neighbor_choice", "neighbor_choice"),
    ("kernels", "kernels"),
    ("kernel_beam_merge", "beam_merge"),
    ("quantized_store", "quantization"),
    ("search_pareto", "search_pareto"),
    ("serving_open_loop", "serving_load"),
    ("roofline", "roofline_report"),
]

QUICK_OVERRIDES = {
    "qps_recall": dict(n=2000, n_query=128),
    "exploration": dict(n=2000, n_query=128),
    "build_cost": dict(n=1500, n_query=100),
    "scalability": dict(sizes=(500, 1000, 2000)),
    "edge_optimization": dict(n=1200, n_query=100,
                              batches=(0, 300, 900)),
    "degree_sweep": dict(n=1500, n_query=100, degrees=(8, 16)),
    "graph_stats": dict(n=1200),
    "neighbor_choice": dict(n=1200, n_query=100),
    "beam_merge": dict(shapes=((64, 64, 20), (64, 128, 32))),
    "quantization": dict(n=1500, n_query=128, rerank_ks=(10, 20),
                         pq_rerank_ks=(80,)),
    "search_pareto": dict(n=1500, n_query=128, expand_widths=(1, 2),
                          beam_widths=(32, 48), backends=("jnp",),
                          refine=100),
    # the serving smoke shares the CI gate config so there is exactly one
    # quick configuration (see serving_load.QUICK_CONFIG)
    "serving_load": None,       # resolved below: serving_load.QUICK_CONFIG
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--csv", default="reports/bench.csv")
    args = ap.parse_args()

    import importlib
    import os

    from repro.launch import compile_cache

    from . import common

    print(f"compile cache: {compile_cache.enable()}")
    only = set(args.only.split(",")) if args.only else None
    failures = []
    for title, mod_name in SECTIONS:
        if only and mod_name not in only:
            continue
        print(f"\n=== {title} ({mod_name}) " + "=" * 30, flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            kw = QUICK_OVERRIDES.get(mod_name, {}) if args.quick else {}
            if kw is None:      # module exports its own quick config
                kw = dict(mod.QUICK_CONFIG)
            summary = mod.run(**kw)
            print(f"--- {mod_name} done in {time.time()-t0:.1f}s: {summary}")
        except Exception as e:
            failures.append((mod_name, e))
            traceback.print_exc()
            # a broken section must leave a machine-readable trace in the
            # CSV, not just a traceback on a terminal nobody scrolls back
            common.emit("section_failure", section=mod_name,
                        error=f"{type(e).__name__}: {e}",
                        seconds=time.time() - t0)
    if args.csv:
        os.makedirs(os.path.dirname(args.csv) or ".", exist_ok=True)
        common.write_csv(args.csv)
        print(f"\nwrote {len(common.rows())} rows to {args.csv}")
    if failures:
        print(f"\n{len(failures)} benchmark sections FAILED: "
              f"{[m for m, _ in failures]}")
        return 1
    print("\nall benchmark sections passed")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
