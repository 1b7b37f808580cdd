#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate it sustains.

    python bench/sweep.py --workload audio.serve_poisson --seed 1 \\
        --rates 1000,2000,3000 --seconds 5

One process: set-up once (data, index, engine, warm-up, as a run of the
cell does), then the cell's open-loop schedule at each offered rate in
turn.  For each rate it prints the completed rate, the p50, p95 and p99 latency
from the scheduled send, and the growth of latency across the step (the
mean latency of the last fifth of requests minus that of the first fifth):
a backlog that grows through the step marks a rate above the knee.  The
benchmark's cells do not run this; the knee it finds is written into the
mix's ``rate_qps`` by hand, with the sweep in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import drivers, spec, stats
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    kind = drivers.load_kind(mix["kind"])
    drv = kind.Driver(cfg, mix, args.seed,
                      lambda c: drivers.Tracer(False, 0, 0, c),
                      spec.BENCH_DIR)
    drv.setup()
    gc.collect()                    # as bench/run.py does for its window
    gc.freeze()
    st = drv.eng.stats
    k = cfg["search"]["k"]
    for rate in [float(r) for r in args.rates.split(",")]:
        drv.mix = dict(mix, rate_qps=rate)
        drv.ledger = drivers.Ledger(k)
        q0, f0 = st.queries, st.flushes
        run = drv.window(args.seconds)
        drv.ledger.drain(run.window_end + 60.0)
        drv.ledger.fill(run)
        lat = stats.latencies_ms(run.completed_at, run.due_at, run.failed)
        fifth = max(1, len(lat) // 5)
        span = np.nanmax(run.completed_at) - run.window_start
        print(json.dumps({
            "offered_qps": rate, "requests": len(lat),
            "completed_qps": len(lat) / span,
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "p99_ms": stats.percentile(lat, 99),
            "growth_ms": float(lat[-fifth:].mean() - lat[:fifth].mean()),
            "send_lag_p99_ms": stats.percentile(
                (run.sent_at - run.due_at) * 1e3, 99),
            "lanes_per_flush": (st.queries - q0) / (st.flushes - f0),
        }), flush=True)
    drv.eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
