#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, one precision step below the configuration's, must be judged not
correct.

    python bench/control.py --workload audio.serve_poisson --seeds 1,2,3

The configuration serves float32 distances, computed exactly; the control
answers the same requests with the brute force in three bfloat16 passes
(``reference.brute_force_control``, what ``Precision.HIGH`` does on a
TPU), and the judge compares those answers as it compares the program's.
For each seed it prints the numbers and the verdict as one JSON line.

The requests are those a run of the cell judges, made from the seed by the
kind's ``control_requests`` (``bench/kinds/<kind>.py``): the open-loop
schedule of ``run_seconds`` (BENCHMARK.json), or ``--requests``
exploration queries drawn as the closed loop draws them, or the build
cell's probe queries over ``--rows`` inserted rows.  Where the kind
restricts its requests to some rows (an exploration query's own vertex
left out, a filter), the control searches those rows only.  The
benchmark's own runs do not run this; ``bench/tests/test_bench_control.py``
runs it at the sizes each configuration gives under ``control``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control_numbers(cfg: dict, mix: dict, seed: int, seconds: float,
                    requests: int, rows: int, bench_dir=None) -> dict:
    import numpy as np

    from bench import drivers, judge, reference

    bench_dir = bench_dir or drivers.BENCH_DIR
    kind = drivers.load_kind(mix["kind"], bench_dir)
    base, q, truth, admissible = kind.control_requests(
        cfg, mix, seed, seconds, requests, rows, bench_dir)
    k, metric = cfg["search"]["k"], cfg["metric"]
    d, ids = reference.brute_force_control(q, base, k, admissible, metric)
    return judge.answer_numbers(base, q, ids, d, np.ones(len(q), bool),
                                truth, k, admissible, metric)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--requests", type=int, default=20000,
                    help="exploration queries judged (closed loop)")
    ap.add_argument("--rows", type=int, default=3000,
                    help="rows inserted (build cell)")
    args = ap.parse_args(argv)

    from bench import judge, spec

    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        nums = control_numbers(cfg, mix, seed, bench["run_seconds"],
                               args.requests, args.rows)
        ok, checks = judge.verdict(nums, cfg["limits"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
