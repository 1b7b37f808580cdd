#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, in one process that owns the chip.  The cell
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``) in ``BENCHMARK.json``; the mix's ``kind``
names its driver, ``bench/kinds/<kind>.py`` (``bench/drivers.py``).
Set-up makes the data from the seed, builds the index and compiles every
program the window will run; the window then measures for ``--seconds``.
After the window the program's answers are judged against the plain
references (``bench/judge.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a
steady stretch of the window.  Each metric is computed by its reader,
``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with a trace,
``breakdown``), then ``checks``: each number compared, with its limit.
The same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run fails
before any work and prints no result.  ``--rehearse`` runs the cell at the
tiny size its files give under ``rehearsal`` on the CPU
(``JAX_PLATFORMS=cpu``); it prints ``platform cpu`` and no metric values.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

#: a traced run records a stretch of the window from ``TRACE_AFTER`` of it
#: (or the mix's ``trace_after``), for ``TRACE_SECONDS`` (or the mix's
#: ``trace_seconds``; at most half the window)
TRACE_AFTER = 0.3
TRACE_SECONDS = 2.0


class NoDevice(RuntimeError):
    pass


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _device(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if rehearse:
        if d0.platform != "cpu":
            raise NoDevice("--rehearse runs on the CPU only "
                           "(JAX_PLATFORMS=cpu)")
    elif d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees platform {d0.platform!r}; the "
                       "benchmark never falls back to the CPU")
    elif len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    print(f"device: platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devs)}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "devices": devs[:chips]}


def _memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run(argv=None, bench_dir: Path = spec.BENCH_DIR,
        root: Path = spec.ROOT) -> dict:
    """One run; returns the result object it prints."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU; prints no metric values")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(root)
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(cell["config"], bench_dir)
    mix = spec.mix(cell["traffic"], bench_dir)
    if args.rehearse:
        cfg = _merge(cfg, cfg.get("rehearsal", {}))
        mix = _merge(mix, mix.get("rehearsal", {}))
    else:
        from repro.launch import compile_cache

        compile_cache.enable()
    dev = _device(cell["chips"], args.rehearse)

    from bench import drivers, peaks
    from bench.compiles import CompileLog, CompileNames

    log = CompileLog()
    trace = bool(args.trace)
    seconds = args.seconds
    traced_s = min(mix.get("trace_seconds", TRACE_SECONDS), seconds * 0.5)

    def tracer(counters):
        return drivers.Tracer(
            trace, mix.get("trace_after", TRACE_AFTER) * seconds, traced_s,
            counters)

    kind = drivers.load_kind(mix["kind"], bench_dir)
    drv = kind.Driver(cfg, mix, args.seed, tracer, bench_dir)
    drv.setup()
    # the set-up heap (the index, JAX's caches) lives as long as the run:
    # freeze it, so that the collector, which stays on, does not walk it
    # again in the window
    gc.collect()
    gc.freeze()
    t_window = time.perf_counter()
    before = log.programs
    with CompileNames() as compiled:
        rec = drv.window(seconds)
    in_window = log.programs - before
    print(f"window_compiles: {in_window} {compiled.names} (set-up "
          f"compiled {before} programs, {log.hits} from the persistent "
          "cache)", flush=True)
    rec.setup_s = t_window - T_START
    answers = drv.settle(rec)
    t_settled = time.perf_counter()
    print(f"settled: {t_settled - t_window:.1f} s after the window opened",
          file=sys.stderr, flush=True)
    mem = _memory_peak(dev["devices"])
    rec.trace = drv.tracer.reduce()
    rec.peaks = None if args.rehearse else peaks.peaks(dev["kind"])
    drv.free()
    gc.collect()
    numbers = drv.judge(rec, answers)
    from bench import judge

    correct, checks = judge.verdict(numbers, cfg["limits"])
    print(f"times: set-up {rec.setup_s:.1f} s, window and settle "
          f"{t_settled - t_window:.1f} s, trace and judge "
          f"{time.perf_counter() - t_settled:.1f} s", file=sys.stderr)
    attempted, failed = drv.counts(rec)

    metrics, readable = {}, []
    for m in spec.metrics_for(bench, args.workload, trace):
        value = spec.metric_reader(m["name"], bench_dir)(rec)
        if value is not None:
            readable.append(m["name"])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    if args.rehearse:
        out["rehearsal"] = {"readable": readable}
    else:
        out["metrics"] = metrics
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    out["device"] = device
    if trace and rec.trace is not None and not args.rehearse:
        out["breakdown"] = {"device_ops": rec.trace["top_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    try:
        run(argv)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
