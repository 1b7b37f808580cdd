"""Open-loop serving latency under Poisson load — the honest online
version of ``search_pareto.py``'s offline QPS.

A closed-loop benchmark (submit a batch, wait, repeat) can never observe
queueing delay: the load adapts to the server.  This harness drives the
continuous-batching ``AsyncQueryEngine`` **open-loop**: request arrival
times are drawn from a Poisson process at a fixed offered rate and each
request is submitted at its scheduled instant *regardless of how the
server is doing* — late submission (the generator falling behind) counts
against the measured latency, exactly like a real front end under heavy
traffic.  Per-request latency = completion time − scheduled arrival
time, so p50/p99/p99.9 include queueing, coalescing linger, device
compute, and extract.

Protocol:

1. build the bench-small index (+refine), exact ground truth;
2. measure the **offline closed-loop baseline**: full-batch
   ``DEGIndex.search`` wall-clock QPS (the ``search_pareto.py`` figure
   this engine is held to — acceptance: sustained online QPS within
   1.3x at equal recall@10);
3. boot the async engine, ``warmup()`` (every (bucket, variant) program
   precompiled — no request pays a trace);
4. offered rate = ``rate`` or ``rate_fraction`` × the offline baseline;
   submit for ``duration`` seconds of Poisson arrivals, block for all
   completions;
5. report p50/p99/p99.9 latency, sustained QPS, recall@10, partial /
   deadline-forced-flush counts; write ``BENCH_serving.json`` at the
   repo root (the standing perf trajectory across PRs).

Telemetry (obs/) is part of the protocol: the headline (non-quick) run
drives the same open-loop trace twice — tracing off, then tracing at
sample rate 1.0 with the JSONL query log — and reports the QPS overhead
ratio (the <3% gate of ISSUE 7).  Every traced phase closes the loop:
the query log is reloaded (``obs.querylog.read_query_log``), replayed
into a fresh registry, and the replayed request-latency p50/p99 and
recall@10 must equal the live registry's / the harness's figures
*exactly* — bench and prod share one measurement path, and the log is
proven to carry it.  The final registry snapshot lands in
``reports/serving_metrics.json``.

``quick=True`` (the CI smoke gate) shrinks everything, pins the seed,
runs one traced phase (timing-ratio gates are too flaky for shared
runners), and enforces the floors: recall@10 >= ``recall_floor`` (the
differential-grid float32 floor), p99 <= ``p99_floor_ms`` (a generous
bound — the gate catches an engine that stops batching or retraces per
request, not millisecond regressions), plus the exact query-log
round-trip equalities.

``--burst`` (:func:`run_burst`) is the overload protocol: the same
open-loop driver against a *bounded* engine (``max_queue`` +
degradation ladder armed), first uncontended (0.5x the offline
baseline) and then at 2x — every submission must end in exactly one of
served / typed ``OverloadError`` shed / typed crash, nothing may hang,
shed rejections must come back within the deadline, and the recall@10
of degraded-mode responses must hold the 0.95 floor.  Counts and
degraded recall land in the ``burst`` section of the commit's
``BENCH_serving.json`` entry.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro.configs.deg import DEG_PAPER_CONFIGS
from repro.core.build import build_deg
from repro.core.metrics import recall_at_k
from repro.obs import (LATENCY_METRIC, MetricsRegistry, QueryLogWriter,
                       clock, read_query_log, recall_from_log,
                       replay_registry)

from .common import emit, make_bench_dataset, write_bench_json


#: the CI smoke configuration (deterministic seed, small index, short
#: duration, un-overloaded rate) — shared by ``--quick`` and
#: ``benchmarks.run``'s QUICK_OVERRIDES so the gate is one config.
#: multi-e2-l64 is the saturated-recall preset (PR 4's headline point),
#: which is what the 0.95 differential-grid float32 floor pins.
QUICK_CONFIG = dict(n=1500, n_query=128, duration=1.5, refine=100,
                    search_preset="multi-e2-l64", max_batch=64,
                    bucket_floor=16, deadline_ms=400.0,
                    rate_fraction=0.6, quick=True)


def _percentiles(lats_ms: np.ndarray) -> dict:
    return {
        "p50_ms": float(np.percentile(lats_ms, 50)),
        "p99_ms": float(np.percentile(lats_ms, 99)),
        "p999_ms": float(np.percentile(lats_ms, 99.9)),
        "max_ms": float(lats_ms.max()),
    }


def run(n: int = 6000, n_query: int = 256, dim: int = 32, k: int = 10,
        eps: float = 0.1, seed: int = 0, refine: int = 300,
        search_preset: str = "multi-e2-l64", max_batch: int = 128,
        bucket_floor: int = 32, deadline_ms: float = 600.0,
        linger_ms: float = 4.0, partial_hops: int = 8,
        rate: float | None = None, rate_fraction: float = 0.85,
        duration: float = 6.0, max_requests: int = 20000,
        quick: bool = False, p99_floor_ms: float = 1000.0,
        recall_floor: float = 0.95) -> dict:
    from repro.serving.async_engine import AsyncQueryEngine

    from repro.configs.deg import SEARCH_PRESETS

    ds = make_bench_dataset("bench-small", n, n_query, dim, "low", k=k,
                            seed=seed)
    params = DEG_PAPER_CONFIGS["bench-small"]
    idx = build_deg(ds.base, params, wave_size=16)
    if refine:
        idx.refine(refine, seed=seed)

    # -- offline closed-loop baseline (the search_pareto protocol, same
    # search program as the engine will serve — equal-recall comparison) --
    sp = SEARCH_PRESETS[search_preset]

    def offline(qs):
        res = idx.search(qs, k=k, eps=eps, beam_width=sp.beam_width,
                         expand_width=sp.expand_width,
                         visited_size=sp.visited_size,
                         hop_backend=sp.hop_backend)
        jax.block_until_ready(res.ids)
        return res

    offline(ds.queries)                       # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        res = offline(ds.queries)
        best = min(best, time.perf_counter() - t0)
    offline_qps = n_query / best
    offline_recall = recall_at_k(np.asarray(res.ids)[:, :k],
                                 ds.gt_ids[:, :k])
    emit("serving_offline_baseline", dataset=ds.name, qps=offline_qps,
         recall=offline_recall, batch=n_query)

    # -- the async engine under open-loop Poisson load --------------------
    offered = rate if rate is not None else rate_fraction * offline_qps
    rng = np.random.default_rng(seed)
    n_req = int(min(offered * duration, max_requests))
    if n_req < 32:
        n_req = 32
    inter = rng.exponential(1.0 / offered, size=n_req)
    arrivals = np.cumsum(inter)               # scheduled instants
    q_idx = rng.integers(0, n_query, size=n_req)

    engine_cfg = dict(k=k, eps=eps, preset=search_preset,
                      max_batch=max_batch, bucket_floor=bucket_floor,
                      deadline_ms=deadline_ms, linger_ms=linger_ms,
                      partial_hops=partial_hops)

    def drive(eng):
        """One open-loop pass over the precomputed arrival schedule.

        Returns (futures, wall seconds, exact per-request latency ms).
        clock.now() (perf_counter) on both sides of the subtraction —
        AsyncResult stamps come from the same clock (obs/clock.py)."""
        futs = []
        t_start = clock.now()
        for i in range(n_req):
            # open loop: sleep only when ahead of schedule; when behind,
            # fire immediately — the backlog shows up as latency, never
            # as a lower offered rate
            lag = arrivals[i] - (clock.now() - t_start)
            if lag > 0:
                time.sleep(lag)
            futs.append(eng.submit(ds.queries[q_idx[i]]))
        for f in futs:
            f.result(timeout=300.0)
        t_last = clock.now() - t_start
        # latency vs the *scheduled* arrival (open-loop convention)
        lats_ms = np.array([
            (f.completed_at - (t_start + arrivals[i])) * 1e3
            for i, f in enumerate(futs)])
        return futs, t_last, lats_ms

    def phase_recall(futs):
        full = [i for i, f in enumerate(futs) if not f.partial]
        if not full:   # partial (deadline-shed) results are load-shedding
            return 0.0, full          # by design, not a recall sample
        got = np.stack([futs[i].ids for i in full])
        return recall_at_k(got[:, :k], ds.gt_ids[q_idx[full]][:, :k]), full

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    reports = os.path.join(root, "reports")
    os.makedirs(reports, exist_ok=True)

    # Phase A (headline runs only): tracing *off* — the baseline QPS the
    # <3% telemetry-overhead gate is measured against.  Quick/CI skips it:
    # a wall-clock ratio on a shared runner is noise, and the quick gates
    # are the deterministic round-trip equalities below.
    base_sustained = None
    if not quick:
        eng0 = AsyncQueryEngine(idx, **engine_cfg)
        eng0.warmup()
        _, t_last0, lats0 = drive(eng0)
        eng0.close()
        base_sustained = n_req / t_last0
        emit("serving_untraced_baseline", sustained_qps=base_sustained,
             p99_ms=float(np.percentile(lats0, 99)))

    # Phase B: tracing at sample rate 1.0 + the structured query log —
    # the instrumented run all reported figures come from.
    qlog_path = os.path.join(reports, "serving_querylog.jsonl")
    for seg in [qlog_path] + [f"{qlog_path}.{j}" for j in range(1, 9)]:
        if os.path.exists(seg):
            os.remove(seg)            # fresh log: round trip counts it
    registry = MetricsRegistry()
    qlog = QueryLogWriter(qlog_path)
    eng = AsyncQueryEngine(idx, metrics=registry, trace_sample=1.0,
                           query_log=qlog, **engine_cfg)
    t0 = time.perf_counter()
    compile_times = eng.warmup()
    warmup_s = time.perf_counter() - t0
    emit("serving_warmup", programs=len(compile_times), seconds=warmup_s,
         slowest_ms=max(compile_times.values()) * 1e3)

    futs, t_last, lats_ms = drive(eng)
    eng.close()
    qlog.close()

    pct = _percentiles(lats_ms)
    sustained = n_req / t_last
    rec, full = phase_recall(futs)
    st = eng.stats
    lat_hist = registry.histogram(LATENCY_METRIC)
    overhead_pct = (None if base_sustained is None else
                    (base_sustained - sustained) / base_sustained * 100.0)
    row = emit("serving_open_loop", dataset=ds.name,
               preset=search_preset, offered_qps=offered,
               sustained_qps=sustained, recall=rec,
               online_vs_offline=offline_qps / max(sustained, 1e-9),
               partials=st.partials, forced_flushes=st.forced_flushes,
               flushes=st.flushes, requests=n_req,
               engine_p50_ms=lat_hist.percentile(50),
               engine_p99_ms=lat_hist.percentile(99), **pct)
    if overhead_pct is not None:
        emit("serving_trace_overhead", untraced_qps=base_sustained,
             traced_qps=sustained, overhead_pct=overhead_pct,
             gate_pct=3.0)

    # -- query-log round trip: the log must carry the measurement ---------
    # Reload the JSONL, replay it into a *fresh* registry, and demand the
    # replayed request-latency histogram and recall@k equal the live
    # figures exactly — deterministic (bucket counts and set-intersection
    # recall are pure functions of the records), so asserted on every
    # run including CI.
    recs = read_query_log(qlog_path)
    assert len(recs) == n_req, (
        f"query log has {len(recs)} records for {n_req} requests "
        f"(trace_sample=1.0 must log every query)")
    replayed = replay_registry(recs).histogram(LATENCY_METRIC)
    assert replayed.counts == lat_hist.counts, (
        "replayed latency histogram != live registry histogram")
    assert (replayed.percentile(50), replayed.percentile(99)) == \
        (lat_hist.percentile(50), lat_hist.percentile(99))
    log_rec = recall_from_log(recs, lambda qid: ds.gt_ids[q_idx[qid]][:k],
                              k)
    assert abs(log_rec - rec) < 1e-12, (
        f"query-log recall {log_rec} != harness recall {rec}")
    emit("serving_log_roundtrip", records=len(recs),
         replay_p50_ms=replayed.percentile(50),
         replay_p99_ms=replayed.percentile(99), replay_recall=log_rec)

    # registry snapshot, for reading the counters offline
    metrics_path = os.path.join(reports, "serving_metrics.json")
    with open(metrics_path, "w") as f:
        f.write(registry.snapshot_json())
        f.write("\n")

    write_bench_json("serving", {
        "dataset": ds.name,
        "config": {
            "n": n, "n_query": n_query, "dim": dim, "k": k, "eps": eps,
            "seed": seed, "refine": refine, "search_preset": search_preset,
            "max_batch": max_batch, "bucket_floor": bucket_floor,
            "deadline_ms": deadline_ms, "linger_ms": linger_ms,
            "partial_hops": partial_hops, "duration": duration,
            "quick": quick,
        },
        "offered_qps": offered, "sustained_qps": sustained,
        "offline_qps": offline_qps, "offline_recall": offline_recall,
        "online_vs_offline": offline_qps / max(sustained, 1e-9),
        "recall_at_10": rec, "requests": n_req,
        "partials": st.partials, "forced_flushes": st.forced_flushes,
        "flushes": st.flushes, "bucket_hist": {
            str(b): c for b, c in sorted(st.bucket_hist.items())},
        "warmup_programs": len(compile_times), "warmup_s": warmup_s,
        "engine_p50_ms": lat_hist.percentile(50),
        "engine_p99_ms": lat_hist.percentile(99),
        "untraced_qps": base_sustained,
        "trace_overhead_pct": overhead_pct,
        "query_log_records": len(recs),
        **pct,
    })

    summary = dict(offered_qps=offered, sustained_qps=sustained,
                   offline_qps=offline_qps, recall=rec,
                   p50_ms=pct["p50_ms"], p99_ms=pct["p99_ms"],
                   p999_ms=pct["p999_ms"], partials=st.partials,
                   trace_overhead_pct=overhead_pct)
    if quick:
        # CI smoke gates (generous floors — catch an engine that stopped
        # batching / retraced per request, not shared-runner jitter)
        assert rec >= recall_floor, (
            f"serving recall@{k}={rec:.4f} under the pinned floor "
            f"{recall_floor} (differential-grid float32 floor)")
        assert pct["p99_ms"] <= p99_floor_ms, (
            f"serving p99={pct['p99_ms']:.1f}ms over the {p99_floor_ms}ms "
            f"smoke floor")
    return summary


#: the --quick --burst configuration (the chaos-smoke CI job's gate).
QUICK_BURST_CONFIG = dict(n=1500, n_query=128, duration=1.25, refine=100,
                          search_preset="multi-e2-l64", max_batch=64,
                          bucket_floor=16, deadline_ms=400.0, quick=True)


def run_burst(n: int = 6000, n_query: int = 256, dim: int = 32, k: int = 10,
              eps: float = 0.1, seed: int = 0, refine: int = 300,
              search_preset: str = "multi-e2-l64", max_batch: int = 128,
              bucket_floor: int = 32, deadline_ms: float = 600.0,
              linger_ms: float = 4.0, partial_hops: int = 8,
              max_queue: int | None = None, shed_policy: str = "reject",
              burst_factor: float = 2.0, duration: float = 4.0,
              max_requests: int = 20000, quick: bool = False,
              degraded_recall_floor: float = 0.95) -> dict:
    """Overload protocol: drive the bounded engine uncontended, then at
    ``burst_factor`` x the offline closed-loop baseline, and account for
    every submission.  See the module docstring for the gates."""
    from repro.resilience import EngineCrashedError, OverloadError
    from repro.serving.async_engine import AsyncQueryEngine

    from repro.configs.deg import SEARCH_PRESETS

    ds = make_bench_dataset("bench-small", n, n_query, dim, "low", k=k,
                            seed=seed)
    params = DEG_PAPER_CONFIGS["bench-small"]
    idx = build_deg(ds.base, params, wave_size=16)
    if refine:
        idx.refine(refine, seed=seed)

    sp = SEARCH_PRESETS[search_preset]

    def offline(qs):
        res = idx.search(qs, k=k, eps=eps, beam_width=sp.beam_width,
                         expand_width=sp.expand_width,
                         visited_size=sp.visited_size,
                         hop_backend=sp.hop_backend)
        jax.block_until_ready(res.ids)
        return res

    offline(ds.queries)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        offline(ds.queries)
        best = min(best, time.perf_counter() - t0)
    offline_qps = n_query / best

    if max_queue is None:
        max_queue = 4 * max_batch
    eng = AsyncQueryEngine(idx, k=k, eps=eps, preset=search_preset,
                           max_batch=max_batch, bucket_floor=bucket_floor,
                           deadline_ms=deadline_ms, linger_ms=linger_ms,
                           partial_hops=partial_hops, max_queue=max_queue,
                           shed_policy=shed_policy, degrade=True)
    eng.warmup()

    rng = np.random.default_rng(seed)

    def drive_typed(offered):
        """Open-loop pass where every submission is accounted to exactly
        one typed outcome: served / shed / crashed / hung."""
        n_req = max(32, int(min(offered * duration, max_requests)))
        arrivals = np.cumsum(rng.exponential(1.0 / offered, size=n_req))
        q_idx = rng.integers(0, n_query, size=n_req)
        pend = []                      # (arrival, submit_t, future)
        served, shed, crashed, hung = [], [], [], 0
        t_start = clock.now()
        for i in range(n_req):
            lag = arrivals[i] - (clock.now() - t_start)
            if lag > 0:
                time.sleep(lag)
            t_sub = clock.now()
            try:
                fut = eng.submit(ds.queries[q_idx[i]])
            except OverloadError:
                shed.append(clock.now() - t_sub)   # time to typed reject
                continue
            except EngineCrashedError:
                crashed.append(i)
                continue
            pend.append((i, t_sub, fut))
        for i, t_sub, fut in pend:
            try:
                fut.result(timeout=120.0)
            except TimeoutError:       # a hung future — the satellite bug
                hung += 1
                continue
            except OverloadError:      # drop-policy eviction from the queue
                shed.append(fut.completed_at - t_sub)
                continue
            except EngineCrashedError:
                crashed.append(i)
                continue
            served.append((i, q_idx[i], fut,
                           fut.completed_at - (t_start + arrivals[i])))
        assert len(served) + len(shed) + len(crashed) + hung == n_req, \
            "submission accounting leak — an outcome was double/un-counted"
        return n_req, served, shed, crashed, hung

    def served_recall(served, degraded_only):
        rows = [(qi, f) for _, qi, f, _ in served
                if not f.partial and (f.degraded if degraded_only else True)]
        if not rows:
            return None
        got = np.stack([f.ids for _, f in rows])
        gt = ds.gt_ids[np.array([qi for qi, _ in rows])]
        return recall_at_k(got[:, :k], gt[:, :k])

    # Phase 1: uncontended — the p99 yardstick the burst is held to.
    n0, served0, shed0, crashed0, hung0 = drive_typed(0.5 * offline_qps)
    lats0 = np.array([s[3] for s in served0]) * 1e3
    base_p99 = float(np.percentile(lats0, 99))
    emit("serving_burst_uncontended", offered_qps=0.5 * offline_qps,
         served=len(served0), shed=len(shed0), p99_ms=base_p99)

    # Phase 2: the burst — burst_factor x the offline closed-loop QPS.
    offered = burst_factor * offline_qps
    n1, served1, shed1, crashed1, hung1 = drive_typed(offered)
    peak_level = eng.health()["degrade_level"]
    eng.close()

    lats1 = np.array([s[3] for s in served1]) * 1e3 if served1 else \
        np.array([0.0])
    burst_p99 = float(np.percentile(lats1, 99))
    degraded_served = sum(1 for _, _, f, _ in served1 if f.degraded)
    rec_all = served_recall(served1, degraded_only=False)
    rec_degraded = served_recall(served1, degraded_only=True)
    max_reject_ms = max((t * 1e3 for t in shed0 + shed1), default=0.0)

    row = emit("serving_burst", offered_qps=offered,
               requests=n1, served=len(served1), shed=len(shed1),
               crashed=len(crashed1), hung=hung1,
               degraded=degraded_served, degrade_level=peak_level,
               recall=rec_all, degraded_recall=rec_degraded,
               p99_ms=burst_p99, uncontended_p99_ms=base_p99,
               max_reject_ms=max_reject_ms)

    # -- the resilience gates (every run, quick included, except the
    # wall-clock p99 ratio which is too noisy for shared runners) --------
    assert hung0 + hung1 == 0, (
        f"{hung0 + hung1} requests hung past the timeout — every submit "
        "must resolve to a result or a typed error")
    assert not crashed0 and not crashed1, (
        f"engine crashed under overload ({len(crashed0) + len(crashed1)} "
        "typed crash errors) — shedding must protect the loops")
    assert len(shed1) + degraded_served > 0, (
        f"burst at {burst_factor}x offered neither shed nor degraded — "
        "the bounded queue/ladder never engaged (overload not exercised)")
    assert max_reject_ms <= deadline_ms, (
        f"slowest typed rejection took {max_reject_ms:.1f}ms — sheds must "
        f"come back within the {deadline_ms}ms deadline, not after it")
    if rec_degraded is not None:
        assert rec_degraded >= degraded_recall_floor, (
            f"degraded-mode recall@{k}={rec_degraded:.4f} under the "
            f"{degraded_recall_floor} floor — the ladder traded too much "
            "accuracy for throughput")
    if not quick:
        assert burst_p99 <= 2.0 * base_p99, (
            f"burst p99={burst_p99:.1f}ms > 2x uncontended "
            f"p99={base_p99:.1f}ms — served requests must stay fast while "
            "the overflow sheds")

    write_bench_json("serving", {"burst": {
        "offered_qps": offered, "offline_qps": offline_qps,
        "burst_factor": burst_factor, "max_queue": max_queue,
        "shed_policy": shed_policy, "requests": n1,
        "served": len(served1), "shed": len(shed1),
        "crashed": len(crashed1), "hung": hung1,
        "degraded": degraded_served,
        "recall_at_10": rec_all, "degraded_recall_at_10": rec_degraded,
        "p99_ms": burst_p99, "uncontended_p99_ms": base_p99,
        "max_reject_ms": max_reject_ms, "quick": quick,
    }}, merge=True)

    return dict(requests=n1, served=len(served1), shed=len(shed1),
                degraded=degraded_served, hung=hung1,
                recall=rec_all, degraded_recall=rec_degraded,
                p99_ms=burst_p99, uncontended_p99_ms=base_p99)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small index, short duration, deterministic seed, "
                    "recall/p99 floors enforced (the CI smoke gate)")
    ap.add_argument("--burst", action="store_true",
                    help="run the overload protocol instead: bounded "
                    "queue + degradation ladder at 2x offered load, "
                    "typed-outcome accounting (the chaos-smoke gate)")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered QPS (default: 0.8x the measured offline "
                    "closed-loop baseline)")
    ap.add_argument("--duration", type=float, default=4.0)
    a = ap.parse_args()
    if a.burst:
        cfg = dict(QUICK_BURST_CONFIG) if a.quick else \
            dict(duration=a.duration)
        print(run_burst(**cfg))
    elif a.quick:
        print(run(**dict(QUICK_CONFIG, rate=a.rate)))
    else:
        print(run(rate=a.rate, duration=a.duration))
