#!/usr/bin/env python3
"""Bring-up smoke test: the DEG build-and-serve path on a TPU chip.

Run from the root of a checkout, in one process that owns the chip:

    python chip_smoke.py              # one chip: build, serve, explore,
                                      # mutate live, fused hop,
                                      # the table's layout
    python chip_smoke.py --chips 4    # four chips: the sharded index only

The configuration is the paper's ``audio`` deployment (Table 2/3: 53,387
base vectors, dim 192, l2; ``DEG_PAPER_CONFIGS["audio"]``: degree 20,
k_ext 40, eps_ext 0.3) over a seeded Gaussian mixture of that shape
(``data/synthetic.make_dataset``); nothing is downloaded.  Every recall is
judged against the exact brute force (``core/distances.exact_knn_batched``,
``Precision.HIGHEST`` matmuls).

Each phase prints one line of its numbers; any failed check raises and the
process exits nonzero without the final line.  The final line is one JSON
object naming the device.  Without a TPU the script fails at once: it
never falls back to the CPU.  The persistent compile cache lives where
``launch/compile_cache.py`` says (``JAX_COMPILATION_CACHE_DIR`` or
``.jax_cache/``), so a second run compiles less.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch import compile_cache  # noqa: E402

# -- the deployment --------------------------------------------------------
N_BASE = 53387        # paper Table 2, audio
DIM = 192
N_QUERY = 1000
N_INSERT = 1000       # live-mutation phase
N_EXPLORE = 256
K = 10
WAVE = 16
REFINE_ITERS = 4000
# Search settings and recall floors.  The corpus is 32 isotropic 192-d
# clusters, so the beam must be wide and the graph refined: on the CPU at
# the full shape this script's serve phase gave recall@10 0.795
# unrefined, 0.956 after 1000 refine iterations, 0.990 after 2000 and
# 0.998 after 4000 (all at L=768, eps=0.2).  Builds differ in rounding
# from one backend to the next: on a v5e chip, 2000 iterations served
# 0.944 where the CPU's served 0.982 (4000 served 0.990), so the floors
# leave room for that spread.
BEAM = 768
EPS = 0.2
FLOOR = 0.93
# exploration asks for the neighbours of an indexed vertex other than
# itself (0.977 on the CPU at 2000 iterations); approximate search may
# miss a few inserted vectors as their own top hit (0.990 there)
EXPLORE_FLOOR = 0.90
READ_BACK_FLOOR = 0.95
FUSED_PRESET, TWIN_PRESET = "multi-e4-fused", "multi-e4"
# sharded path: four round-robin shards of the same corpus, each refined
# 500 iterations (recall@10 0.999 at L=256 on the CPU with four virtual
# devices, 0.994 on four v5e chips)
SHARD_REFINE = 500
SHARD_BEAM = 256
SHARD_FLOOR = 0.93


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileLog:
    """Seconds spent in backend compiles (cache retrievals included) and
    persistent-cache hits/misses, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def recall_vs(found, gt) -> float:
    from repro.core.metrics import recall_at_k

    return recall_at_k(np.asarray(found), np.asarray(gt))


def agreement(a, b) -> float:
    """Mean share of result ids two searches agree on, per query."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.mean([len(set(x.tolist()) & set(y.tolist())) / len(x)
                          for x, y in zip(a, b)]))


def exact_topk(queries, base, k):
    from repro.core.distances import exact_knn_batched

    return exact_knn_batched(np.asarray(queries), np.asarray(base), k)[1]


def dataset(n: int, seed: int):
    from repro.data.synthetic import make_dataset

    pts, queries = make_dataset("gaussian", n + N_INSERT, N_QUERY, DIM,
                                seed=seed)
    return pts[:n], pts[n:], queries


def audio_params():
    import dataclasses

    from repro.configs.deg import DEG_PAPER_CONFIGS

    return dataclasses.replace(DEG_PAPER_CONFIGS["audio"])


def check_table1(builder, phase: str) -> None:
    from repro.core.invariants import check_invariants

    ok, problems = check_invariants(builder)
    say(phase, even_regular_undirected_connected=ok)
    check(ok, f"Table 1 invariants broken: {problems}")


def serve_all(eng, queries, timeout: float = 600.0, **submit_kw):
    futs = [eng.submit(q, **submit_kw) for q in queries]
    outs = [f.result(timeout) for f in futs]
    return futs, np.stack([o[0] for o in outs])


def check_engine(eng, what: str) -> None:
    st, h = eng.stats, eng.health()
    check(st.shed == 0 and st.invalid == 0 and st.crashes == 0
          and st.restarts == 0 and st.partials == 0,
          f"{what}: shed={st.shed} invalid={st.invalid} "
          f"crashes={st.crashes} restarts={st.restarts} "
          f"partials={st.partials}")
    check(h["status"] == "ok", f"{what}: health {h}")


def engine(idx, **kw):
    """Async engine in the smoke's shape: one 64-lane bucket, no deadline
    (a smoke checks answers, not an SLO)."""
    from repro.serving.async_engine import AsyncQueryEngine

    return AsyncQueryEngine(idx, k=K, eps=EPS, beam_width=BEAM,
                            max_batch=64, bucket_floor=64, deadline_ms=None,
                            **kw)


# -- one chip --------------------------------------------------------------
def run_one_chip(n: int, seed: int, assert_kernel=None) -> None:
    from repro.analysis.hlo import result_copies
    from repro.core.build import build_deg
    from repro.core.search import range_search, table_format
    from repro.core.visited import default_size

    base, extra, queries = dataset(n, seed)

    # -- build -------------------------------------------------------------
    t0 = time.perf_counter()
    idx = build_deg(base, audio_params(), wave_size=WAVE,
                    capacity=n + N_INSERT)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    improved = idx.refine(REFINE_ITERS, seed=seed)
    refine_s = time.perf_counter() - t0
    say("build", n=idx.n, dim=DIM, degree=idx.params.degree, wave=WAVE,
        build_s=f"{build_s:.1f}", inserts_per_s=f"{idx.n / build_s:.0f}",
        refine_iterations=REFINE_ITERS, refine_s=f"{refine_s:.1f}",
        improved_edges=improved)
    check(idx.n == n, f"built {idx.n} of {n} vertices")
    check_table1(idx.builder, "invariants")

    gt = exact_topk(queries, base, K)

    # -- serve -------------------------------------------------------------
    with engine(idx) as eng:
        t0 = time.perf_counter()
        eng.warmup()
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, found = serve_all(eng, queries)
        wall = time.perf_counter() - t0
        check_engine(eng, "serve")
        rec = recall_vs(found, gt)
        say("serve", queries=len(queries), flushes=eng.stats.flushes,
            warmup_s=f"{warm_s:.1f}", wall_s=f"{wall:.2f}",
            beam=BEAM, eps=EPS, recall_at_10=f"{rec:.4f}", floor=FLOOR)
        check(rec >= FLOOR, f"serve recall@10 {rec:.4f} < floor {FLOOR}")

        # -- exploration (paper Sec. 6.7) ----------------------------------
        rng = np.random.default_rng(seed)
        sv = rng.choice(n, N_EXPLORE, replace=False)
        futs = [eng.submit(base[v], seed_vertex=int(v), exclude=[int(v)])
                for v in sv]
        found = np.stack([f.result(600.0)[0] for f in futs])
        check(not (found == sv[:, None]).any(),
              "an exploration query returned its own vertex")
        gt_x = exact_topk(base[sv], base, K + 1)
        gt_x = np.stack([row[row != v][:K] for row, v in zip(gt_x, sv)])
        rec_x = recall_vs(found, gt_x)
        check_engine(eng, "explore")
        say("explore", queries=N_EXPLORE, self_returned=0,
            recall_at_10_without_self=f"{rec_x:.4f}", floor=EXPLORE_FLOOR)
        check(rec_x >= EXPLORE_FLOOR, f"exploration recall@10 {rec_x:.4f} "
                                      f"< floor {EXPLORE_FLOOR}")

        # -- live mutation: inserts + publish while the engine serves ------
        epochs_mgr = idx.enable_publishing()
        first_epoch = epochs_mgr.current.epoch
        writer_errors = []

        def writer():
            try:
                for i in range(0, N_INSERT, 64):
                    idx.add(extra[i:i + 64], wave_size=WAVE)
                    idx.publish()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                writer_errors.append(e)

        w = threading.Thread(target=writer, name="smoke-writer", daemon=True)
        w.start()
        epochs, rounds = set(), 0
        while w.is_alive() or rounds == 0:
            futs, _ = serve_all(eng, queries[rounds % 8::8][:64])
            epochs.update(f.epoch for f in futs)
            rounds += 1
        w.join()
        if writer_errors:
            raise writer_errors[0]
        check(None not in epochs, "a result carried no epoch")
        last = epochs_mgr.current.epoch
        check_engine(eng, "mutate")
        check_table1(idx.builder, "invariants_after_mutation")
        # every acknowledged insert is in the last published epoch, and
        # searching for it finds it
        view = idx.acquire_view()
        try:
            stored = np.asarray(view.vectors[n:n + N_INSERT])
            check(view.n == n + N_INSERT and np.array_equal(stored, extra),
                  f"published epoch holds n={view.n}, inserted rows "
                  f"{'match' if np.array_equal(stored, extra) else 'differ'}")
        finally:
            idx.release_view(view)
        futs, found = serve_all(eng, extra)
        read_back = float(np.mean(found[:, 0] == n + np.arange(N_INSERT)))
        say("mutate", inserted=N_INSERT, n=idx.n, epochs_published=(
            last - first_epoch), epochs_served=len(epochs),
            served_rounds=rounds, stored_rows_exact=True,
            read_back_top1=f"{read_back:.4f}", floor=READ_BACK_FLOOR)
        check(read_back >= READ_BACK_FLOOR, f"inserted vectors found as "
              f"their own top hit {read_back:.4f} < {READ_BACK_FLOOR}")
    all_pts = np.concatenate([base, extra])
    gt_all = exact_topk(queries, all_pts, K)

    # -- fused hop vs its jnp twin ------------------------------------------
    vs = default_size(BEAM, idx.params.degree)
    results = {}
    for preset, hop in ((FUSED_PRESET, "pallas"), (TWIN_PRESET, "jnp")):
        with engine(idx, preset=preset, visited_size=vs) as eng:
            eng.warmup()
            _, found = serve_all(eng, queries)
            check_engine(eng, preset)
        results[preset] = found
        view = idx.acquire_view()
        try:
            text = range_search.lower(
                view.graph, view.vectors, queries[:64].astype(np.float32),
                np.full((64, 1), view.medoid(), np.int32), k=K, eps=EPS,
                beam_width=BEAM, expand_width=4, visited_size=vs,
                hop_backend=hop).compile().as_text()
        finally:
            idx.release_view(view)
        if hop == "pallas":
            (assert_kernel or _assert_tpu_kernel)(text)
    rf = recall_vs(results[FUSED_PRESET], gt_all)
    rt = recall_vs(results[TWIN_PRESET], gt_all)
    agree = agreement(results[FUSED_PRESET], results[TWIN_PRESET])
    say("fused_hop", preset=FUSED_PRESET, twin=TWIN_PRESET,
        recall_fused=f"{rf:.4f}", recall_twin=f"{rt:.4f}",
        id_agreement=f"{agree:.4f}", tpu_custom_call=True)
    check(abs(rf - rt) <= 0.01,
          f"fused recall {rf:.4f} vs jnp twin {rt:.4f} differ > 0.01")

    # -- the table's layout: the beam reads it in place or staged on chip --
    p = idx.params
    table = idx._dev_vectors
    text = range_search.lower(
        idx.frozen(), table, extra[:1], np.array([[0, 1]], np.int32),
        k=p.k_opt, eps=p.eps_opt).compile().as_text()
    copies = result_copies(text, "f32", table.shape)
    layout = table.format.layout
    staged = table_format(table.sharding, table.shape).layout is None
    say("table_layout", shape="x".join(map(str, table.shape)),
        major_to_minor=",".join(map(str, layout.major_to_minor)),
        tiling=str(layout.tiling).replace(" ", ""), staged_on_chip=staged,
        table_sized_copies=len(copies))
    if staged:
        check(copies and all("S(1)" in t for _, t in copies),
              f"a table that fits on chip is not staged there: {copies}")
    else:
        check(not copies, f"the single-lane search copies the whole "
                          f"table: {copies}")

def _assert_tpu_kernel(program_text: str) -> None:
    check("tpu_custom_call" in program_text,
          "the fused-hop program holds no tpu_custom_call")


# -- four chips: the sharded index -----------------------------------------
def run_sharded(n: int, seed: int, log: "CompileLog",
                n_shards: int = 4) -> None:
    from repro.distributed.index import build_sharded_deg
    from repro.launch.mesh import make_shard_mesh

    base, _, queries = dataset(n, seed)
    mesh = make_shard_mesh(n_shards)
    t0 = time.perf_counter()
    sd = build_sharded_deg(base, n_shards, audio_params(), wave_size=WAVE,
                           refine_iterations=SHARD_REFINE)
    build_s = time.perf_counter() - t0
    sd.place(mesh)
    holders = {s.device for s in sd.vectors.addressable_shards}
    say("sharded_build", n=n, shards=n_shards, build_s=f"{build_s:.1f}",
        inserts_per_s=f"{n / build_s:.0f}", devices_holding_vectors=len(
            holders), sharding=str(sd.vectors.sharding.spec).replace(" ", ""))
    check(len(holders) == n_shards,
          f"shard arrays sit on {len(holders)} devices, not {n_shards}")
    for s, sh in enumerate(sd.shards):
        check_table1(sh.builder, f"invariants_shard{s}")

    gt = exact_topk(queries, base, K)
    ids, _ = sd.search(mesh, queries, k=K, eps=EPS, beam_width=SHARD_BEAM)
    before = log.programs
    ids2, _ = sd.search(mesh, queries, k=K, eps=EPS, beam_width=SHARD_BEAM)
    recompiles = log.programs - before
    check(recompiles == 0, f"a repeated sharded search compiled "
                           f"{recompiles} programs")
    check(np.array_equal(ids, ids2), "repeated sharded search differs")
    rec = recall_vs(ids, gt)

    # host merge of the shards' own single-device searches
    cand_ids, cand_d = [], []
    for s, sh in enumerate(sd.shards):
        r = sh.search_batch(queries, None, k=K, eps=EPS,
                            beam_width=SHARD_BEAM)
        local = np.asarray(r.ids)
        cand_ids.append(np.where(local < 0, -1, local * n_shards + s))
        cand_d.append(np.asarray(r.dists))
    cand_ids = np.concatenate(cand_ids, axis=1)
    cand_d = np.concatenate(cand_d, axis=1)
    order = np.argsort(cand_d, axis=1, kind="stable")[:, :K]
    merged = np.take_along_axis(cand_ids, order, axis=1)
    rec_m = recall_vs(merged, gt)
    agree = agreement(ids, merged)
    say("sharded_search", queries=len(queries), beam=SHARD_BEAM, eps=EPS,
        recall_at_10=f"{rec:.4f}", floor=SHARD_FLOOR,
        host_merge_recall=f"{rec_m:.4f}", id_agreement=f"{agree:.4f}",
        recompiles_on_repeat=recompiles)
    check(rec >= SHARD_FLOOR,
          f"sharded recall@10 {rec:.4f} < floor {SHARD_FLOOR}")
    check(abs(rec - rec_m) <= 0.01 and agree >= 0.99,
          f"sharded search vs host merge: recall {rec:.4f}/{rec_m:.4f}, "
          f"agreement {agree:.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path; 4: the sharded index only")
    ap.add_argument("--n", type=int, default=N_BASE,
                    help="corpus rows (cut only if the build cannot fit "
                    "the run; dim and degree never change)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable()
    import jax

    log = CompileLog()
    devs = jax.devices()
    d0 = devs[0]
    say("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs))
    check(d0.platform == "tpu",
          f"no TPU: JAX sees platform {d0.platform!r} — the smoke never "
          "falls back to the CPU")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX sees "
          f"{len(devs)}")
    if args.n != N_BASE:
        say("cut", n=args.n, of=N_BASE, note="corpus rows only; dim and "
            "degree unchanged")

    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args.n, args.seed)
    else:
        run_sharded(args.n, args.seed, log, n_shards=args.chips)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
             for d in devs[:args.chips]]
    say("memory", peak_bytes_in_use=",".join(str(p) for p in peaks))
    say("compile", seconds=f"{log.seconds:.1f}", programs=log.programs,
        cache_hits=log.hits, cache_misses=log.misses, cache_dir=cache_dir,
        run_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
