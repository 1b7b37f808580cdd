"""Bucketed fixed-shape search programs — the one dispatch path under both
serving engines.

The beam engine compiles one program per operand-shape family, so an
engine that pads every flush to an ad-hoc batch size either retraces
constantly (shape per request count) or always pays the largest batch
(the pre-PR sync flush padded everything to ``max_batch``).  This module
is the middle ground both engines share:

* :func:`bucket_sizes` — the power-of-two batch buckets between
  ``floor`` and ``max_batch``; a flush of B requests is padded to
  ``pow2_bucket(B, floor)``, so steady state compiles at most
  ``len(buckets)`` programs per search configuration;
* :class:`ProgramConfig` — the frozen per-engine search knobs (k/eps/L,
  codec + rerank, multi-expansion E/backend/visited) that, together with
  a bucket, name one compiled program;
* :func:`pad_batch` — request list -> padded (queries, seeds, exclude)
  operands, exclude lanes bucketed to powers of two exactly like the
  sync engine always did;
* :func:`dispatch` — the single ``DEGIndex.search_batch`` call site for
  both ``QueryEngine.flush`` and ``AsyncQueryEngine``.  **Bit-identity
  invariant**: per-lane results do not depend on batch composition (dead
  lanes are no-ops in the lock-step loop), so sync and async flushes of
  the same request produce identical ids/dists no matter how the
  scheduler groups them — buckets change padding, never semantics;
* :func:`precompile` — boot-time warmup: traces and compiles every
  (bucket, variant) program so no live request ever pays a trace
  (``launch/serve.py --warmup``, ``AsyncQueryEngine.warmup``).

Filtered requests (``core/tags.py``) take one of two routes, chosen per
request by :func:`route` from the rows its filter admits: the beam with a
fixed-width filter operand (:func:`pad_filter`), or, where the filter
admits no more rows than the beam is expected to score, an exact scan of
those rows (:func:`pad_scan`, :func:`dispatch_scan`): the scanned
requests' rows in chunks of :data:`SCAN_CHUNK`, bucketed by the chunks'
count.  A flush with no filter dispatches the filter-free program,
as one with no exclusions dispatches the exclusion-free one.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from repro.core.graph import INVALID, pow2_bucket
from repro.core.tags import MAX_FILTER_TAGS
from repro.obs import clock

#: hop budget meaning "unlimited" for non-expired lanes in a budgeted
#: batch (any value above the engine's max_hops bound behaves as no cap)
NO_BUDGET = np.int32(2**31 - 1)

#: rows a chunk of the scan route holds (each request's own chunks)
SCAN_CHUNK = 256


def bucket_sizes(max_batch: int, floor: int = 8) -> tuple[int, ...]:
    """Power-of-two batch buckets covering 1..max_batch flushes."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    floor = max(1, min(floor, max_batch))
    sizes = []
    b = pow2_bucket(floor)
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(pow2_bucket(max_batch))
    return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class ProgramConfig:
    """Everything (besides the batch bucket and the operand values) that
    names one compiled search program.  Built once per engine from its
    constructor arguments / a ``configs.deg.SearchPreset``."""

    k: int = 10
    eps: float = 0.1
    beam_width: Optional[int] = None
    codec: str = "float32"
    rerank_k: Optional[int] = None
    expand_width: Optional[int] = None
    visited_size: Optional[int] = None
    hop_backend: Optional[str] = None

    @classmethod
    def from_preset(cls, preset_name: str, *, k: int = 10, eps: float = 0.1,
                    codec: str = "float32",
                    rerank_k: Optional[int] = None) -> "ProgramConfig":
        from repro.configs.deg import SEARCH_PRESETS

        p = SEARCH_PRESETS[preset_name]
        return cls(k=k, eps=eps, beam_width=p.beam_width, codec=codec,
                   rerank_k=rerank_k, expand_width=p.expand_width,
                   visited_size=p.visited_size, hop_backend=p.hop_backend)


@dataclasses.dataclass
class BatchItem:
    """One request as the dispatch layer sees it: the query vector, the
    already-resolved exclude ids (session history, seed included when the
    protocol wants it hidden), and an optional seed vertex (None = the
    index medoid)."""

    query: np.ndarray
    exclude: Sequence[int] = ()
    seed_vertex: Optional[int] = None
    filter: tuple = ()          # tag ids ANDed, () = every row admitted


def pad_batch(items: Sequence[BatchItem], bucket: int, medoid: int,
              exclude_floor: int = 8
              ) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Request list -> fixed-shape (queries, seeds, exclude) operands.

    Queries are padded to ``bucket`` lanes (pad lanes repeat the first
    query — any in-bounds value; their results are discarded).  A batch
    with no exclusions passes ``exclude=None`` (the exclusion-free
    program); otherwise the exclude width is the batch's need bucketed to
    a power of two above ``exclude_floor``, so one long session never
    permanently widens later flushes."""
    B = len(items)
    if not (0 < B <= bucket):
        raise ValueError(f"batch size {B} does not fit bucket {bucket}")
    qs = np.stack([np.asarray(it.query, np.float32) for it in items]
                  + [np.asarray(items[0].query, np.float32)] * (bucket - B))
    seeds = np.full((bucket, 1), medoid, np.int32)
    max_ex = max((len(it.exclude) for it in items), default=0)
    excl = None
    if max_ex:
        xw = pow2_bucket(max_ex, floor=max(1, exclude_floor))
        excl = np.full((bucket, xw), INVALID, np.int32)
    for i, it in enumerate(items):
        if it.seed_vertex is not None:
            seeds[i, 0] = it.seed_vertex
        if it.exclude:
            excl[i, : len(it.exclude)] = list(it.exclude)
    return qs, seeds, excl


def pad_filter(items: Sequence[BatchItem], bucket: int
               ) -> Optional[np.ndarray]:
    """The (bucket, MAX_FILTER_TAGS) int32 filter operand, INVALID-padded
    (a lane without a filter admits every row), or None when no request
    carries a filter: the filter-free program."""
    if not any(it.filter for it in items):
        return None
    out = np.full((bucket, MAX_FILTER_TAGS), INVALID, np.int32)
    for i, it in enumerate(items):
        out[i, : len(it.filter)] = it.filter
    return out


def route(items: Sequence[BatchItem], view, limit: int
          ) -> tuple[list[int], list[int], list[np.ndarray]]:
    """Split a flush by route: a filtered request whose filter admits at
    most ``limit`` rows of ``view`` is scanned, every other request goes
    to the beam.  Returns (beam positions, scan positions, each scanned
    request's admitted rows, its exclude ids left out)."""
    beam, scan, rows = [], [], []
    for i, it in enumerate(items):
        if it.filter and view.filter_count(it.filter) <= limit:
            r = view.filter_rows(it.filter)
            if len(it.exclude):
                r = r[~np.isin(r, it.exclude)]
            scan.append(i)
            rows.append(r)
        else:
            beam.append(i)
    return beam, scan, rows


def pad_scan(queries: Sequence[np.ndarray], rows: Sequence[np.ndarray],
             lanes: int, chunk_buckets: Sequence[int], lane_chunks: int
             ) -> tuple[np.ndarray, ...]:
    """Scanned requests -> fixed-shape operands of ``filtered_scan``:
    ``lanes`` query lanes; each request's rows in chunks of
    :data:`SCAN_CHUNK`, INVALID-padded, the chunks padded to the first
    chunk bucket that holds them; the lane of each chunk; and each lane's
    chunks, ``lane_chunks`` of them at most (the padding points past the
    chunks)."""
    B = len(queries)
    qs = np.zeros((lanes, len(queries[0])), np.float32)
    qs[:B] = queries
    per = [-(-len(r) // SCAN_CHUNK) for r in rows]
    n = next(b for b in chunk_buckets if b >= sum(per))
    chunks = np.full((n, SCAN_CHUNK), INVALID, np.int32)
    chunk_lane = np.full(n, lanes, np.int32)
    lane_chunk = np.full((lanes, lane_chunks), n, np.int32)
    at = 0
    for i, r in enumerate(rows):
        chunks.reshape(-1)[at * SCAN_CHUNK: at * SCAN_CHUNK + len(r)] = r
        chunk_lane[at: at + per[i]] = i
        lane_chunk[i, : per[i]] = np.arange(at, at + per[i])
        at += per[i]
    return qs, chunks, chunk_lane, lane_chunk


def dispatch(index, cfg: ProgramConfig, qs: np.ndarray, seeds: np.ndarray,
             excl: Optional[np.ndarray],
             hop_budget: Optional[np.ndarray] = None,
             filter_tags: Optional[np.ndarray] = None):
    """The one ``search_batch`` call site both engines flush through.

    ``index`` is whatever ``DEGIndex.acquire_view()`` returned: the index
    itself (single-writer mode) or an immutable
    :class:`repro.core.epoch.PublishedEpoch` (live mutation under
    serving) — both expose the same ``search_batch`` surface, and their
    operand shapes match, so they share the compiled beam programs.
    ``filter_tags`` (:func:`pad_filter`) restricts each lane's results
    to the rows its filter admits."""
    filt = {} if filter_tags is None else {"filter_tags": filter_tags}
    return index.search_batch(
        qs, seeds, excl, k=cfg.k, eps=cfg.eps, beam_width=cfg.beam_width,
        quantized=None if cfg.codec == "float32" else cfg.codec,
        rerank_k=cfg.rerank_k, expand_width=cfg.expand_width,
        visited_size=cfg.visited_size, hop_backend=cfg.hop_backend,
        hop_budget=hop_budget, **filt)


def dispatch_scan(index, cfg: ProgramConfig, *operands):
    """The scan route's call (:func:`pad_scan`'s operands): the exact k
    nearest of each lane's rows."""
    return index.scan_batch(*operands, k=cfg.k)


def precompile(index, cfg: ProgramConfig, buckets: Sequence[int], *,
               with_budget: bool = False) -> dict[tuple, float]:
    """Compile every (bucket[, budgeted]) program before traffic arrives.

    Runs one throwaway flush per shape family and blocks on the result, so
    the trace + compile cost is paid at boot, not by the first request of
    each shape.  Returns ``{(bucket, variant): seconds}`` wall times (the
    figure ``launch/serve.py --warmup`` logs).  ``with_budget`` also
    compiles the deadline-expired variant (the same shapes plus the
    per-lane ``hop_budget`` operand) that a flush containing an expired
    request uses."""
    import jax

    dim = index.dim
    medoid = index.medoid()
    times: dict[tuple, float] = {}
    variants = [("plain", None)]
    if with_budget:
        variants.append(("budget", True))
    for b in buckets:
        items = [BatchItem(query=np.zeros(dim, np.float32))] * b
        qs, seeds, excl = pad_batch(items, b, medoid)
        for name, budgeted in variants:
            budget = (np.full(b, NO_BUDGET, np.int32) if budgeted else None)
            t0 = clock.now()
            res = dispatch(index, cfg, qs, seeds, excl, hop_budget=budget)
            jax.block_until_ready(res.ids)
            times[(b, name)] = clock.now() - t0
    return times


def precompile_filtered(index, cfg: ProgramConfig, buckets: Sequence[int],
                        lanes: int, chunk_buckets: Sequence[int],
                        lane_chunks: int, *,
                        with_budget: bool = False) -> dict[tuple, float]:
    """:func:`precompile` for filtered flushes: the beam with a filter
    operand in every bucket (and budgeted, with ``with_budget``), and the
    scan of ``lanes`` query lanes in every chunk bucket."""
    import jax

    dim = index.dim
    medoid = index.medoid()
    times: dict[tuple, float] = {}
    for b in buckets:
        items = [BatchItem(query=np.zeros(dim, np.float32), filter=(0,))] * b
        qs, seeds, excl = pad_batch(items, b, medoid)
        for budget in [None] + ([np.full(b, NO_BUDGET, np.int32)]
                                if with_budget else []):
            t0 = clock.now()
            res = dispatch(index, cfg, qs, seeds, excl, hop_budget=budget,
                           filter_tags=pad_filter(items, b))
            jax.block_until_ready(res.ids)
            times[(b, "filter" if budget is None else "filter-budget")] = \
                clock.now() - t0
    for n in chunk_buckets:
        t0 = clock.now()
        res = dispatch_scan(index, cfg, np.zeros((lanes, dim), np.float32),
                            np.zeros((n, SCAN_CHUNK), np.int32),
                            np.zeros(n, np.int32),
                            np.zeros((lanes, lane_chunks), np.int32))
        jax.block_until_ready(res.ids)
        times[(lanes, f"scan-{n}")] = clock.now() - t0
    return times
