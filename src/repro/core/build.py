"""Incremental DEG construction (paper Algorithm 3 + Sec. 5.2).

`DEGIndex` is the user-facing object: it owns the host-side mutable graph
(`GraphBuilder`), a host mirror of the vectors, and a device-resident vector
buffer kept in sync with donated in-place row updates.  Construction is
host-orchestrated (graph surgery is inherently sequential, paper Sec. 5.2)
around *jitted, batched* range searches — the compute-heavy part.

Two build modes:

* ``wave_size=1`` — paper-faithful sequential insertion;
* ``wave_size=W`` — beyond-paper bulk build: the candidate searches of W
  pending vertices run as ONE batched device call against the pre-wave graph,
  then the W integrations are applied sequentially on the host.  Later wave
  members cannot select earlier ones as neighbors (their searches predate
  them) — a bounded staleness that trades a small recall delta for ~W× fewer
  device dispatches; quantified in benchmarks/build_cost.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
# a thread-local setting that jax exposes nowhere else; see _fresh_compiles
from jax._src import config as _jax_config

from repro.obs.trace import span

from .distances import get_metric
from .graph import (DEGraph, GraphBuilder, INVALID, complete_graph,
                    pow2_bucket)
from .mrng import check_mrng_candidate
from .search import (SearchResult, filtered_scan, medoid_seed, range_search,
                     table_format)
from .tags import TagIndex, tag_rows


# ---------------------------------------------------------------------------
# host-side metric helpers (small vectors; avoids device dispatch overhead)
# ---------------------------------------------------------------------------
def np_pair_dist(metric: str, x: np.ndarray, ys: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.float32)
    ys = np.asarray(ys, dtype=np.float32)
    if ys.ndim == 1:
        ys = ys[None, :]
    if metric in ("l2", "sqeuclidean"):
        d = ys - x[None, :]
        sq = np.maximum(np.einsum("ij,ij->i", d, d), 0.0)
        return sq if metric == "sqeuclidean" else np.sqrt(sq)
    if metric == "ip":
        return -(ys @ x)
    if metric == "cos":
        xn = x / max(np.linalg.norm(x), 1e-12)
        yn = ys / np.maximum(np.linalg.norm(ys, axis=1, keepdims=True), 1e-12)
        return 1.0 - yn @ xn
    raise ValueError(metric)


@dataclasses.dataclass
class DEGParams:
    """Paper Table 3 hyperparameters."""

    degree: int = 20          # d
    k_ext: int = 40
    eps_ext: float = 0.3
    k_opt: int = 20
    eps_opt: float = 0.001
    i_opt: int = 5
    scheme: str = "C"         # paper default: C for extension
    rng_checks: bool = True   # Algorithm 2 during extension
    # Alg. 3 line 17 — marked *optional* in the paper.  Under our batched-beam
    # search, insert-time optimization of the new vertex's far edges degraded
    # the QPS<->recall frontier, while post-build continuous refinement
    # (Alg. 5 via DEGIndex.refine) improves it (see EXPERIMENTS.md, "Edge
    # optimization").  Default off; the faithful knob remains available.
    optimize_new: bool = False
    metric: str = "l2"
    # Alg. 3 neighbor selection as one wave-batched device program (see
    # core/extend.py); False forces the per-vertex host path (the pre-PR
    # behavior, kept as the fallback and the benchmark baseline).
    device_extend: bool = True
    # selection block size within an insert wave: the device program
    # selects this many vertices per call against the freshly synced graph
    # (dirty-row scatter), bounding selection staleness — and wave
    # conflicts — to the block instead of the whole wave.
    extend_block: int = 16
    # -- query-path engine knobs (every search this index runs — queries,
    # build/optimize/delete candidate searches — inherits these unless the
    # caller overrides them per call).  expand_width=1 + jnp hop is the
    # seed program bit for bit; E>1 widens the per-hop frontier with the
    # broadcast dedup (see benchmarks/search_pareto); the visited filter
    # (core/visited.py) engages via an explicit visited_size or the fused
    # pallas hop, which requires it.
    expand_width: int = 1
    hop_backend: str = "jnp"          # "jnp" | "pallas" (fused hop kernel)
    visited_size: Optional[int] = None  # None = auto (0 unless fused hop)

    def __post_init__(self):
        if self.k_ext < self.degree:
            raise ValueError("k_ext must be >= degree (paper Sec. 5.2)")


def _locked(fn):
    """Serialize a mutator on the index's mutation lock (re-entrant, so
    mutators may call each other and ``publish`` from inside)."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._mutex:
            return fn(self, *args, **kwargs)
    return wrapper


@contextlib.contextmanager
def _fresh_compiles(fmt):
    """Keep what compiles inside out of the persistent compilation cache
    where it returns a table in a layout of its own (``fmt.layout`` set).

    An executable read back from that cache marks its outputs with the
    backend's default layout, whatever layout it writes them in (jax 0.9,
    on the CPU and on a v5e alike): a table it returns in ``TABLE_LAYOUT``
    would be read as column-major by every later program.  So each process
    compiles the programs that return such a table itself.  Programs that
    only take the table, as ``range_search`` does, read back correctly."""
    if fmt.layout is None:
        yield
        return
    with _jax_config.persistent_cache_min_compile_time_secs(math.inf):
        yield


def _zeros_table(shape: tuple) -> jax.Array:
    return jnp.zeros(shape, jnp.float32)


def _copy_table(table: jax.Array) -> jax.Array:
    return table


def _write_table_rows(buf: jax.Array, rows: jax.Array, start: jax.Array
                      ) -> jax.Array:
    return jax.lax.dynamic_update_slice(buf, rows, (start, jnp.int32(0)))


class _TablePrograms:
    """The programs that hand back a vector table in one format: zeros, a
    copy (of host rows, or of a device table in any layout), and the
    donated row update in place.  Each pins its output to the format
    through ``out_shardings``; see :func:`_fresh_compiles`."""

    def __init__(self, fmt):
        self.format = fmt
        self._zeros = jax.jit(_zeros_table, static_argnums=(0,),
                              out_shardings=fmt)
        self._copy = jax.jit(_copy_table, out_shardings=fmt)
        self._write = jax.jit(_write_table_rows, donate_argnums=(0,),
                              out_shardings=fmt)

    def zeros(self, shape: tuple) -> jax.Array:
        with _fresh_compiles(self.format):
            return self._zeros(shape)

    def copy(self, table) -> jax.Array:
        with _fresh_compiles(self.format):
            return self._copy(table)

    def write_rows(self, buf: jax.Array, rows: jax.Array,
                   start: jax.Array) -> jax.Array:
        with _fresh_compiles(self.format):
            return self._write(buf, rows, start)


_table_programs = functools.cache(_TablePrograms)


class DEGIndex:
    """A Dynamic Exploration Graph over a growing set of vectors."""

    def __init__(self, dim: int, params: DEGParams | None = None,
                 capacity: int = 1024):
        self.params = params or DEGParams()
        self.dim = dim
        capacity = max(capacity, self.params.degree + 1)
        self.vectors = np.zeros((capacity, dim), dtype=np.float32)
        # the device table, in the format every search reads it in
        # (core/search.py::table_format), made on the device
        self._table = self._programs_for(capacity)
        self._dev_vectors = self._table.zeros((capacity, dim))
        self.builder: Optional[GraphBuilder] = None
        self._pending: list[np.ndarray] = []   # points before K_{d+1} exists
        self._rng = np.random.default_rng(0)
        self._medoid: Optional[int] = None     # cached medoid_seed entry
        # compressed views of _dev_vectors, keyed by codec name; invalidated
        # whenever the indexed vector set changes (post-training recipe:
        # re-encode + re-calibrate from the live rows, never retrain)
        self._stores: dict = {}
        # per-stage wall time of _insert_wave (candidate search vs vertex
        # extension), from the deg.add.search / deg.add.extend spans —
        # benchmarks/build_cost.py reports both
        self.build_stats = {"search_s": 0.0, "extend_s": 0.0, "vertices": 0}
        # optional obs.MetricsRegistry: when attached (launch/serve.py,
        # benches), insert waves and refine sweeps record their stage
        # spans/counters into it; None (the default) costs a None check
        self.metrics = None
        # mid-build checkpointing (persist/snapshot.py): every insert wave
        # and refine chunk ticks the counter; when due, the full index state
        # is snapshotted at the wave boundary (the only mid-build points
        # where the graph invariants hold)
        self._ckpt_path = None
        self._ckpt_every = 0
        self._wave_counter = 0
        # mutation WAL (persist/wal.py): when enabled, every mutation unit
        # (bootstrap take / insert wave / remove / refine) is journaled
        # before it is applied, so load(snapshot) + replay(wal) is
        # bit-identical to the uninterrupted build.  _wal_replay holds the
        # record being re-applied (verify, don't re-append); _wal_op_active
        # suppresses checkpoint *saves* inside a journaled op (a snapshot
        # there would advance the cursor past a half-applied record)
        self._wal = None
        self._wal_seq = 0
        self._wal_replay = None
        self._wal_op_active = False
        # live-mutation-under-serving state (core/epoch.py): mutators
        # serialize on _mutex (re-entrant — publish() runs inside remove's
        # lock scope); _epochs holds the refcounted published snapshots
        # once enable_publishing() ran; quarantine is the scrubber's set of
        # damaged vertices, excluded from published seeds/results until
        # repaired and re-audited
        self._mutex = threading.RLock()
        self._epochs = None
        self.quarantine: set[int] = set()
        self._publish_every_chunks = 0
        self._refine_chunk_counter = 0
        # row tags (core/tags.py), None until rows are added with tags:
        # the host table (capacity, T), its device twin and its posting
        # lists, the latter two made when first read after a change
        self.tags: Optional[np.ndarray] = None
        self._dev_tags: Optional[jax.Array] = None
        self._tag_index: Optional[TagIndex] = None

    # -- sizes -------------------------------------------------------------
    @property
    def n(self) -> int:
        return 0 if self.builder is None else self.builder.n

    @property
    def capacity(self) -> int:
        return self.vectors.shape[0]

    def grow(self, new_capacity: int) -> None:
        if new_capacity <= self.capacity:
            return
        vecs = np.zeros((new_capacity, self.dim), dtype=np.float32)
        vecs[: self.capacity] = self.vectors
        self.vectors = vecs
        self._table = self._programs_for(new_capacity)
        self._dev_vectors = self._table.copy(vecs)
        self._placed_table()
        self._stores = {}
        if self.tags is not None:
            tags = np.full((new_capacity, self.tags.shape[1]), INVALID,
                           np.int32)
            tags[: len(self.tags)] = self.tags
            self.tags = tags
            self._dev_tags = None
        if self.builder is not None:
            self.builder.grow(new_capacity)

    # -- device sync ---------------------------------------------------------
    def _programs_for(self, capacity: int) -> _TablePrograms:
        """The table programs for a table of ``capacity`` rows, in the
        format core/search.py::table_format gives that size."""
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        return _table_programs(table_format(sharding, (capacity, self.dim)))

    def _placed_table(self) -> jax.Array:
        """The device table, re-placed in the search layout if it is kept
        in that layout and some path dropped it (counted as
        ``vector_table_relayouts_total``: each later search would otherwise
        relayout the whole table first)."""
        want = self._table.format.layout
        if (want is not None and self._dev_vectors.format.layout
                .major_to_minor != want.major_to_minor):
            from repro.obs.metrics import VECTOR_TABLE_RELAYOUTS_TOTAL

            self._dev_vectors = self._table.copy(self._dev_vectors)
            if self.metrics is not None:
                self.metrics.counter(VECTOR_TABLE_RELAYOUTS_TOTAL).inc()
        return self._dev_vectors

    def _put_rows(self, rows: np.ndarray, start: int) -> None:
        self._medoid = None                    # vector set changed
        self._stores = {}
        self._dev_vectors = self._table.write_rows(
            self._placed_table(), jnp.asarray(rows, dtype=jnp.float32),
            jnp.asarray(start, dtype=jnp.int32))

    def medoid(self) -> int:
        """Cached approximate-median entry vertex (paper Sec. 5.4).

        ``medoid_seed`` is a full device reduction over the vector buffer;
        recomputing it per query was pure overhead.  The cache is
        invalidated whenever the indexed vector set changes (insert waves,
        deletion compaction — both funnel through ``_put_rows`` — and
        ``remove``'s slot shrink)."""
        if self._medoid is None or self._medoid >= self.n:
            self._medoid = medoid_seed(self._dev_vectors, self.n)
        return self._medoid

    # -- row tags (core/tags.py) ---------------------------------------------
    @property
    def tagged(self) -> bool:
        """Whether rows carry tags, so that filtered searches can run."""
        return self.tags is not None

    def _put_tags(self, tags, start: int, rows: int, slots: int) -> None:
        """Write a CSR tag list for the ``rows`` rows from ``start``."""
        indptr, indices = tags
        if len(indptr) != rows + 1:
            raise ValueError(f"tags list {len(indptr) - 1} rows for "
                             f"{rows} points")
        if self.tags is None:
            self.tags = np.full((self.capacity, slots), INVALID, np.int32)
        self.tags[start: start + rows] = tag_rows(indptr, indices,
                                                  self.tags.shape[1])
        self._tags_changed()

    def _move_tags(self, src: int, dst: int) -> None:
        """Deletion's compaction moved row ``src`` into slot ``dst``."""
        if self.tags is not None:
            self.tags[dst] = self.tags[src]
            self.tags[src] = INVALID
            self._tags_changed()

    def _tags_changed(self) -> None:
        self._dev_tags = None
        self._tag_index = None

    def device_tags(self) -> jax.Array:
        """The (capacity, T) int32 tag table on the device."""
        if self._dev_tags is None:
            self._dev_tags = jnp.asarray(self.tags)
        return self._dev_tags

    def tag_index(self) -> TagIndex:
        """Posting lists of the indexed rows' tags."""
        ti = self._tag_index
        if ti is None or len(ti.table) != self.n:
            ti = self._tag_index = TagIndex(self.tags[: self.n].copy())
        return ti

    def filter_rows(self, filt) -> np.ndarray:
        """The indexed rows ``filt`` admits (core/tags.py), ascending."""
        return self.tag_index().rows(filt)

    def filter_count(self, filt) -> int:
        """How many indexed rows ``filt`` admits."""
        return self.tag_index().count(filt)

    def frozen(self) -> DEGraph:
        """The device twin consumed transiently by every search call —
        valid until the next graph mutation + sync (donated buffers); use
        ``builder.freeze()`` for a snapshot that must survive mutations."""
        return self.builder.device_graph()

    # -- epoch publication (core/epoch.py; live mutation under serving) ------
    @property
    def mutation_lock(self) -> threading.RLock:
        """Re-entrant lock every mutator holds; external writers (the
        scrubber, background refinement threads) take it around any direct
        builder surgery so a ``publish()`` can never capture mid-surgery
        state."""
        return self._mutex

    @property
    def publishing(self) -> bool:
        return self._epochs is not None

    def enable_publishing(self, publish_now: bool = True,
                          every_chunks: int = 0):
        """Turn on epoch publication: serving flushes will search
        refcounted immutable snapshots (``acquire_view``) instead of the
        live donation-invalidated device cache, making the index safely
        mutable while an async engine is live.  ``every_chunks > 0``
        additionally republishes every that-many refine chunks so long
        sweeps surface improvements mid-run.  Returns the epoch manager."""
        from .epoch import EpochManager

        with self._mutex:
            if self._epochs is None:
                self._epochs = EpochManager(self)
            self._publish_every_chunks = int(every_chunks)
            if publish_now and self.builder is not None:
                self.publish()
        return self._epochs

    def publish(self) -> int:
        """Atomically publish the current graph + vector state as a new
        epoch (at a mutation-batch boundary — the caller guarantees the
        Table-1 window, i.e. not mid-surgery).  Journals an
        ``epoch_publish`` record when a WAL is attached and the publish is
        not nested inside a journaled op, so ``recover()`` lands exactly on
        the last published epoch.  Returns the new epoch number."""
        from repro.obs.metrics import EPOCH_GAUGE, EPOCH_PUBLISH_TOTAL
        from repro.resilience import faults as _faults

        from .epoch import PublishedEpoch

        if self._epochs is None:
            raise RuntimeError("enable_publishing() first")
        if self.builder is None:
            raise RuntimeError("nothing to publish: index is empty")
        with self._mutex:
            e = self._epochs.next_epoch
            gen = self.builder.generation
            quar = tuple(sorted(q for q in self.quarantine if q < self.n))
            # mid-op publishes (refine-chunk ticks) are serving-only: the
            # enclosing journaled record already replays the mutations, and
            # a nested record would break the seq/verify protocol
            if not self._wal_op_active and self._wal_replay is None:
                self._wal_record("epoch_publish",
                                 {"epoch": int(e), "n": int(self.n),
                                  "gen": int(gen),
                                  "quarantine": [int(q) for q in quar]}, {})
            tagged = self.tags is not None
            ep = PublishedEpoch(
                epoch=e, graph=self.builder.freeze(),
                vectors=self._table.copy(self._placed_table()), n=self.n,
                medoid_id=self._publish_medoid(quar),
                metric=self.params.metric, params=self.params,
                quarantine=quar, builder_gen=gen,
                tags=self.device_tags() if tagged else None,
                tag_index=self.tag_index() if tagged else None)
            _faults.fire("publish.swap", epoch=e, n=self.n)
            self._epochs.publish(ep)
        if self.metrics is not None:
            self.metrics.gauge(EPOCH_GAUGE).set(e)
            self.metrics.counter(EPOCH_PUBLISH_TOTAL).inc()
        return e

    def _publish_medoid(self, quarantine) -> int:
        """The entry vertex a published epoch seeds from: the cached medoid
        unless it is quarantined, in which case the nearest-to-centroid
        healthy vertex."""
        m = self.medoid()
        bad = set(quarantine)
        if m not in bad:
            return m
        vecs = self.vectors[: self.n]
        dist = np.linalg.norm(vecs - vecs.mean(axis=0), axis=1)
        dist[list(bad)] = np.inf
        return int(np.argmin(dist))

    def acquire_view(self):
        """The view a serving flush searches.  With publishing enabled:
        the current epoch, refcounted — the caller MUST pass it back to
        :meth:`release_view` once results are on host.  Without publishing
        (the historical single-writer mode) the index itself is returned
        and release is a no-op."""
        if self._epochs is not None:
            return self._epochs.acquire()
        return self

    def release_view(self, view) -> None:
        if self._epochs is not None and view is not self and view is not None:
            self._epochs.release(view)

    def _publish_tick(self) -> None:
        """Refine-chunk boundary hook (core/optimize.py): republish every
        ``every_chunks`` chunks when configured via
        ``enable_publishing(every_chunks=...)``."""
        if self._epochs is None or self._publish_every_chunks <= 0:
            return
        self._refine_chunk_counter += 1
        if self._refine_chunk_counter % self._publish_every_chunks == 0:
            with span("deg.tick"):
                self.publish()

    # -- insertion -----------------------------------------------------------
    @_locked
    def add(self, points: np.ndarray, wave_size: int = 1, tags=None,
            tag_slots: int = 16) -> None:
        """Insert points (Alg. 3). ``wave_size>1`` enables bulk build.

        ``tags`` gives the points' tags as a CSR list ``(indptr,
        indices)`` (core/tags.py); the first tagged add sizes the tag
        table at ``tag_slots`` tags a row.  Rows added without tags carry
        none."""
        if tags is not None:
            if self._wal is not None:
                raise NotImplementedError("the WAL does not journal tags")
            pts = np.asarray(points)
            start = self.n + len(self._pending)
            rows = 1 if pts.ndim == 1 else len(pts)
            if start + rows > self.capacity:
                self.grow(max(2 * self.capacity, start + rows))
            self._put_tags(tags, start, rows, tag_slots)
        with span("deg.add"):
            self._add(points, wave_size)

    def _add(self, points: np.ndarray, wave_size: int) -> None:
        points = np.asarray(points, dtype=np.float32)
        if points.ndim == 1:
            points = points[None]
        if self.n + len(self._pending) + points.shape[0] > self.capacity:
            self.grow(max(2 * self.capacity,
                          self.n + len(self._pending) + points.shape[0]))
        d = self.params.degree
        i = 0
        # bootstrap: K_{d+1} complete graph (Sec. 5.1)
        if self.builder is None:
            need = d + 1 - len(self._pending)
            take = min(need, points.shape[0])
            if take:
                self._wal_record("add", {"wave_size": int(wave_size)},
                                 {"points": points[:take]})
            self._pending.extend(points[:take])
            i = take
            if len(self._pending) == d + 1:
                init = np.stack(self._pending)
                self.vectors[: d + 1] = init
                self._put_rows(init, 0)
                self.builder = complete_graph(
                    init, d, self.capacity, self.params.metric)
                self._pending = []
            if i >= points.shape[0]:
                return
        while i < points.shape[0]:
            w = min(wave_size, points.shape[0] - i)
            # one WAL record per wave (not per add() call): the record is
            # durable before the wave mutates anything, and the
            # end-of-wave checkpoint sees a cursor that exactly covers
            # the applied waves
            self._wal_record("add", {"wave_size": int(w)},
                             {"points": points[i : i + w]})
            with span("deg.add.wave", wave=self._wave_counter):
                self._insert_wave(points[i : i + w])
            i += w

    def _insert_wave(self, pts: np.ndarray) -> None:
        W = pts.shape[0]
        start = self.builder.n
        self.vectors[start : start + W] = pts
        self._put_rows(pts, start)
        # one batched candidate search for the whole wave (pre-wave graph),
        # through the same engine program as every other consumer
        with span("deg.add.search", self.metrics, device=True,
                  metric="build_wave_search") as s_search:
            seeds = np.full((W, 1), self._entry_vertex(), dtype=np.int32)
            res = self.search_batch(pts, seeds, k=self.params.k_ext,
                                    eps=self.params.eps_ext)
            ids = np.asarray(res.ids)
            dists = np.asarray(res.dists)
        use_device = self.params.device_extend
        block = max(int(self.params.extend_block), 1) if use_device else W
        # the extension stretch: device selection + its bulk application,
        # with the host completions (deg.add.complete) nested inside
        with span("deg.add.extend", self.metrics, device=use_device,
                  metric="build_wave_extend") as s_extend:
            for j0 in range(0, W, block):
                j1 = min(j0 + block, W)
                vs = [self.builder.add_vertex() for _ in range(j0, j1)]
                assert vs[0] == start + j0
                if use_device:
                    # Alg. 3 selection for a block of vertices in ONE
                    # device program against the freshly synced graph (the
                    # dirty-row scatter in device_graph picks up the
                    # previous block's edge swaps), then ONE vectorized
                    # application of every selection that survived
                    # intra-block conflicts (first-lane-wins, matching the
                    # host application order).
                    from .extend import extend_wave

                    sel_ids, sel_d, ok = extend_wave(
                        self, pts[j0:j1], ids[j0:j1], dists[j0:j1],
                        start + j0)
                    self._apply_extension_block(start + j0, sel_ids, sel_d,
                                                ok)
                with span("deg.add.complete"):
                    for j in range(j0, j1):
                        v = start + j
                        # warm start from the LIVE row: a host completion
                        # of an earlier lane may have stolen (or added)
                        # edges of this vertex since the bulk apply
                        live = self.builder.neighbors(v)
                        if len(live) == self.params.degree:
                            new_edges = [int(x) for x in live]
                        else:
                            new_edges = self._extend_vertex(
                                v, pts[j], ids[j], dists[j],
                                [int(x) for x in live],
                                [float(x) for x in
                                 self.builder.neighbor_weights(v)])
                        self._post_insert(v, new_edges, ids[j])
        self.build_stats["search_s"] += s_search.seconds
        self.build_stats["extend_s"] += s_extend.seconds
        self.build_stats["vertices"] += W
        if self.metrics is not None:
            self.metrics.counter("build_vertices_total").inc(W)
        self._checkpoint_tick()

    def _post_insert(self, v: int, new_edges, cand_ids) -> None:
        if not self.params.optimize_new:
            return
        from .optimize import optimize_edge

        in_s = set(int(x) for x in cand_ids if x != INVALID)
        for u in new_edges:
            if u not in in_s and self.builder.has_edge(v, u):
                # Alg. 3 line 17: replace the far neighbors of the new
                # vertex.  Alg. 4's search finds a new neighbor for its
                # *second* argument, so the new vertex goes second
                # (the paper's prose reading; measured better than the
                # literal (v, u) order — see EXPERIMENTS.md).
                optimize_edge(self, u, v,
                              i_opt=self.params.i_opt,
                              k_opt=self.params.k_opt,
                              eps_opt=self.params.eps_opt)

    def _entry_vertex(self) -> int:
        return int(self._rng.integers(0, max(self.builder.n, 1)))

    def _apply_extension_block(self, start_v: int, sel_ids: np.ndarray,
                               sel_d: np.ndarray, ok: np.ndarray) -> None:
        """Apply a block of device-selected neighborhoods in one vectorized
        pass of Alg. 3 edge swaps.

        An edge may be surrendered by several lanes of the block (they all
        selected against the same snapshot); the first lane wins — exactly
        the host application order — via a lane-major first-occurrence
        dedup, and ``GraphBuilder.replace_edges`` skips anything else that
        is stale.  Lanes left short of ``degree`` edges are completed
        through the host path by the caller (off the live rows)."""
        b = self.builder
        Wb, D = sel_ids.shape
        P = D // 2
        v_arr = start_v + np.arange(Wb)
        lane_ok = np.asarray(ok, bool).copy()
        # structural sanity (the device program guarantees these; cheap)
        lane_ok &= ((sel_ids >= 0).all(axis=1)
                    & (sel_ids < v_arr[:, None]).all(axis=1))
        srt = np.sort(sel_ids, axis=1)
        lane_ok &= (srt[:, 1:] != srt[:, :-1]).all(axis=1)
        bs, ns = sel_ids[:, 0::2], sel_ids[:, 1::2]          # (Wb, P)
        lo = np.minimum(bs, ns).astype(np.int64)
        hi = np.maximum(bs, ns).astype(np.int64)
        key = lo * b.capacity + hi
        # failed lanes claim nothing: give them unique sentinel keys
        sentinel = -1 - (np.arange(Wb, dtype=np.int64)[:, None] * P
                         + np.arange(P, dtype=np.int64)[None, :])
        key = np.where(lane_ok[:, None], key, sentinel)
        _, first = np.unique(key.reshape(-1), return_index=True)
        keep = np.zeros(key.size, dtype=bool)
        keep[first] = True
        keep = keep.reshape(Wb, P) & lane_ok[:, None]
        k = keep.reshape(-1)
        # v-row slots stay at the pair's original position (2t, 2t+1);
        # dropped pairs leave INVALID holes the host completion refills
        t_idx = np.broadcast_to(np.arange(P), (Wb, P))
        b.replace_edges(
            np.broadcast_to(v_arr[:, None], (Wb, P)).reshape(-1)[k],
            (2 * t_idx).reshape(-1)[k].astype(np.int64),
            bs.reshape(-1)[k], ns.reshape(-1)[k],
            sel_d[:, 0::2].reshape(-1)[k], sel_d[:, 1::2].reshape(-1)[k])

    # -- Alg. 3 core: select d/2 (b, n) pairs -------------------------------
    def _extend_vertex(self, v: int, vec: np.ndarray, cand_ids: np.ndarray,
                       cand_dists: np.ndarray,
                       U0: Optional[list[int]] = None,
                       U0_d: Optional[list[float]] = None) -> list[int]:
        """Host Alg. 3 selection (the pre-device reference path).  ``U0`` /
        ``U0_d`` optionally seed the selected set with already-applied
        pairs (device-wave completion after conflicts)."""
        b = self.builder
        d = b.degree
        metric = self.params.metric
        cands: list[tuple[int, float]] = [
            (int(c), float(x)) for c, x in zip(cand_ids, cand_dists)
            if c != INVALID and c < v
        ]
        U: list[int] = list(U0 or [])
        U_d: list[float] = list(U0_d or [])
        n_pre = len(U)            # warm-start edges already in the graph

        def select_n(bb: int, b_dist: float) -> Optional[tuple[int, float]]:
            nbrs = [int(x) for x in b.neighbors(bb) if int(x) not in U]
            if not nbrs:
                return None
            ws = np.array([b.edge_weight(bb, x) for x in nbrs])
            scheme = self.params.scheme
            if scheme == "C":
                j = int(np.argmax(ws))
            elif scheme == "B":
                j = int(np.argmin(ws))
            else:
                nd = np_pair_dist(metric, vec, self.vectors[nbrs])
                if scheme == "A":
                    j = int(np.argmin(nd))
                elif scheme == "D":
                    j = int(np.argmin(nd - ws))
                else:
                    raise ValueError(self.params.scheme)
            n_sel = nbrs[j]
            n_dist = float(np_pair_dist(metric, vec, self.vectors[n_sel])[0])
            return n_sel, n_dist

        skip_rng = not self.params.rng_checks
        exhausted_fallbacks = 0
        while len(U) < d:
            progressed = False
            for bb, bd in cands:
                if len(U) >= d:
                    break
                if bb in U:
                    continue
                if not skip_rng and not check_mrng_candidate(b, bb, bd, U, U_d):
                    continue
                sel = select_n(bb, bd)
                if sel is None:
                    continue
                n_sel, n_dist = sel
                b.remove_edge(bb, n_sel)
                U.extend((bb, n_sel))
                U_d.extend((bd, n_dist))
                progressed = True
            if len(U) >= d:
                break
            if not skip_rng:
                skip_rng = True      # phase 2 (Alg. 3 line 14)
                continue
            if not progressed:
                # candidate list exhausted — widen with exact nearest actives
                exhausted_fallbacks += 1
                if exhausted_fallbacks > 3:
                    raise RuntimeError(
                        f"cannot complete neighborhood for vertex {v}")
                cands = self._exact_candidates(vec, set(U), v)
        for u, w in zip(U[n_pre:], U_d[n_pre:]):
            b.add_edge(v, u, w)
        return U

    def _exact_candidates(self, vec, exclude, v):
        """Widened pool for an exhausted extension: every vertex below the
        one being inserted — same-block vertices above ``v`` are added but
        not yet extended, so ``builder.n`` is not the right bound."""
        ds = np_pair_dist(self.params.metric, vec, self.vectors[:v])
        order = np.argsort(ds)
        return [(int(i), float(ds[i])) for i in order if int(i) not in exclude]

    # -- deletion (beyond-paper: completes "fully dynamic", Table 1) --------
    @_locked
    def remove(self, ids, refine_after: int = 0) -> int:
        """Delete vertices preserving regularity/connectivity (no
        tombstones); see core/delete.py. Returns the number deleted.
        NOTE: deletion compacts slots — the last vertex moves into the freed
        slot, so external id maps must be updated via the return protocol of
        delete_vertices."""
        from .delete import delete_vertices

        id_list = [int(v) for v in
                   (ids if hasattr(ids, "__iter__") else [ids])]
        self._wal_record("remove", {"refine_after": int(refine_after)},
                         {"ids": np.asarray(id_list, np.int64)})
        self._medoid = None
        self._stores = {}
        self._wal_op_active = True
        try:
            return delete_vertices(self, id_list,
                                   refine_after=refine_after)
        finally:
            self._wal_op_active = False

    # -- continuous refinement (Alg. 5 driver) -------------------------------
    @_locked
    def refine(self, iterations: int, seed: Optional[int] = None) -> int:
        """Continuous edge optimization (Alg. 5) over ``iterations`` random
        vertices, via the *batched* candidate-search path: each chunk of
        vertices prefetches the first Alg.-4 search of every edge task in
        ONE device call (optimize.refine_sweep), instead of a per-edge
        ``_search_from`` round-trip.  Host-side graph surgery is unchanged.
        Returns the number of improved edges."""
        with span("deg.refine"):
            return self._refine(iterations, seed)

    def _refine(self, iterations: int, seed: Optional[int]) -> int:
        from .optimize import refine_sweep

        if self.builder is None or self.builder.n <= self.builder.degree + 1:
            return 0
        journaled = self._wal is not None or self._wal_replay is not None
        drew = seed is None
        if drew and journaled:
            # a replayable run must not depend on OS entropy: resolve the
            # seed from the persisted build stream, so replay (which
            # restores the stream from the snapshot) re-draws it exactly
            seed = int(self._rng.integers(0, 2**31 - 1))
        self._wal_record("refine",
                         {"iterations": int(iterations),
                          "seed": None if seed is None else int(seed),
                          "drew": drew}, {})
        rng = np.random.default_rng(seed)
        vertices = rng.integers(0, self.builder.n, size=int(iterations))
        self._wal_op_active = journaled
        try:
            return refine_sweep(
                self, vertices,
                i_opt=self.params.i_opt, k_opt=self.params.k_opt,
                eps_opt=self.params.eps_opt)
        finally:
            self._wal_op_active = False

    # -- quantized store views ----------------------------------------------
    def store_for(self, codec: str):
        """The :class:`repro.quant.VectorStore` view the beam traverses
        under ``codec`` — encoded once per (codec, vector-set version) and
        cached until the indexed vectors change."""
        from repro.quant import make_store

        if codec not in self._stores:
            self._stores[codec] = make_store(self._dev_vectors, codec,
                                             n=self.n)
        return self._stores[codec]

    def memory_stats(self) -> dict:
        """Vector-store footprint of the *hot traversal path* per codec
        (live rows only).  The exact float32 copy used by two-stage rerank
        is reported separately — it is touched ``rerank_k`` rows per query,
        not per hop, so it can live off the accelerator."""
        from repro.quant import codec as qc

        n, m = self.n, self.dim
        exact = qc.store_bytes("float32", n, m)
        out = {"n": n, "dim": m, "exact_bytes": exact}
        for name in qc.CODECS:
            b = qc.store_bytes(name, n, m)
            out[f"{name}_bytes"] = b
            out[f"{name}_ratio"] = exact / b if b else 0.0
        return out

    # -- persistence (persist/snapshot.py owns the format) -------------------
    def save(self, path) -> None:
        """Snapshot the complete index state (graph, vectors, materialized
        quant stores, params, RNG/build counters, medoid cache) to one
        versioned npz.  ``DEGIndex.load(path)`` restores a search-identical,
        immediately mutable index."""
        from repro.persist import save_index

        if self.tags is not None:
            raise NotImplementedError("snapshots do not carry row tags")
        save_index(self, path)

    @classmethod
    def load(cls, path, params: "DEGParams | None" = None,
             capacity: Optional[int] = None) -> "DEGIndex":
        """Restore an index saved by :meth:`save`.  The device caches
        (graph adjacency, vector buffer, quant stores) are rebuilt lazily
        from the restored host state — see persist/snapshot.py."""
        from repro.persist import load_index

        return load_index(path, params=params, capacity=capacity)

    def enable_wal(self, path, sync: bool = True) -> None:
        """Journal every future mutation unit to ``path`` (append-only,
        CRC-framed — persist/wal.py) before applying it.  Recovery is
        ``persist.wal.recover(snapshot, wal)``: load the snapshot, replay
        the records past its cursor, bit-identical to the uninterrupted
        build.  NOTE: with the WAL enabled, ``refine(seed=None)`` resolves
        its seed from the persisted build RNG stream (a replayable run
        cannot depend on OS entropy)."""
        from repro.persist.wal import WALWriter

        self._wal = WALWriter(path, sync=sync)

    def _wal_record(self, op: str, meta: dict, arrays: dict) -> None:
        """Journal one mutation unit — or, during replay, verify the op
        against the record being re-applied instead of re-appending it.
        No-op when the WAL is disabled (the sequence counter only
        advances for journaled ops, keeping snapshot cursors aligned)."""
        rec = self._wal_replay
        if rec is not None:
            from repro.persist.wal import WALError

            if rec.op != op:
                raise WALError(
                    f"replay mismatch at seq {rec.seq}: journal says "
                    f"{rec.op!r}, index replayed {op!r}")
            if op == "refine" and rec.meta.get("seed") != meta.get("seed"):
                raise WALError(
                    f"replay mismatch at seq {rec.seq}: refine seed "
                    f"{meta.get('seed')} != journaled "
                    f"{rec.meta.get('seed')} — RNG stream diverged "
                    "(snapshot and WAL don't belong together?)")
            self._wal_seq += 1
            return
        if self._wal is not None:
            self._wal.append(self._wal_seq, op, meta, arrays)
            self._wal_seq += 1

    def enable_checkpoints(self, path, every_waves: int = 1) -> None:
        """Snapshot the full index to ``path`` every ``every_waves``
        insert waves / refine chunks (at wave boundaries, where the graph
        invariants hold).  ``path`` may contain ``{waves}`` / ``{n}``
        placeholders to keep a checkpoint series instead of overwriting.
        ``every_waves=0`` disables."""
        try:
            str(path).format(waves=0, n=0)   # fail at config time, not
        except (KeyError, IndexError) as e:  # waves deep into the build
            raise ValueError(
                f"bad checkpoint path template {path!r}: only {{waves}} and "
                f"{{n}} placeholders are supported ({e!r})")
        self._ckpt_path = path
        self._ckpt_every = int(every_waves)

    def _checkpoint_tick(self) -> None:
        self._wave_counter += 1
        if (self._ckpt_path is not None and self._ckpt_every > 0
                and self._wave_counter % self._ckpt_every == 0
                # inside a journaled remove/refine the WAL cursor already
                # covers the op but the graph is mid-surgery: a snapshot
                # here could not be continued by replay.  Waves are safe
                # (one record per wave).  Replay itself never writes.
                and not self._wal_op_active and self._wal_replay is None):
            with span("deg.tick"):
                self.save(str(self._ckpt_path).format(
                    waves=self._wave_counter, n=self.n))

    # -- queries --------------------------------------------------------------
    def search_batch(self, queries: np.ndarray,
                     seed_ids: Optional[np.ndarray] = None,
                     exclude: Optional[np.ndarray] = None, *, k: int,
                     eps: float = 0.1, beam_width: Optional[int] = None,
                     backend: str = "jnp",
                     quantized: Optional[str] = None,
                     rerank_k: Optional[int] = None,
                     expand_width: Optional[int] = None,
                     visited_size: Optional[int] = None,
                     hop_backend: Optional[str] = None,
                     hop_budget: Optional[np.ndarray] = None,
                     filter_tags: Optional[np.ndarray] = None
                     ) -> SearchResult:
        """The one device entry point every query path funnels through.

        ``seed_ids`` (B, S) / ``exclude`` (B, X) go straight into the beam
        engine; plain searches, exploration sessions and the serving
        flush all share this jitted program (one cache entry per shape
        family instead of one per calling layer).

        ``quantized`` selects the store codec the beam traverses ("fp16" |
        "sq8" | "pq"; None/"float32" = the exact path, bit-identical to the
        pre-quantization engine).  With a compressed codec the search is
        two-stage: the beam runs over compressed distances, then the best
        ``rerank_k`` candidates (default ``4 * k``) are re-scored exactly
        against the float store and the exact top-k is returned.

        ``expand_width`` / ``visited_size`` / ``hop_backend`` default to
        the index's ``DEGParams`` engine knobs (multi-expansion config);
        pass explicit values to override per call.

        ``hop_budget`` (B,) int32 per-lane expansion caps (serving
        deadline early-extract; a traced operand, so all budget values
        share one compiled program per shape family).

        ``filter_tags`` (B, F) int32, INVALID-padded: each lane returns
        only rows carrying every tag of its filter (core/tags.py).
        """
        E = self.params.expand_width if expand_width is None else expand_width
        hb = self.params.hop_backend if hop_backend is None else hop_backend
        vs = self.params.visited_size if visited_size is None else visited_size
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        if seed_ids is None:
            seeds = jnp.full((q.shape[0], 1), self.medoid(), dtype=jnp.int32)
        else:
            seeds = jnp.asarray(np.asarray(seed_ids, np.int32))
            if seeds.ndim == 1:
                seeds = seeds[:, None]
        excl = None if exclude is None else jnp.asarray(
            np.asarray(exclude, np.int32))
        hbud = None if hop_budget is None else jnp.asarray(
            np.asarray(hop_budget, np.int32))
        filt = {} if filter_tags is None else {
            "tags": self.device_tags(),
            "filter_tags": jnp.asarray(np.asarray(filter_tags, np.int32))}
        if quantized in (None, "float32"):
            return range_search(self.frozen(), self._dev_vectors, q, seeds,
                                k=k, eps=eps, beam_width=beam_width,
                                metric=self.params.metric, exclude=excl,
                                backend=backend, expand_width=E,
                                visited_size=vs, hop_backend=hb,
                                hop_budget=hbud, **filt)
        store = self.store_for(quantized)
        rk = int(rerank_k) if rerank_k else 4 * k
        return range_search(self.frozen(), store, q, seeds, k=k, eps=eps,
                            beam_width=beam_width,
                            metric=self.params.metric, exclude=excl,
                            backend=backend, rerank_k=max(rk, k),
                            exact_vectors=self._dev_vectors, expand_width=E,
                            visited_size=vs, hop_backend=hb,
                            hop_budget=hbud, **filt)

    def scan_batch(self, queries: np.ndarray, *chunks, k: int
                   ) -> SearchResult:
        """Exact k-NN of each query among its own rows, given in chunks
        (``core/search.py::filtered_scan``, ``serving/buckets.py::
        pad_scan``)."""
        return filtered_scan(self._dev_vectors,
                             jnp.asarray(np.asarray(queries, np.float32)),
                             *(jnp.asarray(np.asarray(c, np.int32))
                               for c in chunks),
                             k=k, metric=self.params.metric)

    def search(self, queries: np.ndarray, k: int, eps: float = 0.1,
               beam_width: Optional[int] = None, seed: Optional[int] = None,
               backend: str = "jnp", quantized: Optional[str] = None,
               rerank_k: Optional[int] = None,
               expand_width: Optional[int] = None,
               visited_size: Optional[int] = None,
               hop_backend: Optional[str] = None) -> SearchResult:
        if seed is None:
            seed = self.medoid()
        q = np.atleast_2d(np.asarray(queries, np.float32))
        seeds = np.full((q.shape[0], 1), seed, dtype=np.int32)
        return self.search_batch(q, seeds, k=k, eps=eps,
                                 beam_width=beam_width, backend=backend,
                                 quantized=quantized, rerank_k=rerank_k,
                                 expand_width=expand_width,
                                 visited_size=visited_size,
                                 hop_backend=hop_backend)

    def explore(self, seed_vertices: Sequence[int], k: int, eps: float = 0.1,
                exclude: Optional[np.ndarray] = None,
                beam_width: Optional[int] = None) -> SearchResult:
        """Exploration queries (paper Sec. 6.7): seed == query vertex; the
        seed (and optionally already-seen vertices) are excluded from results."""
        sv = np.asarray(seed_vertices, dtype=np.int32).reshape(-1)
        if exclude is None:
            excl = sv[:, None]
        else:
            excl = np.concatenate([sv[:, None], np.asarray(exclude, np.int32)],
                                  axis=1)
        return self.search_batch(self.vectors[sv], sv[:, None], excl,
                                 k=k, eps=eps, beam_width=beam_width)

    # -- internal search used by optimize.py ----------------------------------
    def _search_from(self, query_vec: np.ndarray, seed_ids: Sequence[int],
                     k: int, eps: float) -> tuple[np.ndarray, np.ndarray]:
        s = np.full((1, 2), INVALID, dtype=np.int32)
        for j, sid in enumerate(list(seed_ids)[:2]):
            s[0, j] = sid
        with span("deg.search_from", device=True):
            res = self.search_batch(
                np.asarray(query_vec, np.float32)[None, :], s, k=k, eps=eps)
            return np.asarray(res.ids)[0], np.asarray(res.dists)[0]

    def _search_from_batch(self, query_vecs: np.ndarray,
                           seed_ids: np.ndarray, k: int, eps: float
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Batched sibling of ``_search_from``: (B, m) queries, (B, S)
        seeds -> host (B, k) ids/dists.  Lanes are padded to a power of two
        so the repeated Alg.-5 sweeps reuse a handful of jit entries."""
        B = query_vecs.shape[0]
        Bp = pow2_bucket(B, floor=8)
        q = np.zeros((Bp, self.dim), np.float32)
        q[:B] = query_vecs
        s = np.full((Bp, seed_ids.shape[1]), INVALID, np.int32)
        s[:B] = seed_ids
        res = self.search_batch(q, s, k=k, eps=eps)
        return np.asarray(res.ids)[:B], np.asarray(res.dists)[:B]


def build_deg(vectors: np.ndarray, params: DEGParams | None = None,
              wave_size: int = 1, refine_iterations: int = 0,
              capacity: Optional[int] = None, tags=None,
              tag_slots: int = 16) -> DEGIndex:
    """One-shot construction of a DEG over ``vectors``; ``tags``, a CSR
    list ``(indptr, indices)`` of each row's tags, makes filtered searches
    possible (``DEGIndex.add``).  The graph is built without them."""
    vectors = np.asarray(vectors, dtype=np.float32)
    idx = DEGIndex(vectors.shape[1], params,
                   capacity=capacity or vectors.shape[0])
    idx.add(vectors, wave_size=wave_size, tags=tags, tag_slots=tag_slots)
    if refine_iterations:
        idx.refine(refine_iterations)
    return idx
