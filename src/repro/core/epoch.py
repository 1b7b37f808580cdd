"""Epoch-published graph snapshots: safe concurrent reads under mutation.

The paper's continuous-refinement story ("a well-organized graph structure
at all times", Sec. 1) only pays off in production if refinement can run
*while* queries flow.  The serving layers historically pinned the opposite
invariant — index read-only while an async engine is live — because the
device cache donates buffers on every post-mutation sync, so a flush racing
a writer could observe a half-applied edge surgery (torn read).

This module replaces that restriction with an epoch protocol:

* Writers mutate the live :class:`GraphBuilder` under the index's mutation
  lock and call ``DEGIndex.publish()`` at batch boundaries.  ``publish``
  captures an *independent, immutable* :class:`PublishedEpoch` — graph rows
  (``freeze()``), a copy of the device vector store (the live one is
  donation-invalidated by inserts), the quarantine set, and a
  quarantine-aware medoid — and atomically swaps it in.
* Readers (the bucket dispatch path) ``acquire()`` the current epoch per
  flush and search only its frozen buffers; every lane of a batch therefore
  sees one coherent graph, tagged with ``epoch`` / ``builder_gen`` so a
  replay against the same snapshot must be bit-identical.
* Old epochs are refcounted and retired only when the last in-flight flush
  releases its reference — never under a reader.

The protocol is deliberately wait-free for readers: ``acquire``/``release``
are a refcount under a small lock, writers never block on readers, and
readers never block on writers (they just keep searching the previous
epoch until the next flush picks up the new one).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.obs import clock
from repro.obs.metrics import EPOCH_RETIRED_LAG_MS

from .search import SearchResult, filtered_scan, range_search


class PublishedEpoch:
    """One immutable published generation of a :class:`DEGIndex`.

    Exposes the same ``search_batch`` / ``medoid`` surface the serving
    bucket dispatcher uses on the index itself, so ``buckets.dispatch``
    accepts either interchangeably.  All buffers are independent copies:
    no later builder mutation, donation, or cache drop can touch them.
    """

    __slots__ = ("epoch", "graph", "vectors", "n", "medoid_id", "metric",
                 "params", "quarantine", "builder_gen", "published_at",
                 "superseded_at", "refs", "tags", "tag_index", "_stores",
                 "_lock")

    def __init__(self, *, epoch: int, graph, vectors, n: int, medoid_id: int,
                 metric: str, params, quarantine=(), builder_gen: int = -1,
                 tags=None, tag_index=None):
        self.epoch = int(epoch)
        self.graph = graph               # independent DEGraph (freeze())
        self.vectors = vectors           # independent device copy
        self.n = int(n)
        self.medoid_id = int(medoid_id)
        self.metric = metric
        self.params = params
        self.quarantine = tuple(int(q) for q in quarantine)
        self.builder_gen = int(builder_gen)
        self.published_at = clock.now()
        self.superseded_at: Optional[float] = None
        self.refs = 0                    # guarded by the owning manager
        # row tags (core/tags.py): the device table and the host posting
        # lists, both immutable, or None for an index without tags
        self.tags = tags
        self.tag_index = tag_index
        self._stores: dict = {}          # per-epoch quant stores, lazy
        self._lock = threading.Lock()

    @property
    def dim(self) -> int:
        return int(self.vectors.shape[1])

    def medoid(self) -> int:
        return self.medoid_id

    @property
    def tagged(self) -> bool:
        return self.tags is not None

    def filter_rows(self, filt) -> np.ndarray:
        """The rows of this epoch ``filt`` admits, ascending."""
        return self.tag_index.rows(filt)

    def filter_count(self, filt) -> int:
        """How many rows of this epoch ``filt`` admits."""
        return self.tag_index.count(filt)

    def scan_batch(self, queries, *chunks, k: int) -> SearchResult:
        """Mirror of ``DEGIndex.scan_batch`` over this epoch's rows."""
        return filtered_scan(self.vectors,
                             jnp.asarray(np.asarray(queries, np.float32)),
                             *(jnp.asarray(np.asarray(c, np.int32))
                               for c in chunks),
                             k=k, metric=self.metric)

    def store_for(self, codec: str):
        """Quant store over *this epoch's* vectors (lazy, cached for the
        epoch's lifetime — degraded-ladder rungs traverse sq8)."""
        from repro.quant import make_store

        with self._lock:
            st = self._stores.get(codec)
            if st is None:
                st = make_store(self.vectors, codec, n=self.n)
                self._stores[codec] = st
        return st

    def search_batch(self, queries, seed_ids=None, exclude=None, *, k: int,
                     eps: float = 0.1, beam_width=None, backend: str = "jnp",
                     quantized=None, rerank_k=None, expand_width=None,
                     visited_size=None, hop_backend=None,
                     hop_budget=None, filter_tags=None) -> SearchResult:
        """Mirror of ``DEGIndex.search_batch`` against this epoch's frozen
        buffers.  Shapes and static config match the live index's, so the
        jitted beam program is shared — publishing costs no retrace."""
        p = self.params
        E = p.expand_width if expand_width is None else expand_width
        hb = p.hop_backend if hop_backend is None else hop_backend
        vs = p.visited_size if visited_size is None else visited_size
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        if seed_ids is None:
            seeds = jnp.full((q.shape[0], 1), self.medoid_id,
                             dtype=jnp.int32)
        else:
            seeds = jnp.asarray(np.asarray(seed_ids, np.int32))
            if seeds.ndim == 1:
                seeds = seeds[:, None]
        excl = None if exclude is None else jnp.asarray(
            np.asarray(exclude, np.int32))
        hbud = None if hop_budget is None else jnp.asarray(
            np.asarray(hop_budget, np.int32))
        filt = {} if filter_tags is None else {
            "tags": self.tags,
            "filter_tags": jnp.asarray(np.asarray(filter_tags, np.int32))}
        if quantized in (None, "float32"):
            return range_search(self.graph, self.vectors, q, seeds, k=k,
                                eps=eps, beam_width=beam_width,
                                metric=self.metric, exclude=excl,
                                backend=backend, expand_width=E,
                                visited_size=vs, hop_backend=hb,
                                hop_budget=hbud, **filt)
        store = self.store_for(quantized)
        rk = int(rerank_k) if rerank_k else 4 * k
        return range_search(self.graph, store, q, seeds, k=k, eps=eps,
                            beam_width=beam_width, metric=self.metric,
                            exclude=excl, backend=backend,
                            rerank_k=max(rk, k), exact_vectors=self.vectors,
                            expand_width=E, visited_size=vs, hop_backend=hb,
                            hop_budget=hbud, **filt)


class EpochManager:
    """Refcounted publish / acquire / release / retire state machine.

    * ``publish(ep)`` swaps the current epoch; the superseded one is
      retired immediately if unreferenced, else when its last reader
      releases.
    * ``acquire()`` hands the current epoch to a flush (refcount++).
    * ``release(ep)`` drops a flush's reference; a superseded epoch whose
      refcount reaches zero is retired (buffers become collectible) and
      its supersede→retire lag is observed on the ``epoch_retired_lag_ms``
      histogram — the backpressure signal for publish frequency.
    """

    def __init__(self, owner=None):
        self._lock = threading.Lock()
        self._owner = owner              # DEGIndex, for metrics resolution
        self.current: Optional[PublishedEpoch] = None
        self.live: dict[int, PublishedEpoch] = {}
        self.retired_total = 0

    @property
    def next_epoch(self) -> int:
        with self._lock:
            return 0 if self.current is None else self.current.epoch + 1

    def publish(self, ep: PublishedEpoch) -> None:
        with self._lock:
            old = self.current
            self.current = ep
            self.live[ep.epoch] = ep
            if old is not None:
                old.superseded_at = clock.now()
                if old.refs == 0:
                    self._retire_locked(old)

    def acquire(self) -> PublishedEpoch:
        with self._lock:
            ep = self.current
            if ep is None:
                raise RuntimeError("no epoch published yet")
            ep.refs += 1
            return ep

    def release(self, ep: PublishedEpoch) -> None:
        with self._lock:
            ep.refs -= 1
            if ep.refs <= 0 and ep is not self.current:
                self._retire_locked(ep)

    def live_epochs(self) -> list[int]:
        with self._lock:
            return sorted(self.live)

    def _retire_locked(self, ep: PublishedEpoch) -> None:
        if self.live.pop(ep.epoch, None) is None:
            return                       # already retired
        self.retired_total += 1
        metrics = getattr(self._owner, "metrics", None)
        if metrics is not None and ep.superseded_at is not None:
            lag_ms = (clock.now() - ep.superseded_at) * 1e3
            metrics.histogram(EPOCH_RETIRED_LAG_MS).observe(lag_ms)
