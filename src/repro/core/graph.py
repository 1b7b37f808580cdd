"""Graph containers for the Dynamic Exploration Graph.

Two layers:

* :class:`DEGraph` — an immutable JAX pytree used on device (search, serving,
  dry-run, and the device-resident construction programs).  The
  even-regularity of DEG (paper Sec. 5.1) means the *entire* graph is one
  dense ``(capacity, d) int32`` adjacency array plus a matching ``float32``
  weight array.  This is the core of the TPU adaptation: every search hop is
  a fixed-shape gather, there is no raggedness and no hubs by construction.

* :class:`GraphBuilder` — a mutable host-side (numpy) twin used by the
  incremental construction (Alg. 3) and edge optimization (Alg. 4/5), which
  are graph-surgery procedures.

Buffer ownership (ARCHITECTURE.md "Device-resident construction layering"):
the numpy arrays are the mutable source of truth; the builder additionally
owns a *device cache* of both buffers.  Every mutator records the touched
rows, and :meth:`device_graph` re-syncs the cache by scattering only the
dirty rows through a **donated** jitted update — per-wave sync cost is
O(rows touched), not O(capacity).  Because the scatter donates the previous
cache buffers, a :class:`DEGraph` obtained from ``device_graph()`` /
``freeze()`` is valid only until the *next* sync after a mutation; consumers
that need a stable snapshot must copy (``to_builder()`` does).

Slots that are transiently unused hold ``INVALID`` (= -1).  A *valid* DEG has
no ``INVALID`` entries among its first ``n`` rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

INVALID = -1

# full re-upload beats the gather+scatter once more than capacity / this
# fraction of the rows are dirty
_FULL_SYNC_FRACTION = 4


def pow2_bucket(n: int, floor: int = 1) -> int:
    """Round up to a power of two (>= floor) — the lane/row bucketing every
    batched construction path uses so repeated calls reuse a handful of
    compiled jit entries instead of one per distinct size."""
    p = floor
    while p < n:
        p *= 2
    return p


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DEGraph:
    """Immutable device-side even-regular graph."""

    adjacency: jax.Array          # (capacity, d) int32, INVALID-padded
    weights: jax.Array            # (capacity, d) float32
    n: jax.Array                  # () int32 — number of active vertices

    @property
    def capacity(self) -> int:
        return self.adjacency.shape[0]

    @property
    def degree(self) -> int:
        return self.adjacency.shape[1]

    def to_builder(self) -> "GraphBuilder":
        b = GraphBuilder.__new__(GraphBuilder)
        b.adjacency = np.asarray(self.adjacency).copy()
        b.weights = np.asarray(self.weights).copy()
        b.n = int(self.n)
        b._init_device_state()
        return b


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_rows(adj: jax.Array, w: jax.Array, rows: jax.Array,
                  adj_rows: jax.Array, w_rows: jax.Array):
    return adj.at[rows].set(adj_rows), w.at[rows].set(w_rows)


class GraphBuilder:
    """Mutable host-side graph for construction / refinement."""

    def __init__(self, capacity: int, degree: int):
        if degree < 4 or degree % 2 != 0:
            raise ValueError(f"DEG degree must be even and >= 4, got {degree}")
        if capacity < degree + 1:
            raise ValueError("capacity must be at least degree + 1")
        self.adjacency = np.full((capacity, degree), INVALID, dtype=np.int32)
        self.weights = np.zeros((capacity, degree), dtype=np.float32)
        self.n = 0
        # lifetime edge-surgery counters (plain ints — two adds per edge
        # op; obs snapshots read them, see DEGIndex metrics wiring).  The
        # add/remove *ratio* is the churn signal: refine sweeps that swap
        # without converging show up as counters racing with no
        # refine_improved_edges_total growth.
        self.edges_added = 0
        self.edges_removed = 0
        self._init_device_state()

    def _init_device_state(self) -> None:
        self._dev_adj = None          # device cache of adjacency/weights
        self._dev_w = None
        self._dirty: set[int] = set() # host rows ahead of the device cache
        # Monotonic mutation counter: bumped on every host-side write
        # (including bulk loads and capacity growth).  Epoch publication
        # stamps this onto each published snapshot so a reader can prove
        # which graph state a flush actually searched — the guard against
        # the stale-epoch hazard where a cached device twin silently mixes
        # rows from before and after a mutation.
        self._gen = 0
        self._dev_sync_gen = -1       # generation the device cache matches

    # -- basic accessors -------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.adjacency.shape[0]

    @property
    def degree(self) -> int:
        return self.adjacency.shape[1]

    def neighbors(self, v: int) -> np.ndarray:
        row = self.adjacency[v]
        return row[row != INVALID]

    def neighbor_weights(self, v: int) -> np.ndarray:
        row = self.adjacency[v]
        return self.weights[v][row != INVALID]

    def vertex_degree(self, v: int) -> int:
        return int((self.adjacency[v] != INVALID).sum())

    @property
    def generation(self) -> int:
        """Monotonic mutation counter of the host graph (see
        ``_init_device_state``); equal generations imply identical content
        under the single-writer lock discipline."""
        return self._gen

    def device_generation(self) -> int:
        """Generation the cached device buffers correspond to, or -1 when
        no cache exists.  ``device_generation() == generation`` iff a
        ``device_graph()`` call right now would be a pure cache hit."""
        if self._dev_adj is None:
            return -1
        return self._dev_sync_gen if not self._dirty else -1

    def edge_slot(self, u: int, v: int) -> int:
        """Slot of ``v`` in ``u``'s row, or -1 — the one lookup shared by
        ``has_edge`` / ``edge_weight`` / ``remove_edge`` (argmax over the
        fixed-width row; no index-array allocation per call)."""
        row = self.adjacency[u]
        s = int(np.argmax(row == v))
        return s if row[s] == v else -1

    def has_edge(self, u: int, v: int) -> bool:
        return self.edge_slot(u, v) >= 0

    def edge_weight(self, u: int, v: int) -> float:
        s = self.edge_slot(u, v)
        if s < 0:
            raise KeyError(f"no edge ({u}, {v})")
        return float(self.weights[u, s])

    # -- device sync -----------------------------------------------------
    def mark_dirty(self, *rows: int) -> None:
        """Record host-side row writes so the next ``device_graph()`` can
        re-sync the device cache.  Mutator methods call this themselves;
        callers writing ``adjacency`` / ``weights`` directly must too."""
        self._gen += 1
        if self._dev_adj is not None:
            self._dirty.update(int(r) for r in rows)

    def invalidate_device(self) -> None:
        """Drop the device cache entirely (bulk host rewrites)."""
        self._gen += 1
        self._drop_cache()
        self._dev_adj = self._dev_w = None
        self._dirty = set()

    def _drop_cache(self) -> None:
        """Free the cached device buffers.  Like the donating scatter path,
        this makes any still-held ``device_graph()`` twin raise on use
        (deterministic failure) instead of silently serving stale rows —
        the documented contract; holders use ``freeze()``."""
        for buf in (self._dev_adj, self._dev_w):
            if buf is not None:
                buf.delete()

    def device_graph(self) -> DEGraph:
        """The device twin of the current host graph.

        First call (or after ``invalidate_device`` / ``grow``) uploads the
        whole buffers; afterwards only the dirty rows are scattered into the
        cache via a donated jit — the donation means any previously returned
        :class:`DEGraph` is invalidated by this call whenever there were
        pending writes.  Dirty-row counts are bucketed to powers of two so
        repeated waves reuse a handful of compiled entries."""
        full = (self._dev_adj is None
                or self._dev_adj.shape != self.adjacency.shape)
        if full or self._dirty:
            with span("deg.graph.sync", device=True):
                self._sync(full)
        return DEGraph(adjacency=self._dev_adj, weights=self._dev_w,
                       n=jnp.asarray(self.n, dtype=jnp.int32))

    def _sync(self, full: bool) -> None:
        """Bring the device twin up to date: the whole buffers, or the
        dirty rows scattered in."""
        rows = None if full else np.fromiter(self._dirty, dtype=np.int32)
        if full or rows.size * _FULL_SYNC_FRACTION >= self.capacity:
            self._drop_cache()         # stale twins must fail loudly
            self._dev_adj = jnp.asarray(self.adjacency)
            self._dev_w = jnp.asarray(self.weights)
        else:
            rows.sort()
            width = pow2_bucket(rows.size)
            # idempotent pad: repeat the last dirty row
            rows = np.concatenate(
                [rows, np.full(width - rows.size, rows[-1], np.int32)])
            self._dev_adj, self._dev_w = _scatter_rows(
                self._dev_adj, self._dev_w, jnp.asarray(rows),
                jnp.asarray(self.adjacency[rows]),
                jnp.asarray(self.weights[rows]))
        self._dirty = set()
        self._dev_sync_gen = self._gen

    # -- mutation --------------------------------------------------------
    def _free_slot(self, v: int) -> int:
        s = self.edge_slot(v, INVALID)
        if s < 0:
            raise RuntimeError(f"vertex {v} already has degree {self.degree}")
        return s

    def add_edge(self, u: int, v: int, w: float) -> None:
        if u == v:
            raise ValueError(f"self loop at {u}")
        if self.has_edge(u, v):
            raise ValueError(f"duplicate edge ({u}, {v})")
        su, sv = self._free_slot(u), self._free_slot(v)
        self.adjacency[u, su] = v
        self.weights[u, su] = w
        self.adjacency[v, sv] = u
        self.weights[v, sv] = w
        self.edges_added += 1
        self.mark_dirty(u, v)

    def remove_edge(self, u: int, v: int) -> float:
        w = None
        for a, b in ((u, v), (v, u)):
            s = self.edge_slot(a, b)
            if s < 0:
                raise KeyError(f"no edge ({a}, {b})")
            w = float(self.weights[a, s])
            self.adjacency[a, s] = INVALID
            self.weights[a, s] = 0.0
        self.edges_removed += 1
        self.mark_dirty(u, v)
        return w

    def replace_edges(self, v_rows: np.ndarray, v_slots: np.ndarray,
                      bs: np.ndarray, ns: np.ndarray, w_vb: np.ndarray,
                      w_vn: np.ndarray) -> np.ndarray:
        """Vectorized Alg. 3 edge swaps: for every pair t, the edge
        (bs[t], ns[t]) becomes (v_rows[t], bs[t]) + (v_rows[t], ns[t]),
        written into ``v_rows[t]``'s row at slots ``v_slots[t]`` and
        ``v_slots[t] + 1``.

        Contract (the device-wave apply in ``core/build.py``): the claimed
        edges are pairwise-distinct, so every write lands in a distinct
        (row, slot); ``v_rows`` are fresh vertices whose target slots are
        empty.  Pairs whose edge is absent (a wave conflict) are skipped —
        the returned bool mask says which pairs were applied."""
        m = len(bs)
        if m == 0:
            return np.zeros(0, dtype=bool)
        idx = np.arange(m)
        rows_b = self.adjacency[bs]
        s1 = np.argmax(rows_b == ns[:, None], axis=1)
        ok = rows_b[idx, s1] == ns
        rows_n = self.adjacency[ns]
        s2 = np.argmax(rows_n == bs[:, None], axis=1)
        ok &= rows_n[idx, s2] == bs
        bs, ns, s1, s2 = bs[ok], ns[ok], s1[ok], s2[ok]
        v_r, v_s = v_rows[ok], v_slots[ok]
        w_b, w_n = w_vb[ok], w_vn[ok]
        self.adjacency[bs, s1] = v_r
        self.weights[bs, s1] = w_b
        self.adjacency[ns, s2] = v_r
        self.weights[ns, s2] = w_n
        self.adjacency[v_r, v_s] = bs
        self.weights[v_r, v_s] = w_b
        self.adjacency[v_r, v_s + 1] = ns
        self.weights[v_r, v_s + 1] = w_n
        # each applied pair removes (b, n) and adds (v, b) + (v, n)
        self.edges_removed += len(bs)
        self.edges_added += 2 * len(bs)
        self.mark_dirty(*bs, *ns, *v_r)
        return ok

    def clear_vertex(self, v: int) -> None:
        """Reset one row to the empty state (deletion compaction)."""
        self.adjacency[v] = INVALID
        self.weights[v] = 0.0
        self.mark_dirty(v)

    def load(self, adjacency: np.ndarray, weights: np.ndarray,
             n: int) -> None:
        """Bulk-load a stored graph (index restore paths)."""
        self.adjacency[: adjacency.shape[0]] = adjacency
        self.weights[: weights.shape[0]] = weights
        self.n = int(n)
        self.invalidate_device()

    def add_vertex(self) -> int:
        if self.n >= self.capacity:
            raise RuntimeError("capacity exhausted; grow() first")
        v = self.n
        self.n += 1
        self._gen += 1                 # n is part of the graph content
        return v

    def grow(self, new_capacity: int) -> None:
        if new_capacity <= self.capacity:
            return
        d = self.degree
        adj = np.full((new_capacity, d), INVALID, dtype=np.int32)
        w = np.zeros((new_capacity, d), dtype=np.float32)
        adj[: self.capacity] = self.adjacency
        w[: self.capacity] = self.weights
        self.adjacency, self.weights = adj, w
        self.invalidate_device()

    # -- snapshot / rollback (Alg. 4 step 6 "revert all changes") --------
    def snapshot(self, vertices: Iterable[int]) -> dict:
        vs = sorted(set(int(v) for v in vertices))
        return {
            "vs": vs,
            "adj": self.adjacency[vs].copy(),
            "w": self.weights[vs].copy(),
        }

    def restore(self, snap: dict) -> None:
        self.adjacency[snap["vs"]] = snap["adj"]
        self.weights[snap["vs"]] = snap["w"]
        self.mark_dirty(*snap["vs"])

    # -- conversion ------------------------------------------------------
    def freeze(self) -> DEGraph:
        """An *independent* device snapshot, safe to hold across later
        mutations (the pre-device-cache contract).  Hot paths that consume
        the graph transiently use :meth:`device_graph` instead — its
        buffers are donated away by the next post-mutation sync."""
        g = self.device_graph()
        return DEGraph(adjacency=jnp.array(g.adjacency),
                       weights=jnp.array(g.weights), n=g.n)

    # -- stats used by Alg. 5 / benchmarks -------------------------------
    def longest_edge_slot(self, v: int) -> int:
        row = self.adjacency[v]
        w = np.where(row != INVALID, self.weights[v], -np.inf)
        return int(np.argmax(w))

    def average_neighbor_distance(self) -> float:
        """Eq. (4) over the whole graph (active vertices only)."""
        if self.n == 0:
            return 0.0
        adj = self.adjacency[: self.n]
        w = self.weights[: self.n]
        valid = adj != INVALID
        denom = np.maximum(valid.sum(axis=1), 1)
        per_vertex = (w * valid).sum(axis=1) / denom
        return float(per_vertex.mean())


def complete_graph(vectors: np.ndarray, degree: int, capacity: int,
                   metric_name: str = "l2") -> GraphBuilder:
    """The smallest possible DEG_d: the complete graph K_{d+1} (Sec. 5.1)."""
    from .distances import get_metric

    metric = get_metric(metric_name)
    k = degree + 1
    if vectors.shape[0] < k:
        raise ValueError(f"need at least {k} vectors for DEG_{degree}")
    b = GraphBuilder(capacity, degree)
    pts = jnp.asarray(vectors[:k])
    dmat = np.asarray(metric.cross(pts, pts))
    for _ in range(k):
        b.add_vertex()
    for i in range(k):
        for j in range(i + 1, k):
            b.add_edge(i, j, float(dmat[i, j]))
    return b
