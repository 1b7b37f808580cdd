"""The vector store the beam engine traverses.

:class:`VectorStore` sits between ``core/graph.py`` (which owns topology)
and ``core/beam.py`` (which owns traversal): the engine asks the store for
seed/neighbor distances and never touches a raw ``(n, m)`` array again.
It is a registered-dataclass pytree — ``data``/``scale``/``codebooks`` are
leaves, the codec name is static — so it passes through ``jax.jit`` /
``shard_map`` boundaries exactly like :class:`repro.core.graph.DEGraph`
does.

Four views behind one interface:

* ``float32`` — the exact store.  ``decode`` is the identity and
  ``neighbor_distances`` lowers to the *same ops* as the pre-quantization
  engine, so this path stays bit-identical (pinned by the golden fixture).
* ``fp16`` — half-precision rows, gathered at half width and upcast
  per-tile inside the kernel.
* ``sq8`` — int8 codes + per-dimension scale; the hot gather path runs the
  fused ``kernels/gather_dist_q`` gather→dequant→distance kernel (with
  ``backend="pallas"``).
* ``pq`` — product-quantized uint8 codes (one byte per subspace) + shared
  ``(m_sub, 256, dsub)`` codebooks; the hot path runs the fused
  ``kernels/pq_adc`` LUT-ADC kernel, which never decodes — for l2 the
  per-query sub-distance table reproduces the exact distance to the
  decoded vector (``quant.pq``).

The store deliberately does NOT hold the exact copy used by two-stage
rerank — that stays with the index owner (host / cold path); see
ARCHITECTURE.md ("Quantized store layering").
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import codec as C
from . import pq as PQ

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class VectorStore:
    """Encoded vector rows + dequant state behind one distance interface."""

    data: Array    # (capacity, m) f32/f16/int8 — or (capacity, m_sub) uint8
    scale: Array   # (m,) float32 — sq8 dequant scale (ones otherwise)
    codec: str = dataclasses.field(metadata=dict(static=True))
    #: (m_sub, 256, dsub) float32 k-means codebooks — pq only, else None
    codebooks: Optional[Array] = None

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def dim(self) -> int:
        if self.codec == "pq":      # data rows hold m_sub code bytes, not m
            m_sub, _, dsub = self.codebooks.shape
            return m_sub * dsub
        return self.data.shape[1]

    @property
    def exact(self) -> bool:
        return self.codec == "float32"

    def decode(self, ids: Array) -> Array:
        """Gather rows by id and decode to float32 (identity for float32).

        Ids are clamped to ``[0, capacity)`` the way ``gather_dist``'s
        ``safe_ids`` are: callers mask INVALID (-1) lanes *after* the
        distance, and an unclipped ``-1`` would silently wrap to the last
        row and feed a junk vector into the jnp distance path and the
        exact rerank.
        """
        safe = jnp.clip(ids, 0, self.capacity - 1)
        if self.codec == "pq":
            return PQ.decode(self.data[safe], self.codebooks)
        return C.decode(self.codec, self.data[safe], self.scale)

    def neighbor_distances(self, queries: Array, nbr_ids: Array,
                           metric_name: str, backend: str = "jnp") -> Array:
        """dist(q_b, decode(row ids[b, j])) for (B, d) ids -> (B, d).

        The one call the beam engine makes per hop.  ``backend='pallas'``
        routes l2 to the fused gather kernels (``gather_dist`` for float
        codecs, ``gather_dist_q`` for sq8, ``pq_adc`` for pq — the last
        never decodes: it scans gathered code bytes against a per-query
        LUT built once in VMEM) and raises for any other metric;
        ``backend='jnp'`` takes the gather+pair path, which for float32 is
        the exact pre-store program.
        """
        from repro.core.distances import get_metric

        if backend == "pallas":
            if metric_name != "l2":
                raise ValueError(
                    "the pallas distance kernels are l2 only, got metric "
                    f"{metric_name!r} (use backend='jnp')")
            if self.codec == "sq8":
                from repro.kernels.gather_dist_q import ops as gdq_ops

                return gdq_ops.gather_dist_q(self.data, self.scale, nbr_ids,
                                             queries)
            if self.codec == "pq":
                from repro.kernels.pq_adc import ops as adc_ops

                return adc_ops.pq_adc(self.data, self.codebooks, nbr_ids,
                                      queries)
            from repro.kernels.gather_dist import ops as gd_ops

            return gd_ops.gather_dist(self.data, nbr_ids, queries)
        if backend != "jnp":
            raise ValueError(f"unknown distance backend {backend!r}")
        metric = get_metric(metric_name)
        nvecs = self.decode(nbr_ids)                       # (B, d, m)
        return metric.pair(queries[:, None, :], nvecs)     # (B, d)

    # -- footprint ---------------------------------------------------------
    def memory_bytes(self, n=None) -> int:
        """Store bytes for ``n`` rows (default: full capacity) + dequant
        state."""
        rows = self.capacity if n is None else int(n)
        return C.store_bytes(self.codec, rows, self.dim)


def make_store(vectors: Array, codec: str = "float32", *,
               n: Optional[int]) -> VectorStore:
    """Encode ``vectors`` under ``codec``.

    ``n`` is the live-row count and is deliberately a *required* keyword:
    calibrated codecs (sq8 scales, pq codebooks) must see only the live
    vertices — calibrating over capacity-padding rows silently skews the
    sq8 range and pulls pq centroids toward zero.  Pass ``n=None``
    explicitly only when every row is live.
    """
    vectors = jnp.asarray(vectors)
    m = vectors.shape[1]
    if codec == "pq":
        books = jnp.asarray(PQ.fit(vectors, n))
        return VectorStore(data=PQ.encode(vectors, books),
                           scale=jnp.ones((m,), jnp.float32),
                           codec=codec, codebooks=books)
    if codec == "sq8":
        scale = C.calibrate_sq8_scale(vectors, n)
    else:
        scale = jnp.ones((m,), jnp.float32)
    return VectorStore(data=C.encode(codec, vectors, scale), scale=scale,
                       codec=codec)


def as_store(vectors) -> VectorStore:
    """Normalize the beam engine's ``vectors`` argument: raw float arrays
    become exact float32 stores (identical ops), stores pass through."""
    if isinstance(vectors, VectorStore):
        return vectors
    vectors = jnp.asarray(vectors)
    return VectorStore(data=vectors,
                       scale=jnp.ones((vectors.shape[1],), jnp.float32),
                       codec="float32")
