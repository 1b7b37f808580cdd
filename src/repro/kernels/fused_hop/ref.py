"""Pure-jnp oracle for the multi-expansion hop.

This is exactly what the beam engine's jnp hop path computes when the
visited filter is active, so the oracle and the engine's composed path
are bit-identical.  The Pallas path (``ops.py``) shares the filter half
(:func:`hop_candidates`) and the compaction half (:func:`compact_hop`)
and replaces only the scoring in between with the ``gather_dist`` kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import INVALID
from repro.core.visited import DEFAULT_PROBES, contains, first_occurrence_mask


@functools.partial(jax.jit, static_argnames=("squared", "n_probes"))
def fused_hop_ref(adjacency: jax.Array, vectors: jax.Array,
                  sel_ids: jax.Array, queries: jax.Array, dmax: jax.Array,
                  visited: jax.Array | None = None, *, n_valid: jax.Array,
                  squared: bool = False, n_probes: int = DEFAULT_PROBES):
    """One multi-expansion hop for B lanes.

    Args:
      adjacency: (N, d) int32, INVALID-padded rows.
      vectors: (Nv, m) float — the store rows.
      sel_ids: (B, E) int32 — vertices to expand (INVALID = inactive lane
        slot; nothing of that slot is gathered or scored).
      queries: (B, m) float.
      dmax: (B,) float32 — keep threshold (candidates with dist > dmax are
        dropped; the engine passes ``radius * (1 + eps)``).
      visited: (B, V) int32 visited table or None (no filtering).
      n_valid: () int32 — neighbors >= n_valid are invalid.
    Returns:
      cand_ids (B, E*d) int32 — kept candidates *compacted* to the front in
        discovery order (e-major, j-minor), INVALID-padded;
      cand_dists (B, E*d) float32 — matching distances, inf-padded;
      nbr_ids (B, E*d) int32 — the raw gathered neighbor ids, valid-masked
        (for the caller's visited-set insertion);
      evals (B,) int32 — distance evaluations performed (post-filter).
    """
    flat, vmask, scored = hop_candidates(adjacency, sel_ids, visited,
                                         n_valid=n_valid, n_probes=n_probes)
    safe = jnp.where(scored, flat, 0)
    g = vectors[safe].astype(jnp.float32)                    # (B, Ed, m)
    diff = g - queries.astype(jnp.float32)[:, None, :]
    d2 = jnp.maximum(jnp.sum(diff * diff, axis=-1), 0.0)
    nd = d2 if squared else jnp.sqrt(d2)
    return compact_hop(flat, vmask, scored, nd, dmax)


def hop_candidates(adjacency, sel_ids, visited, *, n_valid,
                   n_probes: int = DEFAULT_PROBES):
    """The hop's filter half: gather the adjacency rows of the selections
    and decide which neighbors get scored.  Returns (flat (B, E*d) raw
    neighbor ids, vmask (B, E*d) valid, scored (B, E*d) valid & first
    occurrence & not visited)."""
    B, E = sel_ids.shape
    d = adjacency.shape[1]
    Ed = E * d
    act = sel_ids != INVALID
    nbrs = adjacency[jnp.where(act, sel_ids, 0)]             # (B, E, d)
    valid = act[:, :, None] & (nbrs != INVALID) & (nbrs < n_valid)
    flat = nbrs.reshape(B, Ed)
    vmask = valid.reshape(B, Ed)

    # first occurrence among valid ids (two expanded vertices may share a
    # neighbor) — the same shared mask the engine's jnp hop applies
    scored = vmask & first_occurrence_mask(flat, vmask)
    if visited is not None:
        scored &= ~contains(visited, flat, n_probes=n_probes)
    return flat, vmask, scored


def compact_hop(flat, vmask, scored, nd, dmax):
    """The hop's output half: radius keep test and stable compaction of the
    scored distances ``nd`` (B, E*d) — kept candidates first, discovery
    order preserved.  Returns the four hop outputs."""
    nd = jnp.where(scored, nd, jnp.inf)
    keep = scored & (nd <= dmax[:, None])
    order = jnp.argsort(~keep, axis=1, stable=True)
    cand_ids = jnp.take_along_axis(jnp.where(keep, flat, INVALID), order,
                                   axis=1)
    cand_d = jnp.take_along_axis(jnp.where(keep, nd, jnp.inf), order, axis=1)
    nbr_out = jnp.where(vmask, flat, INVALID)
    return (cand_ids, cand_d, nbr_out,
            scored.sum(axis=1).astype(jnp.int32))
