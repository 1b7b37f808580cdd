"""Per-lane visited filter for the multi-expansion beam engine.

The seed engine answered "have I already proposed this vertex?" with an
all-pairs broadcast against the live beam — O(L) compares per candidate,
O(B·E·d·L) per hop.  This module replaces that with a fixed-size
**open-addressing hash set** per query lane, carried through the search
``while_loop`` inside :class:`repro.core.beam.BeamState`:

* membership is O(P) gathered compares per candidate (``P = n_probes``,
  default :data:`DEFAULT_PROBES`), independent of beam width;
* insertion is ``P`` rounds of *deterministic* parallel claiming — empty
  slots are claimed with a scatter-``max`` (ids are non-negative, empty
  slots hold ``INVALID`` = -1), so same-slot races resolve to the largest
  id order-independently, and losers retry their next probe position;
* the table is best-effort by construction: an id whose probe sequence is
  exhausted is simply not recorded.  A dropped insert can only cause a
  re-scored candidate later (a wasted distance evaluation, and — if the
  vertex still sits in the beam — a duplicate entry that
  ``beam.extract(dedup=True)`` removes at result time), never a missed
  vertex, so search correctness does not depend on table occupancy.

Because the visited set remembers vertices that were *evicted* from the
beam (the broadcast dedup forgets them), a visited-filtered search performs
at most as many distance evaluations as the seed semantics; the trajectory
— and the ``evals`` counters — can therefore differ from the E=1 broadcast
engine.  See ARCHITECTURE.md ("Multi-expansion beam layering").

Every membership test, the ``fused_hop`` path's included, goes through
:func:`contains`, so the probe-position formula lives only in
:func:`probe_positions`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .graph import INVALID, pow2_bucket

Array = jax.Array

# Knuth multiplicative hash + a golden-ratio second hash (forced odd) for
# double hashing; the table size is a power of two so ``& (V - 1)`` folds.
_MULT1 = 2654435761        # 2^32 / phi, Knuth
_MULT2 = 0x9E3779B1        # golden-ratio constant
DEFAULT_PROBES = 4


def probe_positions(ids: Array, n_slots: int, n_probes: int) -> Array:
    """Probe sequence of every id: (...,) int32 ids -> (..., P) int32 slot
    positions in [0, n_slots).  ``n_slots`` must be a power of two."""
    x = ids.astype(jnp.uint32)
    h1 = x * jnp.uint32(_MULT1)
    h2 = (x * jnp.uint32(_MULT2)) | jnp.uint32(1)          # odd stride
    t = jnp.arange(n_probes, dtype=jnp.uint32)
    pos = (h1[..., None] + t * h2[..., None]) & jnp.uint32(n_slots - 1)
    return pos.astype(jnp.int32)


def make_table(batch: int, n_slots: int) -> Array:
    """Empty (B, V) table (all INVALID).  ``n_slots`` is rounded up to a
    power of two — the probe fold is ``& (V - 1)``, which only addresses
    the whole table for pow2 sizes (a 1000-slot table would silently use
    256 slots)."""
    return jnp.full((batch, pow2_bucket(n_slots)), INVALID,
                    dtype=jnp.int32)


def contains(table: Array, ids: Array, *,
             n_probes: int = DEFAULT_PROBES) -> Array:
    """(B, V) table, (B, C) ids -> (B, C) bool membership (INVALID never a
    member).  An id is present iff it is stored at one of its P probe
    slots."""
    B, C = ids.shape
    V = table.shape[1]
    pos = probe_positions(ids, V, n_probes)                # (B, C, P)
    vals = jnp.take_along_axis(table, pos.reshape(B, C * n_probes),
                               axis=1).reshape(B, C, n_probes)
    return (vals == ids[..., None]).any(axis=-1) & (ids != INVALID)


def insert(table: Array, ids: Array, mask: Array, *,
           n_probes: int = DEFAULT_PROBES) -> Array:
    """Insert ``ids`` where ``mask`` into each lane's table (best-effort).

    Ids already present anywhere in their probe sequence are skipped
    outright (re-inserting a member is a strict no-op, so two callers that
    insert supersets of each other's id sets produce bit-identical
    tables).  The rest run P rounds of probe-claim: in round t every
    still-unplaced id reads its slot ``pos[..., t]`` and claims it if
    empty via scatter-``max`` (deterministic under same-slot races — the
    largest id wins order-independently, and the loser retries at its next
    probe position).  Ids whose P probes are all occupied are dropped.
    """
    B, C = ids.shape
    V = table.shape[1]
    pos = probe_positions(ids, V, n_probes)                # (B, C, P)
    lane = jnp.arange(B)[:, None]
    vals = jnp.take_along_axis(table, pos.reshape(B, C * n_probes),
                               axis=1).reshape(B, C, n_probes)
    present = (vals == ids[..., None]).any(axis=-1)
    need0 = mask & (ids != INVALID) & ~present

    def body(t, carry):
        table, need = carry
        p = jax.lax.dynamic_index_in_dim(pos, t, axis=2, keepdims=False)
        cur = jnp.take_along_axis(table, p, axis=1)
        need = need & (cur != ids)       # a same-batch duplicate placed it
        claim = need & (cur == INVALID)
        table = table.at[lane, p].max(jnp.where(claim, ids, INVALID))
        placed = jnp.take_along_axis(table, p, axis=1) == ids
        return table, need & ~placed

    table, _ = jax.lax.fori_loop(0, n_probes, body, (table, need0))
    return table


def first_occurrence_mask(ids: Array, valid: Array) -> Array:
    """(B, C) bool: is position j the first occurrence of ``ids[b, j]``
    among the valid positions of lane b?  Masked lanes get unique negative
    sentinels so they never alias each other or real ids.

    This is THE intra-block dedup of the multi-expansion hop — shared by
    the engine's jnp paths and the ``fused_hop`` oracle (whose filter half
    the Pallas hop reuses) so they stay bit-identical."""
    import numpy as np

    C = ids.shape[1]
    sent = -(jnp.arange(C, dtype=jnp.int32) + 2)
    tagged = jnp.where(valid, ids, sent[None, :])
    lower = np.tril(np.ones((C, C), bool), -1)       # j' < j, trace-safe
    dup = ((tagged[:, :, None] == tagged[:, None, :]) & lower).any(axis=2)
    return ~dup


def default_size(beam_width: int, degree: int) -> int:
    """Table-size heuristic: comfortably above the unique-visit count of a
    typical search (≈ hops · new-neighbor fraction · degree, which scales
    with ``beam_width * degree``), rounded to a power of two for the
    ``& (V-1)`` fold.  Dropped inserts degrade gracefully (see module
    docstring), so this is a load-factor target, not a hard capacity."""
    return pow2_bucket(max(512, beam_width * max(degree, 1)))
