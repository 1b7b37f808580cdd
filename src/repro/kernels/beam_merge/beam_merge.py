"""Fused beam-merge Pallas TPU kernel (bitonic partial merge).

One hop of the DEG range search must fold ``d`` freshly scored neighbor
candidates into the distance-sorted beam of static width ``L``.  The seed
implementation re-sorted the whole ``(B, L+d)`` concatenation with
``argsort`` every hop — an O((L+d) log^2 (L+d)) comparator sort that ignores
the fact that ``L`` of the entries are *already sorted*.  This kernel
exploits that invariant:

1. the ``d`` candidates are bitonic-sorted (a log^2 d network over lanes —
   cheap: d is the graph degree, 8..32);
2. ``[beam asc | +inf pads | candidates desc]`` is a bitonic sequence of
   power-of-two length T, so one *bitonic merge* (log T compare-exchange
   stages of pure VPU selects — no gather, no scatter, no sort primitive)
   produces the fully sorted T-vector;
3. the first ``L`` lanes are the new beam.

Every compare-exchange is keyed on the pair ``(distance, rank)`` where
``rank`` is the position in the virtual ``[beam | candidates]``
concatenation.  Ranks are unique, so the network computes a *total* order
that coincides exactly with a stable argsort of the concatenation — the
kernel is bit-identical to the seed merge, not merely equivalent up to
ties.  The same property makes the network deterministic on all backends.

Two forms of the same network.  ``merge_beam_candidates`` exchanges
partner lanes through a ``(..., c, 2, j)`` reshape; it is the XLA path
``ops.beam_merge(backend="jnp")`` (the jitted search loop's default, and
the baseline the microbenchmark compares against argsort).  Mosaic
refuses that reshape, so the Pallas kernel runs ``_rotate_stage``, which
fetches each lane's partner by lane rotation, on rows laid out by
``_layout`` in XLA.  Both compute the same total order.

Payload layout: distances f32 + rank i32 + three payload channels
(vertex id i32, checked flag, excluded flag).  Flags travel as int32 inside
the kernel — TPU has no 1-bit vregs; ``ops.py`` converts at the boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _cmp_swap(fields, j: int, desc=None):
    """One compare-exchange stage at stride ``j`` over the last axis.

    ``fields[0]`` is the distance key, ``fields[1]`` the rank tie-break; the
    rest are payload.  Partner lanes are exchanged via a reshape to
    ``(..., c, 2, j)`` — a pure relayout, no gather.  ``desc`` (broadcast
    over ``(..., c, j)``) flips the direction per chunk for the sort
    network; ``None`` means ascending everywhere (the merge network).
    """
    d = fields[0]
    lead, T = d.shape[:-1], d.shape[-1]
    c = T // (2 * j)

    def halves(x):
        v = x.reshape(*lead, c, 2, j)
        return v[..., 0, :], v[..., 1, :]

    lo_d, hi_d = halves(fields[0])
    lo_r, hi_r = halves(fields[1])
    # (dist, rank) lexicographic: does the high lane belong before the low?
    swap = (hi_d < lo_d) | ((hi_d == lo_d) & (hi_r < lo_r))
    if desc is not None:
        swap = swap != desc            # XOR: descending chunks invert
    out = []
    for x in fields:
        lo, hi = halves(x)
        new_lo = jnp.where(swap, hi, lo)
        new_hi = jnp.where(swap, lo, hi)
        out.append(jnp.stack([new_lo, new_hi], axis=-2).reshape(*lead, T))
    return tuple(out)


def _bitonic_sort(fields):
    """Full bitonic sort (ascending by (dist, rank)) over the last axis."""
    T = fields[0].shape[-1]
    k = 2
    while k <= T:
        j = k // 2
        while j >= 1:
            c = T // (2 * j)
            chunk_start = jnp.arange(c) * (2 * j)
            desc = ((chunk_start // k) % 2 == 1)[:, None]
            fields = _cmp_swap(fields, j, desc)
            j //= 2
        k *= 2
    return fields


def _bitonic_merge(fields):
    """Merge network: bitonic input -> ascending by (dist, rank)."""
    T = fields[0].shape[-1]
    j = T // 2
    while j >= 1:
        fields = _cmp_swap(fields, j)
        j //= 2
    return fields


def merge_beam_candidates(beam_dists, beam_payload, cand_dists, cand_payload,
                          *, out_width: int | None = None,
                          presort: str = "auto"):
    """The fused merge on plain arrays (shared by kernel body and jnp path).

    Args:
      beam_dists: (..., L) f32, ascending (stable order — the beam
        invariant).
      beam_payload: tuple of (..., L) arrays carried through the permutation.
      cand_dists: (..., d) f32, arbitrary order (masked lanes = +inf).
      cand_payload: tuple of (..., d) arrays (same arity as beam_payload).
      presort: how the candidate block is sorted before the merge network.
        "network" = the bitonic sort (the only form a Pallas TPU kernel
        body can lower); "argsort" = one stable XLA sort + gathers — a
        stable sort by distance IS the (dist, rank) order, so the two are
        bit-identical; "auto" = argsort for wide multi-expansion blocks
        (d >= 32, where log^2 d network passes lose to one sort on CPU),
        network otherwise.
    Returns:
      (dists, payload...) each (..., out_width or L) — the first entries of
      the stable-sorted [beam | candidates] concatenation.
    """
    lead = beam_dists.shape[:-1]
    L = beam_dists.shape[-1]
    d = cand_dists.shape[-1]
    out_width = L if out_width is None else out_width
    dp = _next_pow2(d)
    T = _next_pow2(L + dp)
    i32 = jnp.int32
    if presort == "auto":
        presort = "argsort" if d >= 32 else "network"

    # --- candidates: pad to dp, sort asc by (dist, rank), reverse -> desc --
    pad_c = dp - d
    c_dists = jnp.concatenate(
        [cand_dists, jnp.full((*lead, pad_c), _INF, cand_dists.dtype)], -1)
    c_pay = tuple(
        jnp.concatenate([p, jnp.zeros((*lead, pad_c), p.dtype)], -1)
        for p in cand_payload)
    if presort == "argsort":
        order = jnp.argsort(c_dists, axis=-1, stable=True)
        take = functools.partial(jnp.take_along_axis, indices=order, axis=-1)
        c_fields = ((take(c_dists), (L + order).astype(i32))
                    + tuple(take(p) for p in c_pay))
    else:
        c_rank = jnp.broadcast_to(L + jnp.arange(dp, dtype=i32), (*lead, dp))
        c_fields = _bitonic_sort((c_dists, c_rank) + c_pay)
    c_fields = tuple(x[..., ::-1] for x in c_fields)

    # --- bitonic sequence: [beam asc | +inf pads | candidates desc] --------
    mid = T - L - dp
    b_rank = jnp.broadcast_to(jnp.arange(L, dtype=i32), (*lead, L))
    pad_dists = jnp.full((*lead, mid), _INF, beam_dists.dtype)
    pad_rank = jnp.broadcast_to(T + jnp.arange(mid, dtype=i32), (*lead, mid))

    def cat(b, pad, c):
        return jnp.concatenate([b, pad, c], -1)

    fields = (cat(beam_dists, pad_dists, c_fields[0]),
              cat(b_rank, pad_rank, c_fields[1]))
    for bp, cp in zip(beam_payload, c_fields[2:]):
        fields += (cat(bp, jnp.zeros((*lead, mid), bp.dtype), cp),)

    merged = _bitonic_merge(fields)
    return (merged[0][..., :out_width],) + tuple(
        x[..., :out_width] for x in merged[2:])


def _rotate_stage(fields, j: int, desc, active=None):
    """One compare-exchange stage at stride ``j`` over the whole last axis,
    each lane fetching its partner (lane ^ j) by two lane rotations — the
    form Mosaic lowers (it refuses ``_cmp_swap``'s ``(..., c, 2, j)``
    reshape).  ``desc`` flips the direction per lane; lanes outside
    ``active`` keep their values."""
    lane = jax.lax.broadcasted_iota(jnp.int32, fields[0].shape,
                                    fields[0].ndim - 1)
    lo = (lane & j) == 0

    def partner(x):
        return jnp.where(lo, jnp.roll(x, -j, axis=-1),
                         jnp.roll(x, j, axis=-1))

    pd, pr = partner(fields[0]), partner(fields[1])
    # (dist, rank) lexicographic: does the partner belong before this lane?
    before = (pd < fields[0]) | ((pd == fields[0]) & (pr < fields[1]))
    swap = (before != ~lo) != desc         # lo lanes take an earlier
    if active is not None:                 # partner, hi lanes a later one
        swap &= active
    return ((jnp.where(swap, pd, fields[0]), jnp.where(swap, pr, fields[1]))
            + tuple(jnp.where(swap, partner(x), x) for x in fields[2:]))


def _kernel(d_ref, r_ref, i_ref, c_ref, x_ref, od_ref, oi_ref, oc_ref,
            ox_ref, *, dp: int):
    """Rows hold ``[beam asc | +inf pads | candidates]`` (``_layout``):
    sort the last ``dp`` lanes descending, then one bitonic merge."""
    fields = tuple(ref[...] for ref in (d_ref, r_ref, i_ref, c_ref, x_ref))
    T = fields[0].shape[-1]
    c = jax.lax.broadcasted_iota(jnp.int32, fields[0].shape, 1) - (T - dp)
    k = 2
    while k <= dp:             # every comparator flipped: descending
        j = k // 2
        while j >= 1:
            fields = _rotate_stage(fields, j, (c & k) == 0, active=c >= 0)
            j //= 2
        k *= 2
    j = T // 2
    while j >= 1:
        fields = _rotate_stage(fields, j, False)
        j //= 2
    L = od_ref.shape[-1]
    od_ref[...], oi_ref[...], oc_ref[...], ox_ref[...] = (
        x[:, :L] for x in (fields[0],) + fields[2:])


def _layout(beam_dists, beam_payload, cand_dists, cand_payload):
    """The kernel's row layout: ``[beam | +inf pads | candidates | +inf]``
    with ``(dist, rank)`` keys ranked as in ``merge_beam_candidates``
    (beam 0..L-1, candidates L.., pads after everything).  Returns
    (fields, dp)."""
    B, L = beam_dists.shape
    d = cand_dists.shape[1]
    dp = _next_pow2(d)
    T = _next_pow2(L + dp)
    mid = T - L - dp

    def lanes(n):
        return _lane_iota((B,), n)

    dists = jnp.concatenate(
        [beam_dists, jnp.full((B, mid), _INF, beam_dists.dtype), cand_dists,
         jnp.full((B, dp - d), _INF, cand_dists.dtype)], axis=1)
    ranks = jnp.concatenate([lanes(L), T + lanes(mid), L + lanes(dp)], axis=1)
    payload = tuple(
        jnp.concatenate([bp, jnp.zeros((B, mid), bp.dtype), cp,
                         jnp.zeros((B, dp - d), cp.dtype)], axis=1)
        for bp, cp in zip(beam_payload, cand_payload))
    return (dists, ranks) + payload, dp


def _lane_iota(lead, n: int):
    """int32 positions 0..n-1 along a new last axis of shape (*lead, n)."""
    return jax.lax.broadcasted_iota(jnp.int32, (*lead, n), len(lead))


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def beam_merge_pallas(beam_dists, beam_ids, beam_chk, beam_exc,
                      cand_dists, cand_ids, cand_chk, cand_exc,
                      *, tb: int = 8, interpret: bool):
    """Pallas dispatch: (B, L) beam + (B, d) candidates -> merged (B, L).

    Flag channels are int32.  B must be a multiple of ``tb`` (ops.py pads).
    The whole (tb, T<=2*(L+d)) working set lives in VMEM: at production
    shapes (L<=1024, d<=128, tb=8) that is well under 1 MB across the five
    channels, so the grid tiles the batch only.
    """
    B, L = beam_dists.shape
    assert B % tb == 0, (B, tb)
    fields, dp = _layout(beam_dists, (beam_ids, beam_chk, beam_exc),
                         cand_dists, (cand_ids, cand_chk, cand_exc))
    T = fields[0].shape[1]
    ispec = pl.BlockSpec((tb, T), lambda i: (i, 0))
    ospec = pl.BlockSpec((tb, L), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, dp=dp),
        grid=(B // tb,),
        in_specs=[ispec] * 5,
        out_specs=[ospec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((B, L), jnp.float32),
            jax.ShapeDtypeStruct((B, L), jnp.int32),
            jax.ShapeDtypeStruct((B, L), jnp.int32),
            jax.ShapeDtypeStruct((B, L), jnp.int32),
        ],
        interpret=interpret,
    )(*fields)
