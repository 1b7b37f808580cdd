"""The device-side beam engine — the shared inner loop of the whole system.

The paper's RangeSearch (Alg. 1) appears in every layer of this repro:
queries (``core/search.py``), incremental-build candidate searches (Alg. 3,
``core/build.py``), delete-repair and continuous edge optimization (Alg. 5,
``core/delete.py`` / ``core/optimize.py``), shard-local search
(``distributed/index.py``) and the serving flush (``serving/engine.py``).
This module is the single implementation all of them drive:

* :class:`BeamState` — a registered-dataclass pytree holding the lock-step
  beam of ``B`` query lanes: ids / dists / checked / excluded, all ``(B, L)``
  with the *sorted invariant* (ascending by ``(dist, stable-rank)``), plus
  per-lane hop and distance-evaluation counters and (optionally) a per-lane
  visited hash set (``core/visited.py``);
* jitted primitives :func:`init` / :func:`expand` / :func:`merge` /
  :func:`extract` — each usable standalone, and composed by
  :func:`beam_search` into one ``lax.while_loop`` program;
* **multi-expansion** (CAGRA-style): ``expand_width=E`` expands the E
  closest unchecked beam entries per lane per hop instead of one, gathering
  and scoring all ``E*d`` neighbors in a single pass — ~E× fewer
  ``while_loop`` trips at higher arithmetic intensity per dispatch.  With
  ``E=1`` the program is bit-identical to the seed engine (pinned by the
  golden fixture);
* the per-hop dedup is either the seed *beam broadcast* (O(L) compares per
  candidate — the E=1 default, exact seed semantics) or the O(probes)
  *visited filter* of ``core/visited.py`` (``visited_size > 0`` — the
  multi-expansion default, which also remembers evicted vertices, so
  ``evals`` can run below the broadcast engine's);
* ``hop_backend="pallas"`` routes the hop body through ``kernels/fused_hop``:
  the filter runs in XLA and only the surviving vector rows are copied
  into VMEM and scored by the ``gather_dist`` kernel (requires the visited
  filter, an exact float store and l2); the per-hop beam merge dispatches
  to ``kernels/beam_merge`` as before.

``core/search.py::range_search`` is a thin jitted driver over this engine;
see ARCHITECTURE.md ("Multi-expansion beam layering") for how the layers
stack.

Exploration queries (paper Sec. 6.7) are native: seeds may be graph
vertices and ``exclude`` removes vertices from the *result list* (and from
the radius ``r``) while still allowing navigation through them.

Filtered queries (``core/tags.py``) are too: with a ``filter_tags``
operand the beam keeps a second sorted list per lane, its results, which
only vertices carrying every tag of the lane's filter enter; the radius is
that list's k-th distance, and every vertex stays navigable.  Without the
operand nothing of it is traced.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.store import VectorStore, as_store  # noqa: F401  (re-export)

from . import visited as visited_set
from .distances import get_metric
from .graph import DEGraph, INVALID
from .visited import default_size as default_visited_size  # noqa: F401

Array = jax.Array
_INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BeamState:
    """Lock-step beam over B query lanes (sorted invariant along axis 1)."""

    ids: Array        # (B, L) int32, INVALID-padded
    dists: Array      # (B, L) float32, inf-padded
    checked: Array    # (B, L) bool — expanded (or never-expandable) entries
    excluded: Array   # (B, L) bool — in the beam but banned from results
    hops: Array       # (B,) int32 — expanded vertices
    evals: Array      # (B,) int32 — distance evaluations (|C| analogue)
    # (B, V) int32 open-addressing visited set (core/visited.py), or None
    # when the engine runs the seed beam-broadcast dedup instead
    visited: Optional[Array] = None
    # () int32 — the while_loop's trip count, set by beam_search on its
    # final state: every one of the B lanes runs each trip, so
    # trips * B lane-trips against sum(hops) is the lock-step waste
    trips: Optional[Array] = None
    # filtered search: the lane's results (B, R), sorted, INVALID / inf
    # padded — admitted, non-excluded vertices only; their k-th distance
    # is the radius — and (B,) the distance evaluations of vertices the
    # filter did not admit.  None without a filter.
    res_ids: Optional[Array] = None
    res_dists: Optional[Array] = None
    rejected: Optional[Array] = None

    @property
    def width(self) -> int:
        return self.ids.shape[1]


def neighbor_distances_jnp(vectors, queries, nbr_ids, metric_name):
    """jnp gather+pair distance path.  ``vectors`` may be a raw (n, m) array
    (exact float32 semantics — the pre-store program verbatim) or a
    :class:`repro.quant.VectorStore` of any codec."""
    return as_store(vectors).neighbor_distances(queries, nbr_ids, metric_name,
                                                backend="jnp")


def _neighbor_distances(vectors, queries, nbr_ids, metric_name, backend):
    return as_store(vectors).neighbor_distances(queries, nbr_ids, metric_name,
                                                backend=backend)


def in_set(ids: Array, excl: Array) -> Array:
    """ids (B, L), excl (B, X) -> bool (B, L) membership (INVALID never
    member)."""
    hit = (ids[:, :, None] == excl[:, None, :]).any(axis=2)
    return hit & (ids != INVALID)


def admitted(tags: Array, ids: Array, filter_tags: Array) -> Array:
    """ids (B, C) -> bool (B, C): the vertex carries every tag of its
    lane's filter.  ``tags`` is the (capacity, T) tag table,
    ``filter_tags`` (B, F) the lanes' filters; an INVALID filter slot asks
    for nothing, an INVALID id is never admitted."""
    rows = tags[jnp.where(ids == INVALID, 0, ids)]            # (B, C, T)
    has = (rows[:, :, None, :] == filter_tags[:, None, :, None]).any(axis=3)
    has |= (filter_tags == INVALID)[:, None, :]
    return has.all(axis=2) & (ids != INVALID)


def fold_results(res_ids: Array, res_dists: Array, cand_ids: Array,
                 cand_dists: Array, cand_ok: Array) -> tuple[Array, Array]:
    """The sorted result list (B, R) with the ``cand_ok`` candidates
    folded in; the R best stay (``top_k`` keeps the first of equal
    distances, so a tie keeps list order)."""
    R = res_ids.shape[1]
    d = jnp.concatenate([res_dists, jnp.where(cand_ok, cand_dists, _INF)],
                        axis=1)
    ids = jnp.concatenate([res_ids, jnp.where(cand_ok, cand_ids, INVALID)],
                          axis=1)
    neg, order = jax.lax.top_k(-d, R)
    ids = jnp.take_along_axis(ids, order, axis=1)
    return jnp.where(jnp.isinf(neg), INVALID, ids), -neg


def radius(state: BeamState, k: int) -> Array:
    """k-th best non-excluded distance per lane (inf if fewer than k); in
    a filtered search, the k-th admitted one."""
    if state.res_dists is not None:
        return state.res_dists[:, k - 1]
    ok = (state.ids != INVALID) & ~state.excluded
    cnt = jnp.cumsum(ok.astype(jnp.int32), axis=1)
    at_k = ok & (cnt == k)
    has_k = at_k.any(axis=1)
    kth = jnp.where(at_k, state.dists, _INF).min(axis=1)
    return jnp.where(has_k, kth, _INF)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def init(vectors: Array | VectorStore, queries: Array, seed_ids: Array,
         exclude: Array, n_valid: Array, *, beam_width: int,
         metric: str, visited_size: int = 0,
         tags: Optional[Array] = None, filter_tags: Optional[Array] = None,
         res_width: int = 0) -> BeamState:
    """Seed the beam: dedup seeds per lane, score them, sort, pad to L.

    ``visited_size > 0`` additionally allocates the per-lane visited hash
    set (that many slots, power of two) and records the seeds in it —
    :func:`expand` then uses it for the per-hop dedup instead of the beam
    broadcast.  ``filter_tags`` (B, F) starts a filtered search over the
    ``tags`` table with a result list of ``res_width`` entries, which the
    admitted seeds enter."""
    B = queries.shape[0]
    L = beam_width
    store = as_store(vectors)
    metric_obj = get_metric(metric)

    seed_valid = (seed_ids != INVALID) & (seed_ids < n_valid)
    # dedup seeds within each lane (keep first occurrence)
    first_pos = jnp.argmax(seed_ids[:, :, None] == seed_ids[:, None, :],
                           axis=2)
    seed_valid &= first_pos == jnp.arange(seed_ids.shape[1])[None, :]
    safe_seeds = jnp.where(seed_valid, seed_ids, 0)
    seed_d = metric_obj.pair(queries[:, None, :], store.decode(safe_seeds))
    seed_d = jnp.where(seed_valid, seed_d, _INF)
    seed_ids_m = jnp.where(seed_valid, seed_ids, INVALID)

    pad = L - seed_ids.shape[1]
    ids = jnp.concatenate(
        [seed_ids_m, jnp.full((B, pad), INVALID, jnp.int32)], axis=1)
    dists = jnp.concatenate([seed_d, jnp.full((B, pad), _INF)], axis=1)
    checked = ids == INVALID        # invalid slots never selected
    excl = in_set(ids, exclude)

    vis = None
    if visited_size:
        vis = visited_set.make_table(B, visited_size)
        vis = visited_set.insert(vis, seed_ids_m, seed_valid)

    res_ids = res_dists = rejected = None
    if filter_tags is not None:
        adm = admitted(tags, seed_ids_m, filter_tags)
        rejected = (seed_valid & ~adm).sum(axis=1).astype(jnp.int32)
        res_ids, res_dists = fold_results(
            jnp.full((B, res_width), INVALID, jnp.int32),
            jnp.full((B, res_width), _INF), seed_ids_m, seed_d,
            adm & ~in_set(seed_ids_m, exclude))

    order = jnp.argsort(dists, axis=1)
    take = functools.partial(jnp.take_along_axis, indices=order, axis=1)
    return BeamState(
        ids=take(ids), dists=take(dists), checked=take(checked),
        excluded=take(excl),
        hops=jnp.zeros((B,), jnp.int32),
        evals=seed_valid.sum(axis=1).astype(jnp.int32),
        visited=vis, res_ids=res_ids, res_dists=res_dists,
        rejected=rejected)


def merge(state: BeamState, cand_ids: Array, cand_dists: Array,
          cand_exc: Array, *, merge_backend: str = "jnp") -> BeamState:
    """Fold (B, d) scored candidates into the beam, keeping the sorted
    invariant.  Newly merged INVALID slots become checked (never
    expandable)."""
    d, ids, chk, exc = _merge_dispatch(
        state.dists, state.ids, state.checked, state.excluded,
        cand_dists, cand_ids, cand_exc, merge_backend)
    chk = jnp.where(ids == INVALID, True, chk)
    return dataclasses.replace(state, ids=ids, dists=d, checked=chk,
                               excluded=exc)


def _merge_dispatch(beam_d, beam_ids, beam_chk, beam_exc,
                    cand_d, cand_ids, cand_exc, merge_backend):
    from repro.kernels.beam_merge import ops as bm_ops

    return bm_ops.beam_merge(beam_d, beam_ids, beam_chk, beam_exc,
                             cand_d, cand_ids, cand_exc,
                             backend=merge_backend)


def _select_unchecked(state: BeamState, expand_width: int):
    """Positions of the E closest unchecked beam entries per lane.

    Returns (positions (B, E) int32, was_unchecked (B, E) bool).  The beam
    is distance-sorted, so "closest unchecked" = "first unchecked"; for
    E=1 this is exactly the seed's ``argmax(~checked)`` selection, and
    E>1 iterates it (E masked argmax passes beat a per-hop argsort of the
    whole beam — selection runs every ``while_loop`` trip)."""
    B, L = state.ids.shape
    open_ = ~state.checked
    pos_list, un_list = [], []
    for _ in range(expand_width):
        p = jnp.argmax(open_, axis=1)
        pos_list.append(p)
        un_list.append(open_.any(axis=1))
        open_ = open_.at[jnp.arange(B), p].set(False)
    return (jnp.stack(pos_list, axis=1),
            jnp.stack(un_list, axis=1))


def _check_fused_hop(vectors, metric: str) -> None:
    """The fused hop kernel scores exact float rows under l2 only; asking
    for it anywhere else is a configuration error, not a silent jnp run."""
    store = as_store(vectors)
    if not store.exact or metric not in ("l2", "sqeuclidean"):
        raise ValueError(
            "hop_backend='pallas' needs an exact float store and the l2 or "
            f"sqeuclidean metric; got codec {store.codec!r}, metric "
            f"{metric!r} (use hop_backend='jnp')")


def expand(state: BeamState, adjacency: Array, n_valid: Array,
           vectors: Array | VectorStore, queries: Array, exclude: Array, *,
           k: int,
           eps: float, metric: str, backend: str = "jnp",
           merge_backend: str = "jnp", expand_width: int = 1,
           hop_backend: str = "jnp",
           hop_budget: Optional[Array] = None,
           tags: Optional[Array] = None,
           filter_tags: Optional[Array] = None) -> BeamState:
    """One hop: expand each lane's ``expand_width`` closest unchecked
    entries (Alg. 1 lines 8-15, generalized to a multi-expansion frontier)
    and merge their scored neighbors into the beam in one pass.

    Dedup of freshly gathered neighbors is the seed beam broadcast when
    ``state.visited is None`` and the O(probes) visited filter otherwise.
    ``hop_backend="pallas"`` scores the hop's surviving neighbors with the
    ``kernels/fused_hop`` kernel path (visited filter + exact float store +
    l2 only; anything else raises ``ValueError``).

    ``hop_budget`` (B,) int32 caps each lane's expansions: a lane whose
    ``hops`` counter has reached its budget stops expanding (its beam is
    then extractable as a best-so-far result — the serving layer's
    deadline early-extract).  ``None`` (the default) is the unbudgeted
    program, bit for bit.  With ``expand_width > 1`` a lane may overshoot
    its budget by up to E-1 expansions (the E selections of one hop are
    committed together).

    ``filter_tags`` (B, F), with the ``tags`` table, folds the hop's
    admitted candidates into the state's result list (:func:`init`)."""
    B, L = state.ids.shape
    E = expand_width
    d = adjacency.shape[1]
    eps1 = jnp.float32(1.0 + eps)
    r = radius(state, k)
    lane = jnp.arange(B)

    cur, sel_unchecked = _select_unchecked(state, E)
    sel_id = jnp.take_along_axis(state.ids, cur, axis=1)
    sel_d = jnp.take_along_axis(state.dists, cur, axis=1)
    active = (sel_unchecked & (sel_d <= (r * eps1)[:, None])
              & (sel_id != INVALID))
    if hop_budget is not None:
        active &= (state.hops < hop_budget)[:, None]

    # scatter-max == OR: marks active selections checked; inactive (or
    # duplicate, on exhausted lanes) selections are no-ops, associatively
    checked = state.checked.at[lane[:, None], cur].max(active)

    use_visited = state.visited is not None
    if hop_backend == "pallas":
        if not use_visited:
            raise ValueError("hop_backend='pallas' (fused hop) requires the "
                             "visited filter: pass visited_size > 0")
        _check_fused_hop(vectors, metric)
        from repro.kernels.fused_hop import ops as fh_ops

        cand_ids, cand_d, nbr_out, evals_inc = fh_ops.fused_hop(
            adjacency, as_store(vectors).data,
            jnp.where(active, sel_id, INVALID), queries, r * eps1,
            state.visited, n_valid=n_valid,
            squared=(metric == "sqeuclidean"), backend="pallas")
        cand_exc = in_set(cand_ids, exclude) & (cand_ids != INVALID)
        new_visited = visited_set.insert(state.visited, nbr_out,
                                         nbr_out != INVALID)
        scored = lambda: nbr_out                                  # noqa: E731
    else:
        nbrs = adjacency[jnp.where(active, sel_id, 0)]       # (B, E, d)
        valid = active[:, :, None] & (nbrs != INVALID) & (nbrs < n_valid)
        flat = nbrs.reshape(B, E * d)
        vmask = valid.reshape(B, E * d)
        if use_visited:
            if E > 1:
                # two expanded vertices may share a neighbor: keep the
                # first occurrence among valid ids
                vmask = vmask & visited_set.first_occurrence_mask(flat,
                                                                  vmask)
            ok = vmask & ~visited_set.contains(state.visited, flat)
        elif E > 1:
            # beam-membership dedup + intra-block first occurrence (the
            # shared mask keeps this bit-identical to the fused_hop
            # oracle), both in one pass over the candidate block
            in_beam = (flat[:, :, None] == state.ids[:, None, :]).any(axis=2)
            ok = (vmask & ~in_beam
                  & visited_set.first_occurrence_mask(flat, vmask))
        else:
            ok = vmask & ~(flat[:, :, None]
                           == state.ids[:, None, :]).any(axis=2)  # dedup
        safe = jnp.where(ok, flat, 0)
        nd = _neighbor_distances(vectors, queries, safe, metric, backend)
        nd = jnp.where(ok, nd, _INF)
        keep = ok & (nd <= r[:, None] * eps1)                # Alg. 1 line 12
        cand_ids = jnp.where(keep, flat, INVALID)
        cand_d = jnp.where(keep, nd, _INF)
        cand_exc = in_set(cand_ids, exclude) & keep
        evals_inc = ok.sum(axis=1).astype(jnp.int32)
        new_visited = (visited_set.insert(state.visited, flat, ok)
                       if use_visited else state.visited)
        scored = lambda: jnp.where(ok, flat, INVALID)             # noqa: E731

    if filter_tags is not None:
        # a kept candidate that carries the filter's tags is a result
        # candidate; the rest stay navigable only (the ids a hop scored
        # are built here, so the unfiltered program traces none of it)
        scored = scored()
        adm_scored = admitted(tags, scored, filter_tags)
        if hop_backend == "pallas":         # candidates compacted
            adm = admitted(tags, cand_ids, filter_tags)
        else:                               # candidates where scored ones
            adm = adm_scored & (cand_ids != INVALID)
        res_ids, res_dists = fold_results(
            state.res_ids, state.res_dists, cand_ids, cand_d,
            adm & ~cand_exc & ~in_set(cand_ids, state.res_ids))
        rejected = state.rejected + ((scored != INVALID) & ~adm_scored
                                     ).sum(axis=1).astype(jnp.int32)
        state = dataclasses.replace(state, res_ids=res_ids,
                                    res_dists=res_dists, rejected=rejected)

    state = dataclasses.replace(
        state, checked=checked,
        hops=state.hops + active.sum(axis=1).astype(jnp.int32),
        evals=state.evals + evals_inc,
        visited=new_visited)
    return merge(state, cand_ids, cand_d, cand_exc,
                 merge_backend=merge_backend)


def alive(state: BeamState, *, k: int, eps: float,
          hop_budget: Optional[Array] = None) -> Array:
    """(B,) bool: does the lane still have an expandable entry within the
    range radius (Alg. 1 line 7 would NOT yet return)?  A lane whose
    ``hop_budget`` is spent is dead regardless — its beam is the
    best-so-far result."""
    eps1 = jnp.float32(1.0 + eps)
    r = radius(state, k)
    nxt = jnp.argmax(~state.checked, axis=1)
    nxt_d = state.dists[jnp.arange(state.ids.shape[0]), nxt]
    live = (~state.checked.all(axis=1)) & (nxt_d <= r * eps1)
    if hop_budget is not None:
        live &= state.hops < hop_budget
    return live


def extract(state: BeamState, k: int, *, dedup: bool = False
            ) -> tuple[Array, Array]:
    """Top-k non-excluded results: (ids (B, k), dists (B, k)).

    Extraction is a *stable* sort so duplicate distances resolve by beam
    position, matching ``search.exact_rerank`` tie semantics.  ``dedup``
    masks repeated ids (keeping the first occurrence) — the safety net for
    visited-filter searches, where a dropped hash insert can in principle
    let a vertex enter the beam twice.  A filtered search returns its
    result list, which holds no id twice."""
    if state.res_ids is not None:
        return state.res_ids[:, :k], state.res_dists[:, :k]
    final_d = jnp.where(state.excluded | (state.ids == INVALID), _INF,
                        state.dists)
    if dedup:
        first = visited_set.first_occurrence_mask(state.ids,
                                                  state.ids != INVALID)
        final_d = jnp.where(first, final_d, _INF)
    order = jnp.argsort(final_d, axis=1, stable=True)[:, :k]
    out_ids = jnp.take_along_axis(state.ids, order, axis=1)
    out_d = jnp.take_along_axis(final_d, order, axis=1)
    out_ids = jnp.where(jnp.isinf(out_d), INVALID, out_ids)
    return out_ids, out_d


# ---------------------------------------------------------------------------
# the composed program
# ---------------------------------------------------------------------------
def beam_search(graph: DEGraph, vectors: Array | VectorStore, queries: Array,
                seed_ids: Array, *, k: int, eps: float, beam_width: int,
                max_hops: int, metric: str = "l2",
                exclude: Optional[Array] = None, backend: str = "jnp",
                merge_backend: str = "jnp", expand_width: int = 1,
                visited_size: int = 0,
                hop_backend: str = "jnp",
                hop_budget: Optional[Array] = None,
                tags: Optional[Array] = None,
                filter_tags: Optional[Array] = None,
                res_width: int = 0) -> BeamState:
    """init -> while(expand) -> final BeamState, its ``trips`` the loop's
    trip count.  Pure (un-jitted): callers
    embed it in their own jitted programs (``range_search``, the sharded
    search step) so every layer reuses one implementation.

    ``vectors`` may be a raw float array (exact) or a
    :class:`repro.quant.VectorStore` — with a compressed codec the beam
    traverses *approximate* distances; callers that need exact results run
    the two-stage rerank in ``core/search.py`` on top.

    ``expand_width`` (E) widens the per-hop frontier; ``visited_size``
    swaps the beam-broadcast dedup for the visited filter (required for
    ``hop_backend="pallas"``, which fuses the hop into one kernel).  The
    defaults (E=1, no visited, jnp) are the seed program, bit for bit.

    ``hop_budget`` (B,) int32 per-lane expansion caps (serving early
    extract): a budget-exhausted lane stops hopping and its final beam is
    its best-so-far answer.  ``None`` = unbudgeted (the golden program —
    the budget branch is not even traced).

    ``filter_tags`` (B, F) with the (capacity, T) ``tags`` table: a
    filtered search whose results, ``res_width`` of them, carry every tag
    of their lane's filter (``None``: the unfiltered program, untouched)."""
    if expand_width < 1:
        raise ValueError(f"expand_width must be >= 1, got {expand_width}")
    expand_width = min(expand_width, beam_width)
    if hop_backend == "pallas" and not visited_size:
        raise ValueError("hop_backend='pallas' (fused hop) requires the "
                         "visited filter: pass visited_size > 0")
    B = queries.shape[0]
    if exclude is None:
        exclude = jnp.full((B, 1), INVALID, dtype=jnp.int32)
    n_valid = graph.n
    adjacency = graph.adjacency

    state0 = init(vectors, queries, seed_ids, exclude, n_valid,
                  beam_width=beam_width, metric=metric,
                  visited_size=visited_size, tags=tags,
                  filter_tags=filter_tags, res_width=res_width)

    def cond(carry):
        _, it, any_alive = carry
        return any_alive & (it < max_hops)

    def body(carry):
        state, it, _ = carry
        state = expand(state, adjacency, n_valid, vectors, queries, exclude,
                       k=k, eps=eps, metric=metric, backend=backend,
                       merge_backend=merge_backend,
                       expand_width=expand_width, hop_backend=hop_backend,
                       hop_budget=hop_budget, tags=tags,
                       filter_tags=filter_tags)
        return (state, it + 1,
                alive(state, k=k, eps=eps, hop_budget=hop_budget).any())

    state, trips, _ = jax.lax.while_loop(
        cond, body, (state0, jnp.int32(0), jnp.asarray(True)))
    return dataclasses.replace(state, trips=trips)


# jitted standalone primitives (library surface for out-of-loop callers)
init_jit = jax.jit(init, static_argnames=("beam_width", "metric",
                                          "visited_size", "res_width"))
merge_jit = jax.jit(merge, static_argnames=("merge_backend",))
expand_jit = jax.jit(
    expand, static_argnames=("k", "metric", "backend", "merge_backend",
                             "expand_width", "hop_backend"))
extract_jit = jax.jit(extract, static_argnames=("k", "dedup"))


def default_beam_width(k: int, degree: int, n_seeds: int,
                       n_exclude: int = 0) -> int:
    """The L heuristic shared by every driver (seed semantics)."""
    L = max(k + degree, 2 * k)
    L = max(L, k, n_seeds)
    if n_exclude:
        L = max(L, k + n_exclude)
    return L


def default_max_hops(beam_width: int) -> int:
    return 4 * beam_width + 64
