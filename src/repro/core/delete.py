"""Vertex deletion — the missing half of "fully dynamic" (beyond-paper).

Paper Table 1 lists DEG as the only *fully dynamic* graph but defers the
deletion procedure to future work (§8); Appendix A sketches the requirement:
removal must preserve even regularity and connectivity, without tombstones
(flagged-deleted vertices "still consume memory and must be visited").

Procedure for deleting vertex ``v`` (degree d, d even):

1. remove the d edges (v, u_i) — the d neighbors are now degree d-1;
2. re-pair the d deficient neighbors with a *perfect matching* among
   themselves (d is even), chosen greedily by ascending distance subject to
   no-duplicate-edge validity, with 2-swap repair when greedy jams — each
   neighbor gets exactly +1 edge, restoring regularity;  the matching
   minimizes added average-neighbor-distance (Eq. 4) the same way scheme D
   reasons about insertion;
3. verify connectivity (cheap BFS on the ~d affected vertices' component);
   in the (rare — Appendix B bounds it) case the graph split, retry with a
   randomized matching, else revert and report;
4. compact storage: move the last vertex into slot ``v`` (rewriting its
   neighbors' adjacency entries), shrink ``n`` — the index stays a dense
   ``[0, n)`` array, no holes, no tombstones;
5. optionally run Alg. 5 refinement on the re-paired vertices.

``DEGIndex.remove`` wires this up and keeps the device vector buffer in
sync; the QueryEngine exposes online deletes between flushes.
"""
from __future__ import annotations

from typing import Iterable, Optional, Sequence

import numpy as np

from .build import DEGIndex, np_pair_dist
from .graph import GraphBuilder


def _greedy_matching(cands: list, pairs_needed: int,
                     invalid: set) -> Optional[list]:
    """cands: [(w, a, b)] ascending; returns pairs or None."""
    used: set = set()
    out = []
    for w, a, b in cands:
        if a in used or b in used or (a, b) in invalid:
            continue
        out.append((a, b, w))
        used.add(a)
        used.add(b)
        if len(out) == pairs_needed:
            return out
    return None


def delete_vertex(index: DEGIndex, v: int, *, rng=None,
                  refine_after: int = 0, max_retries: int = 8) -> bool:
    """Delete vertex ``v`` preserving regularity + connectivity.

    Returns True on success.  Raises ValueError for out-of-range ids and
    RuntimeError if the graph is at its minimum size (K_{d+1}).
    """
    b = index.builder
    if b is None or not (0 <= v < b.n):
        raise ValueError(f"no such vertex {v}")
    d = b.degree
    if b.n <= d + 2:
        raise RuntimeError("cannot shrink below the minimal DEG (K_{d+1})")
    rng = rng or np.random.default_rng(v)
    metric = index.params.metric

    nbrs = [int(x) for x in b.neighbors(v)]
    assert len(nbrs) == d, (v, nbrs)
    # 1. remove v's edges (log for rollback)
    removed = [(u, b.remove_edge(v, u)) for u in nbrs]

    # candidate pair weights among the deficient neighbors
    base_cands = []
    invalid = set()
    for i, a in enumerate(nbrs):
        ds = np_pair_dist(metric, index.vectors[a],
                          index.vectors[np.asarray(nbrs[i + 1:])]) \
            if i + 1 < len(nbrs) else []
        for off, bb in enumerate(nbrs[i + 1:]):
            if a == bb or b.has_edge(a, bb):
                invalid.add((a, bb))
                invalid.add((bb, a))
            base_cands.append((float(ds[off]), a, bb))
            base_cands.append((float(ds[off]), bb, a))

    def try_matching(cands) -> Optional[list]:
        m = _greedy_matching(sorted(cands), d // 2, invalid)
        return m

    success = False
    for attempt in range(max_retries):
        if attempt == 0:
            matching = try_matching(base_cands)
        else:                       # randomized retry: jitter the order
            jit = [(w * (1.0 + 0.5 * rng.random()), a, bb)
                   for w, a, bb in base_cands]
            matching = try_matching(jit)
        added = []
        if matching is None:
            # dense fallback (small graphs: neighbors mutually adjacent):
            # pair the leftover deficient vertices via an Alg.3-style edge
            # split — connect (a, c), (bb, e) and remove an existing (c, e).
            matching = _split_matching(index, b, nbrs, invalid, v)
            if matching is None:
                continue
            ok_add = True
            for a, bb, kind, c, e in matching:
                if kind == "pair":
                    b.add_edge(a, bb, float(np_pair_dist(
                        metric, index.vectors[a], index.vectors[bb])[0]))
                    added.append(("pair", a, bb, 0.0))
                else:
                    w_ce = b.remove_edge(c, e)
                    b.add_edge(a, c, float(np_pair_dist(
                        metric, index.vectors[a], index.vectors[c])[0]))
                    b.add_edge(bb, e, float(np_pair_dist(
                        metric, index.vectors[bb], index.vectors[e])[0]))
                    added.append(("split", a, bb, w_ce, c, e))
        else:
            for a, bb, w in matching:
                b.add_edge(a, bb, float(np_pair_dist(
                    metric, index.vectors[a], index.vectors[bb])[0]))
                added.append(("pair", a, bb, 0.0))
        # 3. connectivity check from one affected vertex
        if _connected_among(b, nbrs, exclude=v):
            success = True
            break
        for op in reversed(added):  # revert this attempt, retry
            if op[0] == "pair":
                b.remove_edge(op[1], op[2])
            else:
                _, a, bb, w_ce, c, e = op
                b.remove_edge(a, c)
                b.remove_edge(bb, e)
                b.add_edge(c, e, w_ce)
    if not success:
        for u, w in removed:       # full rollback
            b.add_edge(v, u, w)
        return False

    # 4. compact: move last vertex into slot v
    index._medoid = None     # vector set shrinks even when v == last
    last = b.n - 1
    if v != last:
        last_nbrs = [int(x) for x in b.neighbors(last)]
        last_ws = [b.edge_weight(last, u) for u in last_nbrs]
        for u in last_nbrs:
            b.remove_edge(last, u)
        index.vectors[v] = index.vectors[last]
        index._put_rows(index.vectors[v][None], v)
        for u, w in zip(last_nbrs, last_ws):
            b.add_edge(v, u if u != v else last, w)
    b.clear_vertex(last)           # marks the row dirty for the device sync
    index._move_tags(last, v)      # slot v takes the last row's tags
    b.n -= 1

    # quarantine ids (scrubber state) track the compaction remap: the
    # deleted vertex leaves the set, and if the moved last vertex was
    # quarantined its damage now lives in slot v
    q = index.quarantine
    if q:
        q.discard(v)
        if last in q:
            q.discard(last)
            if v != last:
                q.add(v)

    if refine_after:
        # repair ride-along: one batched Alg. 5 sweep over the re-paired
        # neighbors (a single prefetch device call via the beam engine)
        from .optimize import refine_sweep

        refine_sweep(index, [u for u in nbrs[: refine_after] if u < b.n],
                     i_opt=index.params.i_opt, k_opt=index.params.k_opt,
                     eps_opt=index.params.eps_opt)
    return True


def _split_matching(index: DEGIndex, b: GraphBuilder, nbrs: Sequence[int],
                    invalid: set, v: int) -> Optional[list]:
    """Fallback matching for dense neighborhoods: pair what greedy can,
    resolve leftover deficient pairs (a, bb) by splitting an existing edge
    (c, e) not incident to the deficient set: add (a, c), (bb, e).  Returns
    [(a, bb, 'pair'|'split', c, e)] or None."""
    metric = index.params.metric
    left = list(nbrs)
    out = []
    # first: valid direct pairs greedily
    while len(left) >= 2:
        a = left[0]
        best = None
        for bb in left[1:]:
            if (a, bb) in invalid or b.has_edge(a, bb):
                continue
            w = float(np_pair_dist(metric, index.vectors[a],
                                   index.vectors[bb])[0])
            if best is None or w < best[0]:
                best = (w, bb)
        if best is not None:
            out.append((a, best[1], "pair", -1, -1))
            left.remove(a)
            left.remove(best[1])
            continue
        # a cannot pair directly with anyone -> split an existing edge
        bb = left[1]
        deficient = set(left) | {v}
        split = None
        for c in range(b.n):
            if c in deficient or b.has_edge(a, c):
                continue
            for e in b.neighbors(c):
                e = int(e)
                if e in deficient or e == c or b.has_edge(bb, e):
                    continue
                cost = (float(np_pair_dist(metric, index.vectors[a],
                                           index.vectors[c])[0])
                        + float(np_pair_dist(metric, index.vectors[bb],
                                             index.vectors[e])[0])
                        - b.edge_weight(c, e))
                if split is None or cost < split[0]:
                    split = (cost, c, e)
            if split is not None and split[0] <= 0:
                break               # good enough; keep scan bounded
        if split is None:
            return None
        out.append((a, bb, "split", split[1], split[2]))
        left.remove(a)
        left.remove(bb)
    return out


def _connected_among(b: GraphBuilder, seeds: Sequence[int],
                     exclude: int, cap: int = 100000) -> bool:
    """BFS from seeds[0]: all other seeds reachable without ``exclude``?"""
    from collections import deque

    target = set(int(s) for s in seeds)
    seen = {int(seeds[0])}
    dq = deque([int(seeds[0])])
    hits = 1
    steps = 0
    while dq and hits < len(target) and steps < cap:
        u = dq.popleft()
        steps += 1
        for w in b.neighbors(u):
            w = int(w)
            if w == exclude or w in seen:
                continue
            seen.add(w)
            if w in target:
                hits += 1
            dq.append(w)
    return hits == len(target)


def delete_vertices(index: DEGIndex, ids: Iterable[int], *,
                    refine_after: int = 0) -> int:
    """Delete several vertices; later ids are remapped as slots compact
    (each deletion moves the last vertex into the freed slot).  Returns the
    number deleted."""
    remaining = sorted(set(int(i) for i in ids), reverse=True)
    done = 0
    for v in remaining:             # descending: compaction-safe
        if delete_vertex(index, v, refine_after=refine_after):
            done += 1
    return done
