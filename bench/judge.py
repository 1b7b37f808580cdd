"""What decides ``correct``: the answers the timed path produced, compared
with the plain references of ``bench/reference.py``.

Each number compared has a limit of its own, taken from the
configuration's ``limits``; a run is correct when every number is at or
under its limit.  ``PERF.md`` gives the readings each limit was set from.

Answers of a search (the serve and explore cells):

* ``missing`` — requests due that never came back, or failed (exact, 0);
* ``bad_rows`` — answers with an id outside the index, a repeated id,
  distances out of order, or an id outside the request's admissible rows
  (``reference.brute_force``), such as an exploration query's own vertex
  (exact, 0);
* ``dist_err`` — the largest relative gap between a returned distance and
  the float64 distance of the returned id;
* ``recall_miss`` — 1 minus the mean recall@10 against the exact brute
  force; its limit is the configuration's stated recall floor.

A build (the build cell) adds:

* ``rows_differ`` — stored rows that differ from the rows inserted (exact,
  0);
* ``table1`` — the graph's Table-1 breaches (exact, 0);
* ``refine_idle`` — the share of the window's ``refine`` calls after which
  the graph's total edge weight, weighed in float64 from the rows
  inserted, was not lower than before the call: a refinement that leaves
  the graph as it was reads 1.
"""
from __future__ import annotations

import numpy as np

from bench import reference, stats


def answer_numbers(base: np.ndarray, queries: np.ndarray, ids, dists,
                   answered: np.ndarray, truth_ids: np.ndarray, k: int,
                   admissible=None, metric: str = "l2") -> dict:
    """Numbers of a batch of search answers.

    ``ids``/``dists`` (Q, k) as served (rows of unanswered requests are
    ignored); ``answered`` (Q,) bool; ``truth_ids`` (Q, k) the exact
    neighbours; ``admissible`` the requests' admissible rows, as
    ``reference.brute_force`` takes them, or None where every row is;
    ``metric`` the configuration's."""
    answered = np.asarray(answered, bool)
    out = {"missing": int((~answered).sum())}
    q = np.asarray(queries)[answered]
    ids = np.asarray(ids)[answered][:, :k].astype(np.int64)
    d = np.asarray(dists, np.float64)[answered][:, :k]
    truth = np.asarray(truth_ids)[answered][:, :k]
    if len(ids) == 0:
        out.update(bad_rows=0, dist_err=float("inf"), recall_miss=1.0)
        return out
    n = len(base)
    bad = ((ids < 0) | (ids >= n)).any(axis=1)
    srt = np.sort(ids, axis=1)
    bad |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    bad |= (np.diff(d, axis=1) < 0).any(axis=1) | ~np.isfinite(d).all(axis=1)
    if admissible is not None:
        inside = (ids >= 0) & (ids < n)
        req = np.flatnonzero(answered)[:, None]
        bad |= (inside & ~admissible(req, np.where(inside, ids, 0))
                ).any(axis=1)
    out["bad_rows"] = int(bad.sum())
    true_d = reference.exact_distances(q, base, ids, metric)
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.abs(d - true_d) / np.maximum(np.abs(true_d), 1e-30)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    out["dist_err"] = float(rel.max())
    out["recall_miss"] = float(1.0 - stats.recall_at_k(ids, truth, k).mean())
    return out


def build_numbers(stored_rows: np.ndarray, inserted_rows: np.ndarray,
                  adjacency: np.ndarray, degree: int) -> dict:
    """Numbers of a built index: its stored rows and its graph."""
    stored = np.asarray(stored_rows)
    ins = np.asarray(inserted_rows)
    if stored.shape != ins.shape:
        rows_differ = max(len(stored), len(ins))
    else:
        rows_differ = int((stored != ins).any(axis=1).sum())
    t1 = reference.table1_violations(adjacency, degree)
    return {"rows_differ": rows_differ, "table1": int(sum(t1.values()))}


def refine_idle(rows: np.ndarray, refines) -> float:
    """Share of ``refines`` — (changed vertices, their adjacency rows
    before, after) per ``refine`` call — that did not lower the total edge
    weight; 1 with no call."""
    if not refines:
        return 1.0
    gains = [reference.weight_change(rows, *r) for r in refines]
    return float(np.mean(np.asarray(gains) >= 0.0))


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every number under its
    limit.  A number with no limit is a fault of the benchmark."""
    checks = {}
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, checks
