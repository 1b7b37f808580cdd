#!/usr/bin/env python3
"""Faults planted under the timed path, to show that ``correct`` catches
each of them.

    python bench/faults.py --fault refine_noop --workload enron.build_refine \\
        --seed 5 --seconds 10 --trace 0

runs one cell as ``bench/run.py`` does (the same arguments), with one step
of the program broken underneath for the whole run, and prints the run's
result line; ``correct`` must read false.  The faults:

* ``answer_altered`` — a search's first answer gets another id where the
  search program produces it;
* ``half_left_out`` — the second half of every flush gets the first lane's
  answer (half of the batch left out);
* ``add_noop`` — ``DEGIndex.add`` inserts nothing (set-up's first chunk
  excepted);
* ``refine_noop`` — ``DEGIndex.refine`` leaves the graph as it was
  (set-up's first chunk excepted).

The benchmark's own runs plant none; ``bench/tests/test_bench_faults.py``
plants each under a CPU rehearsal.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@contextlib.contextmanager
def _patched(owner, name: str, make):
    real = getattr(owner, name)
    setattr(owner, name, make(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def _broken_dispatch(alter):
    from repro.serving import buckets

    def make(real):
        def dispatch(index, cfg, qs, seeds, excl, hop_budget=None):
            res = real(index, cfg, qs, seeds, excl, hop_budget=hop_budget)
            return dataclasses.replace(res, **alter(res, index.n))
        return dispatch

    return _patched(buckets, "dispatch", make)


def _altered_answer(res, n):
    return {"ids": res.ids.at[0, 0].set((res.ids[0, 0] + 1) % n)}


def _half_left_out(res, n):
    half = (res.ids.shape[0] + 1) // 2
    return {"ids": res.ids.at[half:].set(res.ids[0]),
            "dists": res.dists.at[half:].set(res.dists[0])}


def _after_first_call(method: str):
    """``DEGIndex.<method>`` that does nothing after its first call."""
    from repro.core.build import DEGIndex

    def make(real):
        calls = []

        def broken(self, *args, **kwargs):
            calls.append(1)
            if len(calls) == 1:
                return real(self, *args, **kwargs)
            return 0

        return broken

    return _patched(DEGIndex, method, make)


FAULTS = {
    "answer_altered": lambda: _broken_dispatch(_altered_answer),
    "half_left_out": lambda: _broken_dispatch(_half_left_out),
    "add_noop": lambda: _after_first_call("add"),
    "refine_noop": lambda: _after_first_call("refine"),
}


def planted(fault: str):
    """A context in which ``fault`` is planted."""
    return FAULTS[fault]()


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    args, rest = ap.parse_known_args(argv)
    with planted(args.fault):
        return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
