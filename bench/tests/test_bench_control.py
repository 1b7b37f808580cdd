"""The control of ``correct`` — the brute force one precision step below
the configuration's, put in the program's place — must be judged not
correct on every seed, for each kind of cell.  The audio corpus is whole
here (the control's error grows with the corpus' density); the requests
are few, and the Enron corpus is cut to 4,000 rows with the cell's 1,000
probe queries over 3,000 inserted."""
import pytest

from bench import control, judge, run, spec

SIZES = {"audio-192-l2": {"query_pool": 64},
         "enron-1369-l2": {"n": 4000}}


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.load_benchmark()["workloads"]])
def test_the_control_is_not_correct(workload):
    cell = spec.cell(spec.load_benchmark(), workload)
    cfg = run._merge(spec.config(cell["config"]), SIZES[cell["config"]])
    mix = spec.mix(cell["traffic"])
    mix = run._merge(mix, mix["rehearsal"])
    for seed in (1, 2, 3):
        nums = control.control_numbers(cfg, mix, seed, 2.0, 300, 3000)
        ok, checks = judge.verdict(nums, cfg["limits"])
        assert not ok, checks
        assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
