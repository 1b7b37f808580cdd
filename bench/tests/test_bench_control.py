"""The control of ``correct`` — the brute force one precision step below
the configuration's, put in the program's place — must be judged not
correct on every seed, for each kind of cell.  Each configuration gives
the sizes under ``control``: the audio corpus is whole (the control's error
grows with the corpus' density) and the requests are few; the Enron corpus
is cut to 4,000 rows with the cell's 1,000 probe queries over 3,000
inserted."""
import pytest

from bench import control, judge, run, spec


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.load_benchmark()["workloads"]])
def test_the_control_is_not_correct(workload):
    cell = spec.cell(spec.load_benchmark(), workload)
    cfg = spec.config(cell["config"])
    cfg = run._merge(cfg, cfg["control"])
    mix = spec.mix(cell["traffic"])
    mix = run._merge(mix, mix["rehearsal"])
    for seed in (1, 2, 3):
        nums = control.control_numbers(cfg, mix, seed, 2.0, 300, 3000)
        ok, checks = judge.verdict(nums, cfg["limits"])
        assert not ok, checks
        assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
