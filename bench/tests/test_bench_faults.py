"""A run with the timed path broken underneath must come out not correct:
an answer altered where it is produced, half of each flush left out, a
build step that inserts nothing, and a refinement that leaves the graph as
it was.  The harness's look for a chip is skipped (``--rehearse``); the
rest of the run is the real one."""
import pytest

from bench import faults, run

ARGS = ["--seed", "11", "--seconds", "1.5", "--trace", "0", "--rehearse"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
@pytest.mark.parametrize("workload", ["audio.serve_poisson",
                                      "audio.explore_closed"])
def test_a_broken_search_is_not_correct(workload, fault):
    with faults.planted(fault):
        out = run.run(["--workload", workload] + ARGS)
    assert not out["correct"], out["checks"]


def test_a_build_step_that_changes_nothing_is_not_correct():
    with faults.planted("add_noop"):
        out = run.run(["--workload", "enron.build_refine"] + ARGS)
    assert out["attempted"] > 0
    assert not out["correct"], out["checks"]
    assert out["checks"]["rows_differ"]["value"] > 0


def test_a_refinement_that_changes_nothing_is_not_correct():
    with faults.planted("refine_noop"):
        out = run.run(["--workload", "enron.build_refine"] + ARGS)
    assert out["attempted"] > 0
    assert not out["correct"], out["checks"]
    c = out["checks"]["refine_idle"]
    assert c["value"] > c["limit"], out["checks"]


def test_an_altered_answer_of_a_one_request_flush_is_not_correct():
    """The interactive cell's flushes hold one request each, so half of a
    flush cannot be left out; an altered answer can."""
    with faults.planted("answer_altered"):
        out = run.run(["--workload", "audio.interactive"] + ARGS)
    assert out["attempted"] > 0
    assert not out["correct"], out["checks"]
