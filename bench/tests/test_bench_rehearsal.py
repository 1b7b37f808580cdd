"""Each cell of BENCHMARK.json, rehearsed end to end on the CPU at the tiny
size its files give: the run completes, is judged correct, reads every
metric the cell reports, and prints no metric value from the CPU."""
import pytest

from bench import run, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_each_traffic_mix(workload, capsys):
    out = run.run(["--workload", workload, "--seed", str(2**31 + 7),
                   "--seconds", "1.5", "--trace", "0", "--rehearse"])
    printed = capsys.readouterr().out
    assert "platform=cpu" in printed
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert "metrics" not in out
    want = [m["name"] for m in spec.metrics_for(spec.load_benchmark(),
                                                workload, False)]
    assert sorted(out["rehearsal"]["readable"]) == sorted(want)
    assert list(out)[-1] == "checks"


def test_without_a_chip_the_run_fails_and_prints_no_result(monkeypatch,
                                                           capsys):
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert "{" not in captured.out
    assert "no TPU" in captured.err
