"""The harness is driven by data: a new kind of driver, a new traffic mix
and a new metric are new files only, found by name."""
import json
import shutil

from bench import drivers, run, spec

# a kind the benchmark does not have: a closed loop of plain queries from
# the configuration's pool, written as a later change would add it
CLOSED_SEARCH = '''
import numpy as np

from bench import data, reference
from bench.drivers import Run, SearchDriver, now


class Driver(SearchDriver):

    def window(self, seconds):
        rng = np.random.default_rng([self.seed, 9])
        led, picks = self.ledger, []

        def send():
            i = int(rng.integers(0, len(self.pool)))
            led.add(self.eng.submit(self.pool[i]))
            picks.append(i)

        t0 = now()
        t_end = t0 + seconds
        for _ in range(int(self.mix["outstanding"])):
            send()
        while led.pending:
            led.wait_oldest(t_end + 60.0)
            if now() < t_end:
                send()
        self.picks = np.asarray(picks)
        run = Run(seconds=seconds, window_start=t0, window_end=t_end)
        run.extra["picks"] = len(picks)
        return run

    def judged(self):
        _, truth = reference.brute_force(self.pool, self.base,
                                         self.cfg["search"]["k"])
        return self.pool[self.picks], truth[self.picks], None


def control_requests(cfg, mix, seed, seconds, requests, rows):
    base, pool = data.make_corpus(cfg, seed)
    _, truth = reference.brute_force(pool, base, cfg["search"]["k"])
    return base, pool, truth, None
'''


def test_every_cell_names_files_that_exist():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        assert w["config"] in spec.list_configs()
        assert w["traffic"] in spec.list_mixes()
        assert spec.mix(w["traffic"])["kind"] in spec.list_kinds()
        for trace in (False, True):
            for m in spec.metrics_for(bench, w["name"], trace):
                assert spec.reader_path(m["name"]).exists(), m["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_quantity_read_in_several_cells_has_one_reader():
    path = spec.reader_path("flush_ms.serve")
    assert path.name == "flush_ms.py"
    assert spec.reader_path("flush_ms.some_later_cell") == path
    assert spec.reader_path("qps").name == "qps.py"


def _tree_with_new_files(tmp_path):
    """A copy of the benchmark with a new kind, mix, metric and cell."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "kinds"):
        shutil.copytree(spec.BENCH_DIR / sub, bench_dir / sub)
    (bench_dir / "kinds" / "closed_search.py").write_text(CLOSED_SEARCH)
    mix = spec.mix("explore_closed")
    (bench_dir / "traffic" / "search_closed.json").write_text(json.dumps(
        {"kind": "closed_search", "outstanding": 256,
         "engine": mix["engine"], "rehearsal": mix["rehearsal"]}))
    (bench_dir / "metrics" / "picks_sent.py").write_text(
        '"""picks_sent: requests the closed loop sent."""\n\n\n'
        "def read(run):\n"
        '    return run.extra.get("picks")\n')
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "audio.search_closed",
                               "config": "audio-192-l2",
                               "traffic": "search_closed", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "picks_sent.search", "unit": "req",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["audio.search_closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench_dir, bench


def test_a_dummy_mix_and_metric_are_new_files_only(tmp_path):
    """A new kind of driver too, and the cell and its control run."""
    bench_dir, bench = _tree_with_new_files(tmp_path)
    assert "closed_search" in spec.list_kinds(bench_dir)
    assert "search_closed" in spec.list_mixes(bench_dir)
    assert "picks_sent" in spec.list_metrics(bench_dir)
    read = spec.metric_reader("picks_sent.search", bench_dir)
    assert read(drivers.Run()) is None
    assert read(drivers.Run(extra={"picks": 7})) == 7
    names = [m["name"] for m in spec.metrics_for(bench, "audio.search_closed",
                                                 False)]
    assert names == ["setup_s", "picks_sent.search"]
    assert "picks_sent.search" not in [
        m["name"] for m in spec.metrics_for(bench, "enron.build_refine",
                                            False)]

    out = run.run(["--workload", "audio.search_closed", "--seed", "3",
                   "--seconds", "1.0", "--trace", "0", "--rehearse"],
                  bench_dir=bench_dir, root=tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert sorted(out["rehearsal"]["readable"]) == ["picks_sent.search",
                                                    "setup_s"]
    nums = run_control(bench_dir, tmp_path)
    assert nums["recall_miss"] <= 0.05 and nums["missing"] == 0


def run_control(bench_dir, root):
    from bench import control

    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = spec.cell(bench, "audio.search_closed")
    cfg = run._merge(spec.config(cell["config"], bench_dir),
                     {"query_pool": 16, "n": 400})
    mix = spec.mix(cell["traffic"], bench_dir)
    return control.control_numbers(cfg, mix, 1, 1.0, 16, 0, bench_dir)
