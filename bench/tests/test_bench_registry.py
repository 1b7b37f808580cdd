"""The harness is driven by data: a new kind of driver, a new traffic mix,
a new metric, and a new configuration with its generator, are new files
only, found by name."""
import json
import shutil

import numpy as np
import pytest

from bench import drivers, judge, reference, run, spec

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


def control_requests(cfg, mix, seed, seconds, requests, rows, bench_dir):
    base, pool = data.make_corpus(cfg, seed, bench_dir)
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


@pytest.mark.parametrize("config", spec.list_configs())
def test_every_configuration_states_its_control_sizes(config):
    """The sizes at which test_bench_control.py runs its control."""
    cfg = spec.config(config)
    assert isinstance(cfg.get("control"), dict), (
        f"bench/configs/{config}.json has no 'control' sizes")


def test_a_quantity_read_in_several_cells_has_one_reader():
    path = spec.reader_path("flush_ms.serve")
    assert path.name == "flush_ms.py"
    assert spec.reader_path("flush_ms.some_later_cell") == path
    assert spec.reader_path("qps").name == "qps.py"


def _tree_with_new_files(tmp_path):
    """A copy of the benchmark with a new kind, mix, metric and cell."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "kinds", "generators"):
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


# a generator the benchmark does not have: the planted manifold, each row
# and each query given one of ``tags`` tags
TAGGED_MANIFOLD = '''
from pathlib import Path

import numpy as np

from bench import spec


def make(cfg, seed):
    here = Path(__file__).resolve().parents[1]
    c = spec.generator("planted_manifold", here).make(cfg, seed)
    rng = np.random.default_rng([seed, 5])
    tags = cfg["generator"]["tags"]
    c.meta["tags"] = rng.integers(0, tags, len(c.base))
    c.meta["query_tags"] = rng.integers(0, tags, len(c.pool))
    return c
'''

# a kind the benchmark does not have: a closed loop of queries from the
# pool, each restricted to the rows that carry its tag (the others are
# excluded from its results)
FILTERED_SEARCH = '''
import numpy as np

from bench import data, reference
from bench.drivers import Run, SearchDriver, now

CELL_KIND = "filtered"


def admissible(tags, query_tags):
    return lambda r, x: tags[x] == query_tags[r]


class Driver(SearchDriver):

    def window(self, seconds):
        rng = np.random.default_rng([self.seed, 9])
        tags, qtags = self.meta["tags"], self.meta["query_tags"]
        others = {t: np.flatnonzero(tags != t).tolist()
                  for t in np.unique(qtags)}
        led, picks = self.ledger, []

        def send():
            i = int(rng.integers(0, len(self.pool)))
            led.add(self.eng.submit(self.pool[i], exclude=others[qtags[i]]))
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
        return Run(seconds=seconds, window_start=t0, window_end=t_end)

    def judged(self):
        meta = self.meta
        adm = admissible(meta["tags"], meta["query_tags"][self.picks])
        _, truth = reference.brute_force(self.pool[self.picks], self.base,
                                         self.cfg["search"]["k"], adm,
                                         self.cfg["metric"])
        return self.pool[self.picks], truth, adm


def control_requests(cfg, mix, seed, seconds, requests, rows, bench_dir):
    c = data.corpus(cfg, seed, bench_dir)
    adm = admissible(c.meta["tags"], c.meta["query_tags"])
    _, truth = reference.brute_force(c.pool, c.base, cfg["search"]["k"], adm,
                                     cfg["metric"])
    return c.base, c.pool, truth, adm
'''

NEW_FILES = {"generators/tagged_manifold.py": TAGGED_MANIFOLD,
             "kinds/filtered_search.py": FILTERED_SEARCH}


def _tree_with_a_new_configuration(tmp_path):
    """A copy of the benchmark with a new configuration, its generator, a
    kind that restricts its requests to admissible rows, and a cell."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "kinds", "generators"):
        shutil.copytree(spec.BENCH_DIR / sub, bench_dir / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cfg = spec.config("audio-192-l2")
    cfg.update(name="tagged-192-l2",
               generator=dict(cfg["generator"], kind="tagged_manifold",
                              tags=2),
               rehearsal={"n": 400, "query_pool": 64},
               control={"query_pool": 32})
    mix = spec.mix("explore_closed")
    new = dict(NEW_FILES)
    new["configs/tagged-192-l2.json"] = json.dumps(cfg)
    new["traffic/filtered_closed.json"] = json.dumps(
        {"kind": "filtered_search", "outstanding": 256,
         "engine": mix["engine"], "rehearsal": mix["rehearsal"]})
    for rel, text in new.items():
        (bench_dir / rel).write_text(text)
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": "tagged.filtered_closed",
                               "config": "tagged-192-l2",
                               "traffic": "filtered_closed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "recall_at_10":
            m["workloads"].append("tagged.filtered_closed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench_dir, bench, set(new)


def test_a_new_configuration_is_new_files_only(tmp_path):
    """A configuration with its own generator, whose requests admit only
    the rows of their tag: rehearsed correct, its control not correct on
    dist_err, an answer outside its admissible rows a bad row, and no
    file of the benchmark edited."""
    from bench import control

    bench_dir, bench, new = _tree_with_a_new_configuration(tmp_path)
    out = run.run(["--workload", "tagged.filtered_closed", "--seed", "5",
                   "--seconds", "1.0", "--trace", "0", "--rehearse"],
                  bench_dir=bench_dir, root=tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert sorted(out["rehearsal"]["readable"]) == ["recall_at_10",
                                                    "setup_s"]

    cell = spec.cell(bench, "tagged.filtered_closed")
    cfg = spec.config(cell["config"], bench_dir)
    cfg = run._merge(cfg, cfg["control"])
    mix = spec.mix(cell["traffic"], bench_dir)
    nums = control.control_numbers(cfg, mix, 1, 1.0, 0, 0, bench_dir)
    ok, checks = judge.verdict(nums, cfg["limits"])
    assert not ok and checks["dist_err"]["value"] > cfg["limits"]["dist_err"]
    assert checks["recall_miss"]["value"] == 0.0, checks

    kind = drivers.load_kind(mix["kind"], bench_dir)
    base, q, truth, adm = kind.control_requests(cfg, mix, 1, 1.0, 0, 0,
                                                bench_dir)
    k = cfg["search"]["k"]
    meta = spec.generator("tagged_manifold", bench_dir).make(cfg, 1).meta
    planted = truth.copy()
    planted[0, -1] = np.flatnonzero(meta["tags"] != meta["query_tags"][0])[0]
    for ids, bad in ((truth, 0), (planted, 1)):
        d = reference.exact_distances(q, base, ids)
        order = np.argsort(d, axis=1)
        ids, d = (np.take_along_axis(a, order, 1) for a in (ids, d))
        answered = np.ones(len(q), bool)
        assert judge.answer_numbers(base, q, ids, d, answered, truth, k,
                                    admissible=adm)["bad_rows"] == bad
        assert judge.answer_numbers(base, q, ids, d, answered, truth,
                                    k)["bad_rows"] == 0

    for path in bench_dir.rglob("*"):
        rel = path.relative_to(bench_dir).as_posix()
        if path.is_file() and "__pycache__" not in rel and rel not in new:
            repo = spec.BENCH_DIR / rel
            assert path.read_bytes() == repo.read_bytes(), rel
