"""The benchmark's data, found by name.

``BENCHMARK.json`` at the root of the checkout lists the cells and the
metrics.  Everything that belongs to one configuration, one traffic mix or
one metric sits in a file of its own, which this module finds by the name
``BENCHMARK.json`` gives:

* a configuration: ``bench/configs/<config>.json``, with its ``limits``,
  its ``rehearsal`` sizes and its ``control`` sizes (those at which
  ``bench/tests/test_bench_control.py`` runs the control of ``correct``);
* a generator of data: ``bench/generators/<kind>.py``, named by a
  configuration's ``generator.kind``; its ``make(cfg, seed)`` returns a
  ``bench.data.Corpus``;
* a traffic mix: ``bench/traffic/<mix>.json``, its parameters and the
  ``kind`` of driver that reads them;
* a kind of driver: ``bench/kinds/<kind>.py`` (``bench/drivers.py``);
* a metric: ``bench/metrics/<metric>.py``, a reader with ``read(run)`` that
  returns a number, or ``None`` when it finds nothing to read.  One
  quantity read alike in several kinds of cell, ``<quantity>.<cell kind>``
  (``flush_ms.serve``, ``flush_ms.explore``), has one reader,
  ``bench/metrics/<quantity>.py``, where no reader of the full name exists.

A later change adds a configuration, a generator, a mix, a kind or a
metric by adding files and entries, and edits none of the files that are
there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(bench_dir / "configs" / f"{name}.json")


def mix(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(bench_dir / "traffic" / f"{name}.json")


def list_configs(bench_dir: Path = BENCH_DIR) -> list[str]:
    return sorted(p.stem for p in (bench_dir / "configs").glob("*.json"))


def list_mixes(bench_dir: Path = BENCH_DIR) -> list[str]:
    return sorted(p.stem for p in (bench_dir / "traffic").glob("*.json"))


def list_kinds(bench_dir: Path = BENCH_DIR) -> list[str]:
    return sorted(p.stem for p in (bench_dir / "kinds").glob("*.py")
                  if not p.name.startswith("_"))


def list_metrics(bench_dir: Path = BENCH_DIR) -> list[str]:
    """The readers' names: the metrics and quantities that have one."""
    return sorted(p.name[: -len(".py")]
                  for p in (bench_dir / "metrics").glob("*.py")
                  if not p.name.startswith("_"))


def reader_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    """``bench/metrics/<name>.py``, else that of the quantity before the
    name's last dot."""
    metrics = bench_dir / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    return path


def load_file(path: Path, prefix: str):
    """The module in the file ``path``, loaded under a name of its own."""
    path = Path(path)
    if not path.exists():
        raise KeyError(f"{path} does not exist")
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run)`` function of the metric's reader."""
    return load_file(reader_path(name, bench_dir), "bench_metric_").read


def generator(kind: str, bench_dir: Path = BENCH_DIR):
    """The module ``bench/generators/<kind>.py``."""
    return load_file(bench_dir / "generators" / f"{kind}.py",
                     "bench_generator_")


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end ones with
    ``trace`` off, its per-layer ones with it on.  An end-to-end metric with
    no ``workloads`` key belongs to every cell; a per-layer one with none,
    to every cell that reports the metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads",
                                 [workload] if m["moves"] in moved else [])]
