"""Finds a cell's parts by name.

`BENCHMARK.json` at the root of the checkout names the cells. Everything
that belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own, found by name:

- a configuration: the `file` its entry names (`bench/configs/<name>.json`),
  with the plain reference and the per-step counts of its architecture,
  `bench/reference/<arch>.py` and `bench/counts/<arch>.py`;
- a traffic mix: `bench/traffic/<traffic>.json`, its parameters, read by
  the generator its `kind` names, `bench/traffic/kinds/<kind>.py`;
- a cell's load level and limits: `bench/cells/<cell>.json`;
- a metric, end-to-end or per-layer: its reader `bench/metrics/<name>.py`.

So a later cell, configuration or metric is added by adding files and
entries, never by editing a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file, as run
    traffic_name: str
    traffic: dict           # the traffic mix's parameters
    end_to_end: tuple       # BENCHMARK.json entries this cell reports
    per_layer: tuple
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text(encoding="utf-8"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text(encoding="utf-8"))
    traffic_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_path.read_text(encoding="utf-8"))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
        root=root)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: str) -> Callable[[Any], float | None]:
    """`read(run) -> value or None` from `bench/metrics/<metric>.py`. The
    readers share their arithmetic through modules beside them."""
    metrics = str(cell.bench_dir / "metrics")
    if metrics not in sys.path:
        sys.path.insert(0, metrics)
    mod = _load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def reference_module(cell: Cell):
    """The plain reference of the configuration's architecture
    (`bench/reference/<arch>.py`)."""
    name = cell.config["bench"]["arch"]
    return _load_module(cell.bench_dir / "reference" / f"{name}.py",
                        "bench_reference_" + name.replace("-", "_"))


def counts_module(cell: Cell):
    """The operation and byte counts of the configuration's architecture
    (`bench/counts/<arch>.py`)."""
    name = cell.config["bench"]["arch"]
    return _load_module(cell.bench_dir / "counts" / f"{name}.py",
                        "bench_counts_" + name.replace("-", "_"))
