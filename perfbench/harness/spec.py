"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under the benchmark directory:

  configs/<config>.json     sizes of the configuration as it is run
  reference/<family>.py     its plain reference (named by the config)
  systems/<system>.py       how the program is driven (named by the config)
  traffic/<traffic>.json    the traffic mix, read by harness.traffic
  limits/<workload>.json    the limits of the numbers ``correct`` compares
  metrics/<metric>.py       the reader of one per-layer metric

So a new cell, mix or metric is new files plus new entries in
``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType
from typing import Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(ValueError):
    """A name, file or entry that the benchmark cannot use."""


def check_name(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"bad name {name!r}")
    return name


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (names may hold '-' and '.')."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_name = f"perfbench_{path.parent.name}_{path.stem}".replace(
        "-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # entries of BENCHMARK.json this cell reports
    per_layer: list
    bench_dir: pathlib.Path

    def system(self) -> ModuleType:
        return load_module(self.bench_dir / "systems"
                           / f"{check_name(self.config['system'])}.py")

    def reference(self) -> ModuleType:
        return load_module(self.bench_dir / "reference"
                           / f"{check_name(self.config['reference'])}.py")

    def metric_reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics"
                           / f"{check_name(metric)}.py")


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(workload: str, bench_dir: pathlib.Path = BENCH_DIR,
              bench_json: Optional[pathlib.Path] = None) -> Cell:
    """The cell named ``workload``, with its config, traffic and limits."""
    bench = load_json(bench_json or bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload} names unknown config "
                        f"{w['config']!r}")
    cfg_entry = configs[w["config"]]
    cfg_path = bench_dir.parent / cfg_entry["file"]
    config = load_json(cfg_path)
    traffic = load_json(bench_dir / "traffic"
                        / f"{check_name(w['traffic'])}.json")
    limits = load_json(bench_dir / "limits" / f"{check_name(workload)}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload) and m["moves"] in e2e_names]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)
