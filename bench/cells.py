"""Resolve a cell of BENCHMARK.json to its files, by name.

A cell names a configuration and a traffic mix. Everything that belongs to
one of them, or to one per-layer metric, sits in a file of its own:

  BENCHMARK.json configs[].file     the configuration's sizes (JSON)
  bench/traffic/<traffic>.json      the mix, and the driver and engine it runs under
  bench/drivers/<driver>.py         how a window drives the program
  bench/references/<family>.py      the configuration's plain reference
  bench/counts/<family>.py          its operations and bytes, from shapes
  bench/limits/<cell>.json          the limits that decide `correct`
  bench/metrics/<metric>.py         one reader per per-layer metric

so a later change adds a cell, a mix, a model family or a metric by adding
files and entries.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)   # metric specs
    per_layer: list = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _for_cell(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reference(family: str):
    return importlib.import_module(f"bench.references.{family}")


def counts(family: str):
    return importlib.import_module(f"bench.counts.{family}")


def metric_reader(metric: str, root: Path = ROOT):
    """The `read(ctx)` function of bench/metrics/<metric>.py. Metric names
    may hold dots, so the file is loaded by path."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + metric.replace(".", "__"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
