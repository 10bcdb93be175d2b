"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of its
own, found by name and never by a table in code, so a later change adds a
cell, a configuration or a per-layer metric by adding files:

    bench/configs/<config>.json      model shapes, source, cuts
    bench/workloads/<traffic>.json   the training job: batch, layout, recipe
    bench/metrics/<metric>.py        reader of one per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# every key a workload file may hold; the harness honours each of them, and
# refuses a file with any other, so a knob it does not read cannot be set
TRAFFIC_KEYS = frozenset((
    "why", "zero", "global_batch", "accum", "dataset", "train_size", "guard",
    "prefetch_depth", "optimizer", "check_steps", "ref_rows", "trace_steps",
    "limits"))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/workloads/<traffic>.json
    end_to_end: tuple       # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(
        root / "bench" / "workloads" / f"{_checked(w['traffic'])}.json")
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"workload {w['traffic']!r} sets keys the harness "
                         f"does not read: {sorted(unknown)}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``root/bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{_checked(name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
