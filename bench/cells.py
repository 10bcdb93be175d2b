"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of its
own, found by name and never by a table in code, so a later change adds a
cell, a configuration, a model family or a per-layer metric by adding
files:

    bench/configs/<config>.json      model shapes, source, cuts, family
    bench/workloads/<traffic>.json   the training job: batch, layout, recipe
    bench/families/<family>.py       what the harness needs of a kind of model
    bench/metrics/<metric>.py        reader of one per-layer metric

A family module (``families/vit.py``) provides:

- ``SHAPE_KEYS``: the configuration file's keys that must equal the
  program's ``ModelConfig``; a dotted key (``moe.num_experts``) reaches a
  nested group;
- ``TRAFFIC_KEYS``: the workload keys it reads beyond the harness's own;
- ``build_data(cfg, traffic, seed)``: the program's ``DataPipeline`` for
  the job and the ``preproc`` the engine is given;
- ``batch(config, traffic, seed, k)``: the host arrays of step ``k``, as
  a tuple, the batch axis leading, made again from the seed as the
  program makes them (a token family draws its ids from the
  configuration's vocabulary);
- ``init_params(config, key)``: the reference's weights, drawn as the
  program draws its own;
- ``nll_sum(config, traffic, mm, params, *arrays, shift)``: the sum over
  a block's rows of each row's loss, every product through ``mm``, with
  ``shift`` added to the logits;
- ``logits_shape(config, traffic, rows)``: the shape ``shift`` has;
- ``train_flops_per_sample(config, traffic)``: the model FLOPs of one
  sample (a token family's sample is a sequence of the workload's length);
- ``SMALL``: the cut the CPU tests run it at (``tests/small.py``).
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# the workload keys the harness reads for every family; a file may hold
# these and its family's, and is refused with any other, so a knob nothing
# reads cannot be set
TRAFFIC_KEYS = frozenset((
    "why", "zero", "global_batch", "accum", "guard", "prefetch_depth",
    "optimizer", "check_steps", "ref_rows", "trace_steps", "limits"))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/workloads/<traffic>.json
    family: Any             # bench/families/<config's family>.py
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
    if "family" not in config:
        raise ValueError(f"configuration {cfg_entry['file']} names no "
                         f"family")
    fam = family(config["family"], root)
    traffic = load_json(
        root / "bench" / "workloads" / f"{_checked(w['traffic'])}.json")
    unknown = set(traffic) - TRAFFIC_KEYS - fam.TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"workload {w['traffic']!r} sets keys neither the "
                         f"harness nor the {config['family']!r} family "
                         f"reads: {sorted(unknown)}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        family=fam,
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def _module(kind: str, name: str, root: Path):
    path = root / "bench" / kind / f"{_checked(name)}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} module {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str, root: Path = ROOT):
    """The module ``root/bench/families/<name>.py``."""
    return _module("families", name, root)


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``root/bench/metrics/<name>.py``."""
    return _module("metrics", name, root).read
