"""Cells at widths a CPU test can hold, for each model family: the
configuration and workload of the family's first cell of the benchmark,
cut by the family's ``SMALL`` (its shapes, traffic and limits), with the
recipe of the real cells."""
from __future__ import annotations

import cells


def _cells(chips: int) -> list:
    bench = cells.load_json(cells.ROOT / "BENCHMARK.json")
    return [cells.load_cell(w["name"]) for w in bench["workloads"]
            if w["chips"] == chips]


def families(chips: int = 1) -> list:
    """The families that have a cell of the benchmark on ``chips`` chips."""
    return sorted({c.config["family"] for c in _cells(chips)})


def cell(family="vit", chips=1, config_over=None, **over) -> cells.Cell:
    """The family's first cell on ``chips`` chips, cut to its ``SMALL``;
    ``config_over`` changes shapes, ``over`` workload keys."""
    base = next(c for c in _cells(chips) if c.config["family"] == family)
    cut = base.family.SMALL
    shapes = dict(cut["shapes"], **(config_over or {}))
    config = dict(base.config, name="small", overrides=shapes, **shapes)
    traffic = dict(base.traffic, **dict(cut["traffic"],
                                        limits=cut["limits"], **over))
    return cells.Cell(name="small", chips=chips, config=config,
                      traffic=traffic, family=base.family,
                      end_to_end=(), per_layer=())
