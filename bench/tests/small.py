"""A cell at widths a CPU test can hold: the shapes of vit-b16 cut down,
the recipe and limits of the real cells."""
from __future__ import annotations

import cells

SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "image_size": 32, "patch_size": 8}


def config(**over) -> dict:
    base = cells.load_json(cells.BENCH / "configs" / "vit-b16.json")
    shapes = dict(SMALL, **over)
    return dict(base, name="small", overrides=shapes, **shapes)


def traffic(workload="vit-b16.dp1", **over) -> dict:
    base = cells.load_json(cells.BENCH / "workloads" / f"{workload}.json")
    return dict(base, **dict({"global_batch": 8, "ref_rows": 4,
                              "trace_steps": 2}, **over))


def cell(workload="vit-b16.dp1", chips=1, config_over=None, **over):
    return cells.Cell(name="small", chips=chips,
                      config=config(**(config_over or {})),
                      traffic=traffic(workload, **over),
                      end_to_end=(), per_layer=())
