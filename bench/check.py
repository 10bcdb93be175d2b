"""The comparison that decides ``correct``.

The program's first ``check_steps`` steps, read from its own state, against
the reference's (``reference.py``) on the same seed:

- ``loss_gap``: the largest relative gap of a step's loss.
- ``grad_gap``: the first step's gradient as the optimizer gets it (after
  the clip), leaf by leaf: the gap between the two norms of a leaf over
  the larger of the reference's norm of that leaf and of the median leaf.
  The program's is read twice, from its first Adam moment
  (``mu / (1 - b1)``) and from its second (``sqrt(nu / (1 - b2))``); the
  worse of the two counts, so a wrong ``b1`` or ``b2`` shows.
- ``update_gap``: the same for the change of each parameter over the
  ``check_steps`` steps. Leaves whose reference gradient is under a
  thousandth of the median leaf's move under Adam by round-off alone and
  are left out.

Each number with a limit in the workload file is held to it; a number
without one is not compared (a cell leaves out a number whose sound runs
and whose control and faults do not separate). A gap that is not a
finite number fails.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
STILL = 1e-3


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    if set(prog) != set(ref):
        raise KeyError(f"parameter leaves differ: program only "
                       f"{sorted(set(prog) - set(ref))}, reference only "
                       f"{sorted(set(ref) - set(prog))}")
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in ref if keep is None or keep(k))


def gaps(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [...], "grad": {leaf: norm},
    "grad2": {leaf: norm}, "change": {leaf: norm}}; ``grad2`` is the
    gradient read from the second moment (the reference's own is not
    compared)."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError(f"{len(prog['losses'])} program losses, "
                         f"{len(ref['losses'])} reference losses")
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    floor = STILL * statistics.median(ref["grad"].values())
    return {
        "loss_gap": loss,
        "grad_gap": max(leaf_gap(prog["grad"], ref["grad"]),
                        leaf_gap(prog["grad2"], ref["grad"])),
        "update_gap": leaf_gap(prog["change"], ref["change"],
                               keep=lambda k: ref["grad"][k] >= floor),
    }


def judge(values: dict, limits: dict) -> tuple:
    """{name: {"value": v, "limit": l}} of the numbers ``limits`` names,
    and whether all hold."""
    unknown = set(limits) - set(NUMBERS)
    if unknown or not limits:
        raise KeyError(f"limits must name some of {NUMBERS}, not "
                       f"{sorted(unknown) or 'none'}")
    checks = {k: {"value": values.get(k, float("nan")), "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return checks, ok
