"""Readings that the limits of ``check.py`` are set from, for one cell.

    python bench/calibrate.py --workload vit-b16.dp1 --seeds 1-12 \
        --control-seeds 3 --out calibrate.vit-b16.dp1.json

In one process that holds the cell's chips:

- the program, sound, on every seed: its first ``check_steps`` steps as
  a run makes them, against the float32 reference (the lower readings);
- the control: the reference with fp8 products put in the program's
  place, on the first ``--control-seeds`` seeds (an upper reading);
- faults planted in the reference put in the program's place, on the same
  seeds: half of the batch left out, the mean taken over the rest; the
  exchange between chips left out (each chip's update from its own shard,
  which is what chip 0 keeps), for cells on several chips; one row's
  logits altered where the head produces them; AdamW with a wrong ``b2``,
  and without its weight decay. A step that returns its
  state unchanged reads 1 on ``update_gap`` by construction, and needs
  no run.

The benchmark's own runs do not run this. It prints one JSON object.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import cells  # noqa: E402
import check  # noqa: E402

# one row's answer made a confident wrong one
LOGIT_SHIFT = 8.0
# optimizer settings a step could get wrong: the second moment's decay,
# and the decoupled weight decay left out
OPTIMIZER_FAULTS = {"wrong_b2": {"b2": 0.999}, "no_decay": {"weight_decay": 0.0}}


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def program_readings(cell, seeds, devices) -> dict:
    import run
    import system
    out = {}
    for seed in seeds:
        t = system.build(cell, seed, devices)
        try:
            out[seed] = run.check_steps(t, cell.traffic)
        finally:
            t.close()
    return out


def readings(cell, seeds, control_seeds, devices) -> dict:
    from reference import Reference
    prog = program_readings(cell, seeds, devices)
    ref = Reference(cell, device=devices[0])
    fp8 = Reference(cell, precision="fp8", device=devices[0])
    batch = cell.traffic["global_batch"]
    faults = {"half_batch": {"rows": batch // 2},
              "altered_logits": {"logit_shift": LOGIT_SHIFT}}
    if cell.chips > 1:
        faults["no_exchange"] = {"rows": batch // cell.chips}
    opt = cell.traffic["optimizer"]
    planted = {name: Reference(cell, device=devices[0],
                               step_opt=dict(opt, **over))
               for name, over in OPTIMIZER_FAULTS.items()}
    out = {"program": {}, "control": {},
           "faults": {k: {} for k in list(faults) + list(planted)}}
    for seed in seeds:
        r = ref.readings(seed)
        out["program"][seed] = check.gaps(prog[seed], r)
        if seed in control_seeds:
            out["control"][seed] = check.gaps(fp8.readings(seed), r)
            for name, kw in faults.items():
                out["faults"][name][seed] = check.gaps(
                    ref.readings(seed, **kw), r)
            for name, wrong in planted.items():
                out["faults"][name][seed] = check.gaps(wrong.readings(seed), r)
            unchanged = dict(prog[seed], change={
                k: 0.0 for k in prog[seed]["change"]})
            out["faults"].setdefault("state_unchanged", {})[seed] = \
                check.gaps(unchanged, r)
    for group in [out["program"], out["control"]] + list(
            out["faults"].values()):
        runs = list(group.values())
        if runs:
            group["max"] = {k: max(r[k] for r in runs) for k in check.NUMBERS}
            group["min"] = {k: min(r[k] for r in runs) for k in check.NUMBERS}
    return out


def main(argv=None):
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,9,27")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    run.use_compile_cache()
    devices = run.chip_devices(cell.chips, run.peaks())
    seeds = seed_list(args.seeds)
    out = readings(cell, seeds, set(seeds[:args.control_seeds]), devices)
    out["workload"] = args.workload
    text = json.dumps(out, indent=1, default=str)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
