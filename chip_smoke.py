"""Prove that the vit-b16 trainer runs on a TPU at full width.

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # one four-chip host: dp4 ZeRO-0/3

One process drives every phase through ``repro.launch.train.main``, the
code that ``python -m repro.launch.train`` runs, and it never starts a
child: the chip belongs to this process. Each phase raises on failure.

a. Device check: the first JAX device must be a TPU. There is no fallback.
b. Default path: vit-b16 at its published widths (12 layers, d 768, 224
   px) on procedural CIFAR-10 upsampled on device, bf16 compute, AdamW,
   ZeRO 0, dp 1. Every loss is finite and no step was skipped by the
   anomaly guard. Prints the warm-step time (each step ended by
   ``block_until_ready``) and the device's peak memory.
c. Flash path: the same run with ``--use-pallas``. The compiled step must
   hold the Mosaic kernel (``tpu_custom_call``, not interpret mode) and its
   step-0 loss must match b's within ``FLASH_LOSS_TOL``.
d. Checkpoint round trip: steps 0-3 with async saves every 2 steps into
   ``.smoke_ckpt/``, then ``--resume``: steps 4-7 must equal b's bit for
   bit, the engine's resume contract.

``--four-chips`` runs only this phase: vit-b16 in float32 for 3 steps as
dp4 ZeRO-0 and dp4 ZeRO-3, each compared with the same run on one device
of the four, and ZeRO-3 must spread the parameter and optimizer bytes over
all four devices.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import statistics
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

STEPS = 8
RESUME_AT = 4
# global batch. The compiler puts the bf16 step at 14.8 GiB of a v5e's
# 15.75 GiB at 64 and at 24.9 GiB at 128.
BATCH = 64
# float32 at batch 64 needs 17.1 GiB on one chip: the --four-chips runs
# split the batch into 4 microbatches, the one-device reference included
FOUR_CHIP_ACCUM = 4
# |loss_flash - loss_naive| at step 0. Same params and batch; only the
# attention differs: the flash kernel keeps its softmax and accumulators in
# fp32 while the naive path rounds scores and probabilities to bf16. The
# bound is about one bf16 rounding step (2**-8 relative) of a ~2.5 loss.
FLASH_LOSS_TOL = 1e-2
# per-step loss tolerances of tests/test_distributed_fast.py
DP_LOSS_TOL = 2e-4
ZERO_LOSS_TOL = 3e-4
CKPT_DIR = ROOT / ".smoke_ckpt"


def train_argv(batch, steps=STEPS, *extra):
    return ["--arch", "vit-b16", "--dataset", "cifar10", "--seed", "0",
            "--batch", str(batch), "--steps", str(steps),
            "--log-every", "1", *extra]


def train_rows(run):
    """Train rows of a run, checked: all finite, none guard-skipped."""
    rows = [h for h in run.history if "loss" in h]
    for h in rows:
        if not (math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"])):
            raise RuntimeError(f"non-finite step {h['step']}: {h}")
        if h["step_ok"] != 1 or h["guard_skips"]:
            raise RuntimeError(f"anomaly guard skipped step {h['step']}: "
                               f"{h}")
    return rows


def device_check():
    """Phase a: a TPU, or an error. Returns the device record."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    print(f"[smoke] jax {jax.__version__}: {len(devs)} x "
          f"{devs[0].platform} ({kind})", flush=True)
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke.py needs a TPU; JAX found {devs[0].platform} "
            f"({kind}). It has no CPU fallback: run it on the chip.")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def default_path(batch, kind):
    """Phase b."""
    import jax
    from repro.launch import train
    rows = train_rows(train.main(train_argv(batch)))
    if len(rows) != STEPS:
        raise RuntimeError(f"{len(rows)} train rows, expected {STEPS}")
    warm = [h["step_s"] for h in rows[2:]]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[smoke] {kind}: vit-b16 bf16 batch {batch}: warm step "
          f"median {statistics.median(warm):.4f} s over steps 2-"
          f"{STEPS - 1} {[round(t, 4) for t in warm]}; first step "
          f"{rows[0]['step_s']:.1f} s (compile included); "
          f"peak_bytes_in_use {peak}", flush=True)
    print(f"[smoke] losses {[h['loss'] for h in rows]}", flush=True)
    print(f"[smoke] memory_stats {stats}", flush=True)
    return rows


def flash_path(batch, ref_rows, kind):
    """Phase c."""
    from repro.launch import train
    run = train.main(train_argv(batch, STEPS, "--use-pallas"))
    rows = train_rows(run)
    if "tpu_custom_call" not in run.compiled_step_text():
        raise RuntimeError("the --use-pallas train step holds no Mosaic "
                           "kernel (tpu_custom_call): interpret mode?")
    diff = abs(rows[0]["loss"] - ref_rows[0]["loss"])
    warm = [h["step_s"] for h in rows[2:]]
    print(f"[smoke] {kind}: flash step-0 loss {rows[0]['loss']} vs "
          f"{ref_rows[0]['loss']} (|diff| {diff:.3g}, tol "
          f"{FLASH_LOSS_TOL}); warm step median "
          f"{statistics.median(warm):.4f} s", flush=True)
    if not diff <= FLASH_LOSS_TOL:
        raise RuntimeError(f"flash step-0 loss off by {diff}")


def resume_round_trip(batch, ref_rows):
    """Phase d."""
    from repro.launch import train
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    try:
        ckpt = ["--ckpt-dir", str(CKPT_DIR)]
        first = train_rows(train.main(train_argv(
            batch, STEPS, *ckpt, "--ckpt-every", "2",
            "--stop-after", str(RESUME_AT))))
        second = train_rows(train.main(train_argv(
            batch, STEPS, *ckpt, "--resume")))
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    got = [h["loss"] for h in first + second]
    want = [h["loss"] for h in ref_rows]
    if [h["step"] for h in second] != list(range(RESUME_AT, STEPS)) \
            or got != want:
        raise RuntimeError(f"resumed losses differ from the uninterrupted "
                           f"run:\n got  {got}\n want {want}")
    print(f"[smoke] resume at step {RESUME_AT}: steps {RESUME_AT}-"
          f"{STEPS - 1} bit-identical to the uninterrupted run", flush=True)


def bytes_per_device(tree):
    import jax
    out = Counter()
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] += shard.data.nbytes
    return out


def four_chips(batch):
    """dp4 ZeRO-0 and ZeRO-3 against one device, float32, 3 steps."""
    import jax
    devs = jax.devices()
    if len(devs) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
    # A float32 matmul at the TPU's default precision is a bf16 pass, and
    # its rounding differs between layouts by more than the tolerance
    # (5e-3 after one step on a v5e), so the comparison runs in true fp32.
    with jax.default_matmul_precision("highest"):
        compare_layouts(batch, devs)


def compare_layouts(batch, devs):
    import jax
    from repro.launch import train
    argv = train_argv(batch, 3, "--dtype", "float32",
                      "--accum", str(FOUR_CHIP_ACCUM))
    ref = [h["loss"] for h in train_rows(train.main(argv, devices=devs[:1]))]
    print(f"[smoke] 1 device float32 losses {ref}", flush=True)
    for zero, tol in ((0, DP_LOSS_TOL), (3, ZERO_LOSS_TOL)):
        run = train.main(argv + ["--zero", str(zero)])
        got = [h["loss"] for h in train_rows(run)]
        diffs = [abs(a - b) for a, b in zip(got, ref)]
        print(f"[smoke] dp4 ZeRO-{zero} losses {got}; max |diff| "
              f"{max(diffs):.3g} (tol {tol})", flush=True)
        if len(got) != len(ref) or max(diffs) > tol:
            raise RuntimeError(f"dp4 ZeRO-{zero} diverges from one device")
        tree = (run.state.params, run.state.opt_state)
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        held = bytes_per_device(tree)
        shares = {d: held[d] / total for d in sorted(held)}
        print(f"[smoke] dp4 ZeRO-{zero}: {total} state bytes; share held "
              f"per device {shares}", flush=True)
        if zero == 3 and (len(shares) != 4 or not all(
                abs(s - 0.25) < 0.01 for s in shares.values())):
            raise RuntimeError(f"ZeRO-3 does not spread the state over the "
                               f"4 devices: {shares}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp4 ZeRO-0/ZeRO-3 comparison")
    args = ap.parse_args(argv)
    device = device_check()
    if args.four_chips:
        four_chips(BATCH)
    else:
        kind = device["kind"]
        ref = default_path(BATCH, kind)
        flash_path(BATCH, ref, kind)
        resume_round_trip(BATCH, ref)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
