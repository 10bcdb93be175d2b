import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (the two lines above MUST precede any jax-importing import — jax locks the
# device count on first init; see the multi-pod dry-run contract)

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    ASSIGNED_ARCHS,
    EngineConfig,
    applicable,
    get_config,
    get_shape,
)
from repro.configs.shapes import SHAPES  # noqa: E402
from repro.core.comm_model import TPU_V5E  # noqa: E402
from repro.core.engine import DistributedEngine  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402

def comm_time_seconds(coll: dict, hw=TPU_V5E) -> float:
    """Per-device collective time model (§Roofline collective term).

    all-reduce moves ~2x bytes (reduce-scatter + all-gather phases of a
    ring); the others move ~1x their result bytes per device. Bandwidth: 4
    usable ICI links per v5e chip in a 2D torus -> data crosses ~2 links
    concurrently; we charge the per-link bandwidth on the bottleneck link.
    """
    bw = hw.ici_bw
    t = 2.0 * coll["all-reduce"] / bw
    for k in ("all-gather", "reduce-scatter", "all-to-all",
              "collective-permute"):
        t += coll[k] / bw
    return t


def roofline(totals, *, chips: int, model_flops: float,
             hw=TPU_V5E) -> dict:
    """Terms from the trip-count-aware HLO analyzer (per-device program),
    in seconds. XLA's own cost_analysis counts while bodies once — see
    hlo_analysis module docstring."""
    flops = totals.flops
    bytes_acc = totals.hbm_bytes
    coll = totals.coll
    t_compute = flops / hw.peak_flops
    t_memory = bytes_acc / hw.hbm_bw
    t_coll = comm_time_seconds(coll, hw)
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    useful = model_flops / chips / flops if flops else 0.0
    return {
        **terms,
        "dominant": dom,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "model_flops_per_dev": model_flops / chips,
        "useful_flops_frac": useful,
        "bound_step_s": max(terms.values()),
    }


def engine_for(arch: str, shape_name: str, mesh, *, zero: int = None,
               seq_parallel: str = None, remat: str = None,
               use_pallas: bool = False, moe_impl: str = None,
               bf16_gather: bool = False, embed: str = None,
               chunk: int = 0, micro: int = 0):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    cfg = cfg.replace(attn_impl="blockwise")
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    elif shape.kind == "train":
        cfg = cfg.replace(remat="block")   # default for big-model training
    if use_pallas:
        cfg = cfg.replace(use_pallas=True)
    if moe_impl:
        cfg = cfg.replace(moe_impl=moe_impl)
    if chunk and cfg.ssm is not None:
        import dataclasses as _dc
        cfg = cfg.replace(ssm=_dc.replace(cfg.ssm, chunk_size=chunk))
    # default policy: ZeRO-3 + TP for train; serving replicates over dp
    if zero is None:
        zero = 3 if shape.kind == "train" else 3
    if seq_parallel is None:
        seq_parallel = "ulysses" if shape.kind == "prefill" else "none"
    dp_world = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_world *= mesh.devices.shape[mesh.axis_names.index(a)]
    gb = shape.global_batch
    # production default: accumulate down to micro_batch_per_dev == 2 (the
    # paper's gradient-accumulation knob; bounds live activations per device)
    mb = micro or 2
    accum = max(1, gb // (dp_world * mb)) if gb % dp_world == 0 else 1
    ecfg = EngineConfig(
        train_batch_size=max(gb, dp_world) if gb % dp_world == 0 else gb,
        gradient_accumulation_steps=accum,
        zero_stage=zero,
        sequence_parallel=seq_parallel,
        cast_params_bf16=bf16_gather,
        embed_sharding=embed or "vocab",
    )
    if shape.kind != "train":
        # serving engines don't step an optimizer; relax the invariant
        ecfg = ecfg.replace(train_batch_size=dp_world,
                            gradient_accumulation_steps=1)
    return DistributedEngine(cfg, ecfg, mesh), cfg, shape


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             zero: int = None, seq_parallel: str = None, remat: str = None,
             use_pallas: bool = False, verbose: bool = True,
             moe_impl: str = None, bf16_gather: bool = False,
             embed: str = None, chunk: int = 0, micro: int = 0,
             tag: str = "") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "chips": chips, "status": "skip", "reason": reason}
    if not ok:
        return rec

    eng, cfg, shape = engine_for(arch, shape_name, mesh, zero=zero,
                                 seq_parallel=seq_parallel, remat=remat,
                                 use_pallas=use_pallas, moe_impl=moe_impl,
                                 bf16_gather=bf16_gather, embed=embed,
                                 chunk=chunk, micro=micro)
    rec["tag"] = tag
    rec["options"] = {"moe_impl": moe_impl, "bf16_gather": bf16_gather,
                      "embed": embed, "chunk": chunk, "micro": micro,
                      "zero": zero, "seq_parallel": seq_parallel}
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()

    if shape.kind == "train":
        specs = input_specs(cfg, shape)
        lowered = eng.lower_train(specs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        specs = input_specs(cfg, shape)
        lowered = eng.lower_prefill(specs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode: ONE new token against a seq_len cache
        lowered = eng.lower_decode(shape.global_batch, shape.seq_len)
        model_flops = 2.0 * n_active * shape.global_batch

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    totals = hlo_analysis.analyze(hlo)
    rl = roofline(totals, chips=chips, model_flops=model_flops)
    coll = {k: v for k, v in totals.coll.items()}

    rec.update({
        "status": "ok",
        "params": n_params,
        "active_params": n_active,
        "zero": eng.ecfg.zero_stage,
        "seq_parallel": eng.ecfg.sequence_parallel,
        "argument_bytes_per_dev": getattr(mem, "argument_size_in_bytes", -1),
        "output_bytes_per_dev": getattr(mem, "output_size_in_bytes", -1),
        "temp_bytes_per_dev": getattr(mem, "temp_size_in_bytes", -1),
        "peak_bytes_per_dev": (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
        "collectives": coll,
        "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "roofline": rl,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} (pods={2 if multi_pod else 1})"
              f" params={n_params/1e9:.1f}B"
              f" mem/dev={rec['peak_bytes_per_dev']/2**30:.2f}GiB"
              f" dominant={rl['dominant']}"
              f" compute={rl['compute_s']*1e3:.2f}ms"
              f" memory={rl['memory_s']*1e3:.2f}ms"
              f" coll={rl['collective_s']*1e3:.2f}ms"
              f" (lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        sys.stdout.flush()
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (assigned arch x shape)")
    ap.add_argument("--zero", type=int, default=None)
    ap.add_argument("--seq-parallel", default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--bf16-gather", action="store_true")
    ap.add_argument("--embed", default=None)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--micro", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape in pairs:
        try:
            rec = run_pair(arch, shape, multi_pod=args.multi_pod,
                           zero=args.zero, seq_parallel=args.seq_parallel,
                           remat=args.remat, use_pallas=args.use_pallas,
                           moe_impl=args.moe_impl,
                           bf16_gather=args.bf16_gather, embed=args.embed,
                           chunk=args.chunk, micro=args.micro,
                           tag=args.tag)
        except Exception as e:   # noqa: BLE001 — report, keep sweeping
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                   "status": "fail", "error": f"{type(e).__name__}: {e}"}
            n_fail += 1
        if rec["status"] == "skip":
            print(f"[dryrun] {arch} x {shape}: SKIP ({rec['reason']})")
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
