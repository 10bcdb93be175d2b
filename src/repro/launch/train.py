"""End-to-end training driver (the paper's workload: DeepSpeed-style DP
training of a ViT / LM on a mesh).

On an accelerator the mesh spans every local device:
    PYTHONPATH=src python -m repro.launch.train --arch vit-b16 \
        --steps 50 --batch 64

On CPU (tests, rehearsals):
    PYTHONPATH=src python -m repro.launch.train --arch vit-b16 --smoke \
        --steps 50 --batch 32 --accum 2 --devices 8

--devices N is CPU-only: it puts the run on the CPU backend with N host
devices, so the dp axis is real (the paper's "N GPUs") in the scaling
benchmarks and multi-device integration tests. Compiled programs persist
in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else (accelerator runs)
in ``.jax_cache/`` at the checkout root. ``main(argv)`` also serves
in-process callers (chip_smoke.py): it returns a :class:`TrainRun`.

--pp N enables 1F1B pipeline parallelism (core/pipeline.py): the layer
stack splits into N contiguous stages over a `pipe` mesh axis carved out of
the device grid (devices = dp x pp x model-axis), with gradient-accumulation
microbatches fed through the pipe — so --accum must be >= N (the 1F1B
fill/drain invariant). The staged executor runs each stage chunk under a
manual per-chunk VJP, keeping only O(pp) microbatch residual sets live at
once (memory flat in --accum, unlike GPipe-style AD-through-schedule).
--pp-interleave v places v virtual stage-chunks per device (Megatron
interleaved 1F1B), shrinking the pipeline bubble from (S-1)/(M+S-1) to
(S-1)/(v*M+S-1) at the cost of v-1 extra inter-device hops per microbatch;
it needs --accum divisible by --pp and num_layers divisible by pp*v.
--pp composes with --zero (stage-local shards), --augment (per-microbatch
rng streams thread through the schedule), and cast_params_bf16 (fp32 grad
accumulation per chunk), but not with --seq-parallel.

--seed seeds both parameter init and the EngineConfig so distributed
layouts are loss-trajectory comparable run-to-run.

Checkpointing & resume (elastic, shard-local — repro.checkpoint, format
``repro-elastic-ckpt/v2``): the loop trains a single ``TrainState`` pytree
(params, opt state, step, data cursor, rng). ``--ckpt-dir D
--ckpt-every N`` saves the full state every N steps via the async
double-buffered saver (off the step critical path; ``--ckpt-sync`` forces
blocking saves) and once more at exit. On multi-host meshes every process
stages its own shards + per-process manifest and process 0 merges and
commits once (the merge-barrier protocol). ``--resume`` restores the
latest state from ``--ckpt-dir`` — into THIS run's dp×pp×ZeRO layout,
whatever layout wrote it, reading only the shards that overlap this
host's partition (lazy shard-overlap restore) — and continues the exact
loss trajectory: same schedule position (state.step), same optimizer
moments, and the same data stream from the saved ``(epoch, batch_index)``
cursor. Keep --steps/--batch/--accum/--seed identical across save and
resume; the layout flags (--devices/--zero/--pp/--model-axis) may change
freely. ``--stop-after K`` ends the loop at step K while the LR schedule
stays built for --steps — the "preempted run" half of the resume-parity CI
check:

    train --steps 6 --stop-after 3 --ckpt-dir D          # preempted
    train --steps 6 --resume --ckpt-dir D                # same trajectory

Real-image workload (the paper's actual experiments): for vit archs,
``--dataset cifar10|cifar100`` feeds the CIFAR source (data/datasets.py) —
the real binary batches when ``--data-dir`` holds them, a deterministic
procedural CIFAR-like stream otherwise (CI never downloads). ``--augment``
turns on the on-device RandomCrop+Flip+Mixup/CutMix recipe inside the
jitted step (rng-threaded from the TrainState, so resumed runs replay the
exact augmentation stream); ``--label-smoothing`` smooths the train CE.
``--eval-every N`` runs the sharded eval loop over the held-out split
every N steps and at exit: integer top-1/top-5 correct counts (exactly
layout-invariant) + NLL, mask-padded over the non-divisible final batch,
appended to the metrics history as eval_* rows.

Fault tolerance (repro.resilience): ``--supervise`` wraps the whole run in
an auto-resume supervisor — the training command runs as a child process
(fresh JAX runtime per attempt) that is relaunched with ``--resume`` from
the newest valid checkpoint after a restartable failure (preemption exit,
crash), up to ``--max-restarts`` times with jittered exponential backoff.
SIGTERM/SIGINT trigger a one-shot emergency checkpoint and a restartable
exit (code 75). The in-jit anomaly guard (on by default; ``--no-guard``
disables) skips any optimizer update whose loss or global grad-norm is
non-finite — params/opt/step stay bitwise unchanged, the SAME cursor
batch is retried, and the run aborts after ``--guard-max-skips``
consecutive skips. ``--keep-last K`` turns on retention GC (never deletes
the newest checkpoint that passes checksum verification).
``--inject-faults "nan_grad@3,ckpt_write@4:transient:2,preempt@rand"``
(or ``seeded``) installs a deterministic chaos schedule — see
resilience/faults.py; fired faults land in ``<ckpt-dir>/faults.jsonl`` so
a supervised relaunch doesn't replay them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, NamedTuple, Optional

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_host_devices(n: int):
    """CPU only: put this process on the CPU backend with ``n`` host
    devices (``--devices N``). Must run before the process touches a
    backend; accelerator runs never call it and span every local
    device."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def use_compile_cache():
    """Persist compiled programs across processes. An outside
    ``JAX_COMPILATION_CACHE_DIR`` is honoured as is (JAX reads it).
    Otherwise an accelerator run caches at a fixed path in the checkout,
    because the path is part of the cache key; a CPU run does not cache,
    since its compiles are cheap and XLA:CPU warns on every cached load."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and \
            jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_ROOT / ".jax_cache"))


class TrainRun(NamedTuple):
    """What :func:`main` hands an in-process caller."""
    history: list               # logged metric rows (train, then eval_*)
    state: Any                  # final TrainState
    mesh: Any
    step_fn: Any                # the jitted train step the loop ran
    last_batch: Any             # its last batch (None when no step ran)

    def compiled_step_text(self) -> str:
        """Optimized HLO of the train step for the shapes it ran with."""
        with self.mesh:
            return self.step_fn.lower(self.state, self.last_batch) \
                .compile().as_text()


def main(argv=None, *, devices: Optional[list] = None) -> TrainRun:
    """Parse ``argv`` (default ``sys.argv[1:]``) and train. ``devices``
    restricts the mesh to those devices (default: all local ones)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vit-b16")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--zero", type=int, default=0)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU only: run on N host devices (0 = every "
                         "local device of the default backend)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (1F1B over the `pipe` mesh axis; "
                         "requires --accum >= --pp)")
    ap.add_argument("--pp-interleave", type=int, default=1,
                    help="virtual stage-chunks per pipeline device "
                         "(Megatron interleaved 1F1B; v>1 shrinks the "
                         "bubble to (S-1)/(v*M+S-1) and requires "
                         "--accum %% --pp == 0 and num_layers %% "
                         "(pp*v) == 0)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dataset", default="cifar10",
                    choices=["cifar10", "cifar100", "synthetic"],
                    help="vit data source: real/procedural CIFAR "
                         "(data/datasets.py) or the legacy synthetic "
                         "tensor stream")
    ap.add_argument("--data-dir", default="",
                    help="directory holding the CIFAR binary batches "
                         "(cifar-10-batches-py / cifar-100-python); unset "
                         "or absent -> deterministic procedural CIFAR "
                         "(no downloads, CI-safe)")
    ap.add_argument("--shard-dir", default="",
                    help="stream from a repro-shards/v1 shard directory "
                         "(data/streaming.py; write one with `python -m "
                         "repro.data.streaming --out DIR`) instead of an "
                         "in-RAM split — overrides --dataset/--data-dir")
    ap.add_argument("--train-size", type=int, default=0,
                    help="truncate/bound the train split to N examples "
                         "(0 = full split; bounds disk + shard splits and "
                         "sizes the procedural stream's epoch)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="batches in flight at EACH prefetch stage "
                         "(synthesis and host->device transfer run in "
                         "separate threads)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate on the held-out split every N steps "
                         "and at the end (0 = no eval; needs a real "
                         "dataset, i.e. --dataset != synthetic)")
    ap.add_argument("--eval-batch", type=int, default=0,
                    help="eval batch size (0 -> --batch); the final "
                         "non-divisible batch is mask-padded")
    ap.add_argument("--eval-size", type=int, default=0,
                    help="truncate the eval split to N examples "
                         "(0 = full split; procedural default "
                         f"is small already)")
    ap.add_argument("--augment", action="store_true",
                    help="on-device RandomCrop+Flip+Mixup/CutMix inside "
                         "the jitted step (vit only, rng-threaded from "
                         "the TrainState so resumes replay the stream)")
    ap.add_argument("--label-smoothing", type=float, default=0.0)
    ap.add_argument("--seq-parallel", default="none")
    ap.add_argument("--use-pallas", action="store_true",
                    help="flash-attention Pallas kernels (custom-VJP train "
                         "path; interpret mode off-TPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="override cfg.num_layers (0 = config default; "
                         "pipeline layouts need num_layers %% (pp * "
                         "pp-interleave) == 0)")
    ap.add_argument("--dtype", default="",
                    help="override compute dtype (e.g. float32 for the "
                         "cross-layout resume-parity checks, where bf16 "
                         "rounding would mask the comparison)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the full TrainState every N steps "
                         "(0 = end-of-run only); async unless --ckpt-sync")
    ap.add_argument("--ckpt-sync", action="store_true",
                    help="blocking saves (debug / bench baseline)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint in --ckpt-dir into "
                         "this run's layout and continue the trajectory")
    ap.add_argument("--resume-step", type=int, default=-1,
                    help="restore this specific step instead of the latest")
    ap.add_argument("--stop-after", type=int, default=0,
                    help="stop at this absolute step while the LR schedule "
                         "keeps --steps as its horizon (preemption "
                         "simulation for resume tests)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="synchronous host data path (bench baseline)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    # --- resilience ---------------------------------------------------
    ap.add_argument("--supervise", action="store_true",
                    help="run under the auto-resume supervisor: child "
                         "process per attempt, relaunched with --resume "
                         "from the newest valid checkpoint after "
                         "restartable failures")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="supervisor restart budget")
    ap.add_argument("--inject-faults", default="",
                    help="chaos schedule: 'kind@step[:mode[:count]],...' "
                         "(kinds: nan_grad ckpt_write ckpt_corrupt data "
                         "preempt; '@rand' draws a seeded step) or "
                         "'seeded' for the default seed-derived schedule")
    ap.add_argument("--keep-last", type=int, default=0,
                    help="checkpoint retention: keep the newest K "
                         "(0 = keep all); never deletes the newest "
                         "checkpoint that passes verification")
    ap.add_argument("--no-guard", action="store_true",
                    help="disable the in-jit anomaly guard (non-finite "
                         "loss/grad-norm then corrupts the params)")
    ap.add_argument("--guard-max-skips", type=int, default=3,
                    help="abort after this many consecutive guard-skipped "
                         "updates of the same batch")
    argv = sys.argv[1:] if argv is None else list(argv)
    args = ap.parse_args(argv)

    if args.supervise:
        # the supervisor only starts children and never imports jax: the
        # devices belong to the child
        from repro.resilience.supervisor import child_argv, supervise
        raise SystemExit(supervise(child_argv(argv),
                                   max_restarts=args.max_restarts,
                                   seed=args.seed))
    if args.devices:
        use_host_devices(args.devices)
    use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import latest_step, save_checkpoint
    from repro.configs import EngineConfig, get_config, get_smoke_config
    from repro.core import sharding as shd
    from repro.core.engine import DistributedEngine
    from repro.data import AugmentConfig, DATASETS, DataPipeline, make_source
    from repro.launch.mesh import make_local_mesh
    from repro.resilience import FaultPlan, RESTARTABLE_EXIT
    from repro.resilience import faults as _faults
    from repro.resilience.supervisor import install_preemption_handler

    if args.inject_faults:
        fault_log = os.path.join(args.ckpt_dir, "faults.jsonl") \
            if args.ckpt_dir else None
        if args.inject_faults == "seeded":
            plan = FaultPlan.seeded(args.seed, max_step=args.steps,
                                    log_path=fault_log)
        else:
            plan = FaultPlan.parse(args.inject_faults, seed=args.seed,
                                   max_step=args.steps, log_path=fault_log)
        plan.install()
        print(f"[faults] installed {plan!r}"
              + (f" log={fault_log}" if fault_log else ""))

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.use_pallas:
        cfg = cfg.replace(use_pallas=True)
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    # the data source is built BEFORE the engine: a uint8-shipping source
    # hands the engine its Preproc (the on-device normalize/upsample) and
    # its spec names the class count
    source = None
    if cfg.arch_type == "vit" and \
            (args.shard_dir or args.dataset != "synthetic"):
        source = make_source(args.dataset, data_dir=args.data_dir or None,
                             seed=args.seed, resolution=cfg.image_size,
                             train_size=args.train_size or None,
                             eval_size=args.eval_size or None,
                             shard_dir=args.shard_dir or None)
    if cfg.arch_type == "vit":
        spec = source.spec if source is not None else DATASETS["cifar10"]
        cfg = cfg.replace(num_classes=spec.num_classes,
                          label_smoothing=args.label_smoothing)
    mesh = make_local_mesh(model=args.model_axis, pipe=args.pp,
                           devices=devices)
    dp = mesh.devices.shape[0]
    ecfg = EngineConfig(
        train_batch_size=args.batch,
        gradient_accumulation_steps=args.accum,
        zero_stage=args.zero, optimizer=args.optimizer, lr=args.lr,
        total_steps=args.steps, warmup_steps=max(1, args.steps // 10),
        sequence_parallel=args.seq_parallel, pipeline_stages=args.pp,
        pipeline_interleave=args.pp_interleave,
        seed=args.seed, ckpt_every=args.ckpt_every,
        ckpt_async=not args.ckpt_sync, ckpt_keep_last=args.keep_last,
        guard_anomalies=not args.no_guard,
        guard_max_skips=args.guard_max_skips)
    aug = AugmentConfig(num_classes=cfg.num_classes) \
        if args.augment and cfg.arch_type == "vit" else None
    eng = DistributedEngine(
        cfg, ecfg, mesh, aug=aug,
        preproc=source.preproc if source is not None else None)
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={mesh.devices.size}x{mesh.devices.flat[0].device_kind!r} "
          f"dp={dp} pp={args.pp} "
          f"micro_batch={ecfg.derived_micro_batch(dp)} accum={args.accum} "
          f"zero={args.zero} opt={args.optimizer} "
          f"aug={'on' if aug else 'off'}")

    if cfg.arch_type == "vit":
        if source is not None:
            # real CIFAR from --data-dir when present, a shard stream
            # under --shard-dir, else the deterministic procedural
            # generator — all behind the same cursor contract, all uint8
            # on the host (normalize/upsample run inside the jitted step)
            backing = "shards" if args.shard_dir else \
                "procedural" if source.procedural else "disk"
            print(f"[train] dataset={source.name} {backing} "
                  f"train={source.train_size} eval={source.eval_size}")
            pipe = DataPipeline(kind="image", global_batch=args.batch,
                                source=source, seed=args.seed)
        else:
            pipe = DataPipeline(kind="image", global_batch=args.batch,
                                dataset=DATASETS["cifar10"],
                                resolution=cfg.image_size, seed=args.seed)
    else:
        pipe = DataPipeline(kind="token", global_batch=args.batch,
                            vocab=max(cfg.vocab_size, 2), seq_len=args.seq,
                            epoch_size=args.batch * args.steps,
                            seed=args.seed)
    if args.eval_every and source is None:
        raise SystemExit("[train] --eval-every needs a real dataset "
                         "(--dataset cifar10|cifar100 on a vit arch)")

    state = None
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) >= 0:
        try:
            state = eng.restore_state(
                args.ckpt_dir,
                step=args.resume_step if args.resume_step >= 0 else None)
            print(f"[train] resumed step={int(state.step)} "
                  f"cursor=(epoch {int(state.epoch)}, "
                  f"batch {int(state.batch_index)}) from {args.ckpt_dir}")
        except FileNotFoundError as e:
            # every on-disk step failed checksum verification — a fresh
            # start beats refusing to train (latest-valid fallback for
            # merely-newest-corrupt already happened inside restore_state)
            print(f"[train] --resume: no checkpoint survives "
                  f"verification ({e}); starting fresh")
    if state is None:
        if args.resume and \
                (not args.ckpt_dir or latest_step(args.ckpt_dir) < 0):
            print(f"[train] --resume: no checkpoint in "
                  f"{args.ckpt_dir or '<unset>'}; starting fresh")
        state = eng.init_state(seed=args.seed)
    start_step = int(state.step)
    end_step = min(args.steps, args.stop_after) if args.stop_after \
        else args.steps

    step_fn = eng.jit_train_step()
    saver = eng.make_checkpointer() if ecfg.ckpt_async else None
    preempted = install_preemption_handler()
    hist = []
    t0 = time.time()

    eval_batch = args.eval_batch or args.batch
    eval_fn = eng.jit_eval_step() if args.eval_every else None
    last_eval_step = -1

    def run_eval(state, at_step):
        """Sharded eval over the held-out split; metrics land in history
        (exact integer counts + rates — the layout-invariant signal)."""
        nonlocal last_eval_step
        em = eng.evaluate(state, source.eval_batches(eval_batch),
                          eval_step=eval_fn)
        em["step"] = at_step
        em["wall_s"] = round(time.time() - t0, 2)
        hist.append(em)
        last_eval_step = at_step
        print(f"[eval ] step {at_step:5d} "
              f"top1={em['eval_acc']:.4f} top5={em['eval_top5_acc']:.4f} "
              f"loss={em['eval_loss']:.4f} "
              f"({em['eval_top1_count']}/{em['eval_count']})")

    # cursor-addressable data: vit/token archs ride the background
    # prefetcher; audio/vlm use spec-derived synthetic batches addressed
    # directly by the global step (epoch stays 0 — one endless "epoch")
    cursor_data = cfg.arch_type not in ("audio", "vlm")
    prefetcher = None
    if cursor_data and not args.no_prefetch and start_step < end_step:
        bshard = shd.named(mesh, shd.batch_specs(cfg, pipe.batch_shapes(),
                                                 mesh))
        prefetcher = pipe.prefetch(int(state.epoch), int(state.batch_index),
                                   shardings=bshard,
                                   depth=args.prefetch_depth)

    def fetch(step):
        """-> (batch, cursor-after-this-step)"""
        if not cursor_data:
            from repro.launch.specs import concrete_batch
            batch = concrete_batch(cfg, args.batch, args.seq, seed=step)
            return jax.tree.map(jnp.asarray, batch), (0, step + 1)
        if prefetcher is not None:
            _, batch, nxt = next(prefetcher)
            return batch, nxt
        e, i = int(state.epoch), int(state.batch_index)
        batch = pipe.device_put(pipe.batch_at(e, i))
        return batch, pipe.next_cursor(e, i)

    batch = None
    try:
        with mesh:
            for step in range(start_step, end_step):
                t_step = time.perf_counter()
                batch, nxt = fetch(step)
                # anomaly-guarded step: a non-finite loss/grad-norm makes
                # the jitted step a bitwise no-op (step_ok=0) — retry the
                # SAME cursor batch (state.step didn't advance, so the
                # fold_in rng stream is identical) and escalate after
                # guard_max_skips consecutive skips. Fault poisoning is
                # once-only, so the retry sees the clean batch — the loss
                # trajectory exactly matches an uninterrupted run.
                skips = 0
                while True:
                    fed = _faults.poison_batch(batch, step,
                                               resolution=cfg.image_size)
                    state, metrics = step_fn(state, fed)
                    if not ecfg.guard_anomalies or \
                            bool(np.asarray(metrics["step_ok"])):
                        break
                    skips += 1
                    print(f"[guard] step {step}: non-finite loss/grad-"
                          f"norm — update skipped "
                          f"({skips}/{ecfg.guard_max_skips})", flush=True)
                    if skips >= ecfg.guard_max_skips:
                        raise RuntimeError(
                            f"anomaly guard: {skips} consecutive skipped "
                            f"updates at step {step}; aborting "
                            f"(persistent data/numerics problem)")
                # roll the data cursor on the host — the jitted step passes
                # it through; a checkpoint taken now names the NEXT batch
                state = state.replace(epoch=jnp.int32(nxt[0]),
                                      batch_index=jnp.int32(nxt[1]))
                if step % args.log_every == 0 or step == end_step - 1:
                    jax.block_until_ready(state)
                    step_s = time.perf_counter() - t_step
                    m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    m["step"] = step
                    # wall time of this step, input wait included
                    m["step_s"] = step_s
                    m["guard_skips"] = skips
                    m["wall_s"] = round(time.time() - t0, 2)
                    hist.append(m)
                    print(f"[train] step {step:5d} loss={m['loss']:.4f} "
                          f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                          f"({m['wall_s']:.1f}s)")
                if args.ckpt_dir and ecfg.ckpt_every and \
                        (step + 1) % ecfg.ckpt_every == 0:
                    if saver is not None:
                        saver.save(args.ckpt_dir, step + 1, state)
                    else:
                        save_checkpoint(args.ckpt_dir, step + 1, state)
                if args.eval_every and (step + 1) % args.eval_every == 0:
                    run_eval(state, step + 1)
                # planned preemption fires here (SIGTERM to self); real
                # SIGTERM/SIGINT land in the same flag via the handler
                _faults.preempt_due(step)
                if preempted.triggered:
                    if saver is not None:
                        saver.wait()    # drain before the emergency save
                    if args.ckpt_dir:
                        path = save_checkpoint(args.ckpt_dir,
                                               int(np.asarray(state.step)),
                                               state)
                        print(f"[train] preempted (signal "
                              f"{preempted.signum}) — emergency "
                              f"checkpoint -> {path}", flush=True)
                    # EX_TEMPFAIL: the supervisor relaunches with --resume
                    raise SystemExit(RESTARTABLE_EXIT)
    finally:
        if prefetcher is not None:
            prefetcher.close()

    if args.eval_every and int(state.step) != last_eval_step:
        run_eval(state, int(state.step))    # final-state eval
    if saver is not None:
        saver.wait()                    # drain in-flight async saves
    if args.ckpt_dir and latest_step(args.ckpt_dir) != int(state.step):
        path = save_checkpoint(args.ckpt_dir, int(state.step), state)
        print(f"[train] checkpoint -> {path}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(hist, f, indent=1)
    # final sanity: loss decreased (train rows only; eval rows carry
    # eval_* keys instead)
    tr = [h for h in hist if "loss" in h]
    if len(tr) >= 2 and not (tr[-1]["loss"] < tr[0]["loss"]):
        print("[train] WARNING: loss did not decrease")
    final = f"final loss {tr[-1]['loss']:.4f}" if tr \
        else f"no steps run (start={start_step}, end={end_step})"
    print(f"[train] done in {time.time()-t0:.1f}s; {final}")
    return TrainRun(hist, state, mesh, step_fn, batch)


if __name__ == "__main__":
    main()
