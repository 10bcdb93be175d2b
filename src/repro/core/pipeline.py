"""Pipeline parallelism (DeepSpeed PipelineEngine equivalent) on a `pipe`
mesh axis — memory-bounded 1F1B with interleaved virtual stages.

Two coupled pieces:

1. **Schedule** (`one_f_one_b`, `bubble_count`, `idle_slots`): an explicit
   1F1B (one-forward-one-back) microbatch schedule, simulated per device
   with unit F/B slots. ``interleave=v`` extends it to Megatron-style
   interleaved virtual stages: the layer stack is cut into ``V = v*S``
   chunks and chunk ``c`` lives on device ``c % S``, so each device owns
   ``v`` depth-separated chunks and the warmup ramp is paid in 1/v-depth
   chunk units — the per-device bubble fraction shrinks from
   ``(S-1)/(M+S-1)`` toward ``(S-1)/(v*M+S-1)``
   (`simulated_bubble_fraction`). The simulator is the scheduling and
   accounting source of truth: `pipelined_value_and_grad` walks its slot
   list verbatim and reports the slots it executed, which
   tests/test_pipeline.py asserts equal to the simulator's counts.

2. **Execution** (`pipelined_value_and_grad`, `pipelined_loss`): the
   transformer block stack is partitioned into contiguous per-chunk layer
   ranges (embed pinned to chunk 0, head/loss to chunk V-1) and the
   schedule is executed tick by tick as an unrolled loop. The device
   dimension stays *vectorized* (leading S axis on activations and
   chunk-local params) and sharded over the ``pipe`` mesh axis, so GSPMD
   partitions each tick's chunk computation across pipe devices and lowers
   the inter-chunk activation/cotangent handoff — a shift of the device
   axis — to ``collective-permute`` (verified in the lowered HLO by
   tests/test_pipeline.py).

   **Memory model (the point of this formulation).** Each forward slot
   runs the chunk forward and keeps exactly one residual set per in-flight
   microbatch: the chunk's *input* activation. The backward slot for that
   (chunk, microbatch) re-runs the chunk forward under ``jax.vjp`` from
   the stored input (rematerialization) and applies the pullback, after
   which the residual is dead — the unrolled graph hands XLA's buffer
   liveness exactly the 1F1B lifetime, so peak activation memory is
   O(in-flight) = O(S) per device instead of the O(M) the previous
   AD-through-``lax.scan`` formulation paid (scan saved every tick's
   carry for the transposed replay, giving the 1F1B schedule with GPipe
   memory). `benchmarks/scaling_bench.py` measures this as the
   ``pp_peak_mem_M{4,8,16}`` rows: peak temp memory at fixed S is flat in
   M. Interleaving trades some of it back: v chunks per device hold up to
   ``S`` in-flight inputs *each* (the per-virtual-stage 1F1B cap), so
   interleaved peak memory is O(v*S) chunk inputs per device — still flat
   in M.

   Parameter gradients are accumulated across backward slots in fp32
   (each pullback cotangent is cast to f32 before the ``+= ct/M``), which
   is what makes ``cast_params_bf16`` legal under pp>1: the bf16 compute
   view flows through the chunk/head/embed VJPs while the accumulator —
   like ``accumulate_gradients``'s — stays f32. Per-microbatch PRNG keys
   (``rngs``) thread through ``microbatch_fn`` at every point a microbatch
   is materialized (stage-0 inject, head loss, embed backward), so
   on-device augmentation keyed by ``fold_in(state.rng, step)`` is
   resume-exact under pp, matching the dp path.

   Why not ``shard_map`` + ``jax.lax.ppermute``: manual collectives on a
   manual-subgroup axis combined with ``auto`` (GSPMD) axes hit an
   unimplemented path in the jaxlib 0.4.37 SPMD partitioner ("PartitionId
   instruction is not supported" / IsManualSubgroup check failure). That
   reason is unverified on jax 0.9.0, the version the repo now runs. The
   vectorized-device formulation produces the identical collective-permute
   schedule while keeping ZeRO / tensor-parallel sharding on the remaining
   axes fully composable; grads of chunk-local params stay pipe-sharded
   and reduce-scatter over dp exactly as in the non-pipelined path.

Engine knobs: ``EngineConfig.pipeline_stages`` (=S, the pipe-axis extent)
and ``EngineConfig.pipeline_interleave`` (=v, virtual chunks per device;
``launch/train.py --pp-interleave``). Interleaving requires
``num_layers % (S*v) == 0`` and ``num_micro % S == 0`` (the Megatron
grouping constraint).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.grad_accum import split_microbatches
from repro.models import transformer as model

PIPE_AXIS = "pipe"


# ---------------------------------------------------------------------------
# stage partitioning
# ---------------------------------------------------------------------------

def stage_partition(num_layers: int, stages: int) -> List[tuple]:
    """Contiguous [lo, hi) layer ranges per (virtual) stage; embed is pinned
    to chunk 0 and the head to the last chunk by construction."""
    if stages < 1:
        raise ValueError(f"stages must be >= 1, got {stages}")
    if num_layers % stages:
        raise ValueError(
            f"num_layers={num_layers} not divisible by pipeline "
            f"stages={stages}")
    lps = num_layers // stages
    return [(s * lps, (s + 1) * lps) for s in range(stages)]


def check_supported(cfg) -> None:
    """Pipeline path covers the scan-stacked attn/mla block stack (the
    paper's ViT + dense LMs). Branching stacks need per-stage routing."""
    if cfg.block_kind not in ("attn", "mla"):
        raise ValueError(
            f"pipeline_stages > 1 unsupported for block_kind="
            f"{cfg.block_kind!r} (only attn/mla stacks)")
    if cfg.moe and cfg.moe.num_experts > 0:
        raise ValueError("pipeline_stages > 1 unsupported for MoE stacks "
                         "(dense/moe split breaks contiguous staging)")
    if cfg.mtp_depth > 0:
        raise ValueError("pipeline_stages > 1 unsupported with MTP heads")
    if cfg.hybrid_group > 0:
        raise ValueError("pipeline_stages > 1 unsupported for hybrid stacks")
    if cfg.rope_style == "mrope" or cfg.arch_type == "vlm":
        # M-RoPE positions are batch-supplied per microbatch; the pipelined
        # loop computes positions once from microbatch 0 (valid only for
        # shape-derived arange/None positions), so vlm would silently train
        # with microbatch-0's position grid
        raise ValueError("pipeline_stages > 1 unsupported for vlm/M-RoPE "
                         "(batch-dependent rope positions)")


# ---------------------------------------------------------------------------
# 1F1B schedule (flat + interleaved)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PipeTask:
    kind: str       # "F" | "B"
    micro: int      # microbatch index
    chunk: int = 0  # virtual stage index in [0, stages * interleave)


def one_f_one_b(num_micro: int, num_stages: int, interleave: int = 1
                ) -> List[List[Optional[PipeTask]]]:
    """Simulate the 1F1B schedule with unit F/B slots.

    Returns ``sched[device][tick] -> PipeTask | None`` (None = bubble).
    ``interleave=1`` is the flat schedule: chunk == stage == device, warmup
    forwards, steady-state F/B alternation, cooldown backwards, per-stage
    in-flight cap ``num_stages - stage`` (DeepSpeed/PipeDream-flush).

    ``interleave=v > 1`` is the Megatron interleaved schedule over
    ``V = v * num_stages`` virtual stages, chunk ``c`` on device ``c % S``:
    each device issues forwards in groups of S microbatches cycling through
    its chunks shallow-to-deep (backwards deep-to-shallow), with warmup
    ``min(2*(S-d-1) + (v-1)*S, v*M)`` and strict 1F1B alternation after —
    falling back to the other slot kind only when the scheduled kind's
    dependency is not yet satisfied. In-flight residuals per device never
    exceed ``warmup_d + 1`` — flat in M (asserted here; the hypothesis
    suite in tests/test_pipeline.py re-checks it property-style, and the
    flat schedule keeps the strict ``<= S - d <= S`` cap).
    """
    S, M, v = num_stages, num_micro, interleave
    if v < 1:
        raise ValueError(f"interleave must be >= 1, got {v}")
    if M < S:
        raise ValueError(
            f"1F1B needs microbatches >= stages: {M} < {S}")
    if v == 1:
        return _flat_one_f_one_b(M, S)
    if M % S:
        raise ValueError(
            f"interleaved 1F1B needs num_micro divisible by stages "
            f"(Megatron grouping): {M} % {S} != 0")
    V = S * v
    total = v * M

    def orders(dev):
        chunks = [k * S + dev for k in range(v)]
        groups = [range(g * S, (g + 1) * S) for g in range(M // S)]
        fwd = [(c, m) for g in groups for c in chunks for m in g]
        bwd = [(c, m) for g in groups for c in reversed(chunks) for m in g]
        return fwd, bwd

    forder, border = zip(*(orders(d) for d in range(S)))
    warmup = [min(2 * (S - d - 1) + (v - 1) * S, total) for d in range(S)]
    fwd_done, bwd_done = {}, {}
    nf, nb = [0] * S, [0] * S
    sched: List[List[Optional[PipeTask]]] = [[] for _ in range(S)]
    t = 0
    while min(nb) < total:
        if t > 8 * (total + V):         # simulator safety net
            raise RuntimeError("interleaved 1F1B schedule did not converge")
        for d in range(S):
            def try_fwd():
                if nf[d] >= total:
                    return None
                c, m = forder[d][nf[d]]
                if c > 0 and not fwd_done.get((c - 1, m), t) < t:
                    return None
                fwd_done[(c, m)] = t
                nf[d] += 1
                # the memory invariant the executor's residual store
                # relies on: per-device in-flight chunk inputs stay under
                # the warmup depth + 1 — flat in M
                assert nf[d] - nb[d] <= warmup[d] + 1, (d, m, t)
                return PipeTask("F", m, c)

            def try_bwd():
                if nb[d] >= total:
                    return None
                c, m = border[d][nb[d]]
                ready = (fwd_done.get((c, m), t) < t if c == V - 1
                         else bwd_done.get((c + 1, m), t) < t)
                if not ready or not fwd_done.get((c, m), t) < t:
                    return None
                bwd_done[(c, m)] = t
                nb[d] += 1
                return PipeTask("B", m, c)

            want_fwd = nf[d] < warmup[d] or (
                nf[d] < total and nf[d] - warmup[d] == nb[d])
            task = (try_fwd() or try_bwd()) if want_fwd \
                else (try_bwd() or try_fwd())
            sched[d].append(task)
        t += 1
    return sched


def _flat_one_f_one_b(M: int, S: int) -> List[List[Optional[PipeTask]]]:
    fwd_done = [[None] * M for _ in range(S)]   # tick stage s forwarded m
    bwd_done = [[None] * M for _ in range(S)]
    nf = [0] * S                                # forwards issued per stage
    nb = [0] * S                                # backwards issued per stage
    sched: List[List[Optional[PipeTask]]] = [[] for _ in range(S)]
    t = 0
    while min(nb) < M:
        if t > 4 * (M + S):                     # simulator safety net
            raise RuntimeError("1F1B schedule did not converge")
        for s in range(S):
            can_fwd = nf[s] < M and (
                s == 0 or fwd_done[s - 1][nf[s]] is not None
                and fwd_done[s - 1][nf[s]] < t)
            can_bwd = nb[s] < nf[s] and (
                s == S - 1 or bwd_done[s + 1][nb[s]] is not None
                and bwd_done[s + 1][nb[s]] < t)
            in_flight = nf[s] - nb[s]
            # the 1F1B memory cap: at most S - s activations live on stage
            # s; past the cap the stage waits for a backward, never piles
            # up more forwards (what distinguishes 1F1B from GPipe)
            if can_bwd and (in_flight >= S - s or nf[s] == M):
                bwd_done[s][nb[s]] = t
                sched[s].append(PipeTask("B", nb[s], s))
                nb[s] += 1
            elif can_fwd and in_flight < S - s:
                fwd_done[s][nf[s]] = t
                sched[s].append(PipeTask("F", nf[s], s))
                nf[s] += 1
            elif can_bwd:
                bwd_done[s][nb[s]] = t
                sched[s].append(PipeTask("B", nb[s], s))
                nb[s] += 1
            else:
                sched[s].append(None)
        t += 1
    return sched


def idle_slots(sched: List[List[Optional[PipeTask]]], dev: int) -> int:
    """Raw idle slot count of ``dev`` over the whole schedule."""
    return sum(1 for task in sched[dev] if task is None)


def bubble_count(sched: List[List[Optional[PipeTask]]], stage: int) -> int:
    """Idle slots of ``stage`` in F+B pair units — ``stages - 1`` for the
    flat 1F1B (the warmup/cooldown ramp each stage pays once)."""
    return idle_slots(sched, stage) // 2


def makespan(sched: List[List[Optional[PipeTask]]]) -> int:
    """Schedule length in unit slots (all device rows are equal length).
    One interleaved slot is 1/interleave of a flat slot — normalize by
    ``interleave`` when comparing across v."""
    return len(sched[0])


def bubble_fraction(num_micro: int, num_stages: int) -> float:
    """Analytic flat-1F1B pipeline-bubble fraction (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_micro + num_stages - 1)


def simulated_bubble_fraction(num_micro: int, num_stages: int,
                              interleave: int = 1) -> float:
    """Worst-device bubble fraction read off the simulated schedule — the
    number `scaling_sweep.py`/`scaling_bench.py` record for interleaved
    layouts. Equals `bubble_fraction` at interleave=1 and approaches
    (S-1)/(v*M+S-1) for the interleaved schedule."""
    sched = one_f_one_b(num_micro, num_stages, interleave)
    return max(idle_slots(sched, d) for d in range(num_stages)) \
        / makespan(sched)


def schedule_accounting(num_micro: int, num_stages: int,
                        interleave: int = 1) -> dict:
    """Per-device slot counts of the simulated schedule — the reference the
    executed-schedule accounting is asserted against."""
    sched = one_f_one_b(num_micro, num_stages, interleave)
    return {
        "ticks": makespan(sched),
        "F": [sum(1 for x in sched[d] if x and x.kind == "F")
              for d in range(num_stages)],
        "B": [sum(1 for x in sched[d] if x and x.kind == "B")
              for d in range(num_stages)],
        "idle": [idle_slots(sched, d) for d in range(num_stages)],
    }


# ---------------------------------------------------------------------------
# staged execution
# ---------------------------------------------------------------------------

def _constrain(x, spec):
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except RuntimeError as e:
        # tolerate ONLY the no-mesh case (single-device semantics tests);
        # anything else (spec/rank mismatch under a live mesh) must surface
        # — silently unconstrained stage params replicate across pipe
        if "mesh" not in str(e).lower():
            raise
        return x


def stage_stack_specs(stack_specs, stages_axis=PIPE_AXIS):
    """(L, ...) stacked-param specs -> (S, v, L/(S*v), ...) chunk-local
    specs.

    The engine's param specs put ``pipe`` on the leading L axis; after the
    device-major reshape the leading axis is the device axis (still pipe)
    and the chunk-round / layers-within-chunk axes are unsharded. Inner
    (fsdp/tp) dims are preserved so ZeRO-3 stays chunk-locally sharded.
    """
    def one(spec):
        parts = tuple(spec)
        lead = parts[0] if parts else None
        if lead not in (stages_axis, None):
            lead = stages_axis
        return P(stages_axis if lead is not None else None, None, None,
                 *parts[1:])
    return jax.tree.map(one, stack_specs,
                        is_leaf=lambda s: isinstance(s, P))


def _device_major(x, S: int, v: int):
    """(L, ...) -> (S, v, L/(S*v), ...): lead axis = device, chunk
    ``c = k*S + d`` lands at [d, k] (Megatron round-robin placement)."""
    lpc = x.shape[0] // (S * v)
    return x.reshape((v, S, lpc) + x.shape[1:]).swapaxes(0, 1)


def _device_major_inverse(x):
    """(S, v, lpc, ...) -> (L, ...), inverse of `_device_major`."""
    S, v, lpc = x.shape[:3]
    return x.swapaxes(0, 1).reshape((S * v * lpc,) + x.shape[3:])


def _staged_pipeline(cfg, params, batch, *, stages, num_micro, interleave,
                     dp_axes, pipe_axis, stack_specs, rngs, microbatch_fn,
                     want_grads, schedule_out=None):
    """Shared schedule-driven executor. ``want_grads=False`` runs forward
    slots only (losses at last-chunk exits); ``want_grads=True`` adds the
    backward slots with rematerialized per-chunk VJPs and returns fp32 mean
    grads alongside (loss, metrics)."""
    check_supported(cfg)
    S, M, v = stages, num_micro, interleave
    V = S * v
    stage_partition(cfg.num_layers, V)          # validates divisibility
    sched = one_f_one_b(M, S, v)                # validates M vs S, M % S

    mbs = split_microbatches(batch, M)          # (M, B/M, ...) leaves
    stack = jax.tree.map(lambda x: _device_major(x, S, v), params["stack"])
    if pipe_axis is not None:
        if stack_specs is None:
            stack_specs = jax.tree.map(
                lambda x: P(pipe_axis, *(None,) * (x.ndim - 1)), stack)
        stack = jax.tree.map(_constrain, stack, stack_specs)
    windows = _device_major(
        jnp.asarray(cfg.layer_windows(), jnp.int32), S, v)

    def micro_batch(m):
        mb = jax.tree.map(lambda x: x[m], mbs)
        if microbatch_fn is not None:
            mb = microbatch_fn(mb, None if rngs is None else rngs[m])
        return mb

    mb0 = micro_batch(0)
    inject0, positions = model.embed(cfg, params, mb0)
    dp = tuple(dp_axes)
    state_spec = None
    if pipe_axis is not None:
        state_spec = P(pipe_axis, dp if dp else None,
                       *(None,) * (inject0.ndim - 1))
    zero_lane = jnp.zeros(inject0.shape, inject0.dtype)

    def chunk_fn(chunk_stack, chunk_windows, h):
        return model.stack_forward(cfg, chunk_stack, h, positions,
                                   chunk_windows)

    # the model's forward, scoped inside the functions jax.vjp
    # differentiates, so the backward slots read transpose(jvp(forward))
    # as the dp route's backward does (repro/obs.py)
    def stage_forward(sk, win, x):
        with jax.named_scope(obs.FORWARD):
            return jax.vmap(chunk_fn)(sk, win, x)

    def embed(p, mb):
        with jax.named_scope(obs.FORWARD):
            return model.embed(cfg, p, mb)[0]

    def head_loss(p, h, mb):
        with jax.named_scope(obs.FORWARD):
            logits = model.apply_head(cfg, p, h)
            return model.loss_from_logits(cfg, logits, mb)

    def select_chunks(tasks):
        """Per-device chunk-round selection for one pass. Uniform rounds
        (always true for v=1) keep a plain slice; mixed rounds gather."""
        rounds = [0 if task is None else task.chunk // S for task in tasks]
        if len(set(rounds)) == 1:
            sel = jax.tree.map(lambda p: p[:, rounds[0]], stack)
            win = windows[:, rounds[0]]
        else:
            ar, ridx = jnp.arange(S), jnp.asarray(rounds)
            sel = jax.tree.map(lambda p: p[ar, ridx], stack)
            win = windows[ar, ridx]
        return sel, win, rounds

    def assemble(entries, shift_src_lane, tail_fn, mask_dead=False):
        """Build an (S, B, ...) lane array from per-lane sources.

        ``entries[d]``: None (dead lane, value irrelevant), a jnp array
        (fresh value, e.g. the embed inject or the head cotangent), or
        ``(arr, lane)`` referencing a lane of an earlier pass array. When
        every referenced lane follows the neighbor-shift pattern
        (``lane == (d + shift_src_lane) % S`` of one shared array) the
        handoff is emitted as a single axis-shift — the op GSPMD lowers to
        the inter-device collective-permute. ``tail_fn(base)`` supplies
        the slot the shift vacates.

        ``mask_dead`` zeroes the dead lanes after a shift assembly —
        REQUIRED for cotangents: a stalled backward leaves a live
        cotangent in the previous pass array, and the shift would leak it
        into a dead lane whose pullback then pollutes the stack grads.
        (Forward activations skip it: dead-lane outputs are never stored.)
        """
        base, shift_ok = None, True
        for d, e in enumerate(entries):
            if not isinstance(e, tuple):
                continue
            arr, lane = e
            if lane != (d + shift_src_lane) % S:
                shift_ok = False
            if base is None:
                base = arr
            elif base is not arr:
                shift_ok = False
        fresh = [d for d, e in enumerate(entries)
                 if e is not None and not isinstance(e, tuple)]
        edge = 0 if shift_src_lane < 0 else S - 1
        if base is not None and shift_ok and all(d == edge for d in fresh):
            tail = entries[edge][None] if fresh else tail_fn(base)
            if shift_src_lane < 0:      # forward: lane d <- base[d-1]
                out = jnp.concatenate([tail, base[:-1]], 0)
            else:                       # backward: lane d <- base[d+1]
                out = jnp.concatenate([base[1:], tail], 0)
            dead = [d for d, e in enumerate(entries) if e is None]
            if mask_dead and dead:
                live = jnp.asarray(
                    [0.0 if d in dead else 1.0 for d in range(S)],
                    out.dtype).reshape((S,) + (1,) * (out.ndim - 1))
                out = out * live
            return out
        lanes = [zero_lane if e is None else (e[0][e[1]]
                 if isinstance(e, tuple) else e)
                 for e in entries]
        return jnp.stack(lanes, 0)

    inv_m = 1.0 / M
    loss_sum = jnp.float32(0.0)
    metric0 = jax.eval_shape(lambda: head_loss(params, inject0, mb0))[1]
    metric_sum = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), metric0)

    def acc_tree(acc, ct):
        # the fp32 accumulation policy shared with accumulate_gradients:
        # per-microbatch cotangents (possibly bf16 under cast_params_bf16)
        # cast up BEFORE the += ct/M
        return jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) * inv_m, acc, ct)

    gacc = gstack = None
    if want_grads:
        gacc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        gstack = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              stack)

    act = {}    # (chunk, m) -> (pass array, lane): chunk output
    xin = {}    # (chunk, m) -> (pass array, lane): chunk input (residual)
    gst = {}    # (chunk, m) -> (pass array, lane): dL/d(chunk input)
    counts = {"F": [0] * S, "B": [0] * S, "idle": [0] * S}

    for t in range(makespan(sched)):
        ftasks, btasks = [], []
        for d in range(S):
            task = sched[d][t]
            if task is not None:
                assert task.chunk % S == d, (d, task)
                counts[task.kind][d] += 1
            else:
                counts["idle"][d] += 1
            ftasks.append(task if task and task.kind == "F" else None)
            btasks.append(task if task and task.kind == "B" else None)

        if any(t_ is not None for t_ in ftasks):
            entries = []
            for d, task in enumerate(ftasks):
                if task is None:
                    entries.append(None)
                elif task.chunk == 0:   # stage-0 inject (device 0 only)
                    entries.append(
                        embed(params, micro_batch(task.micro)))
                else:
                    entries.append(act.pop((task.chunk - 1, task.micro)))
            x = _constrain(
                assemble(entries, -1, lambda b: b[-1:]), state_spec)
            sel, win, rounds = select_chunks(ftasks)
            y = _constrain(stage_forward(sel, win, x), state_spec)
            for d, task in enumerate(ftasks):
                if task is None:
                    continue
                act[(task.chunk, task.micro)] = (y, d)
                if want_grads:
                    xin[(task.chunk, task.micro)] = (x, d)
                elif task.chunk == V - 1:
                    # forward-only: microbatch exits the pipe here
                    _, lane = act.pop((task.chunk, task.micro))
                    loss_m, metrics_m = head_loss(
                        params, y[lane], micro_batch(task.micro))
                    loss_sum = loss_sum + loss_m
                    metric_sum = jax.tree.map(
                        lambda a, m_: a + m_, metric_sum, metrics_m)

        if want_grads and any(t_ is not None for t_ in btasks):
            xentries, gentries = [], []
            for d, task in enumerate(btasks):
                if task is None:
                    xentries.append(None)
                    gentries.append(None)
                    continue
                c, m = task.chunk, task.micro
                xentries.append(xin.pop((c, m)))
                if c == V - 1:
                    # head + loss VJP seeds the backward wavefront the
                    # slot the microbatch's forward exited (device S-1)
                    yarr, lane = act.pop((c, m))
                    loss_m, head_pb, metrics_m = jax.vjp(
                        lambda p, h, _m=m: head_loss(
                            p, h, micro_batch(_m)),
                        params, yarr[lane], has_aux=True)
                    p_ct, h_ct = head_pb(jnp.float32(1.0))
                    gacc = acc_tree(gacc, p_ct)
                    loss_sum = loss_sum + loss_m
                    metric_sum = jax.tree.map(
                        lambda a, m_: a + m_, metric_sum, metrics_m)
                    gentries.append(h_ct)
                else:
                    gentries.append(gst.pop((c + 1, m)))
            xb = _constrain(
                assemble(xentries, -1, lambda b: b[-1:]), state_spec)
            g = _constrain(
                assemble(gentries, 1, lambda b: b[:1], mask_dead=True),
                state_spec)
            sel, win, rounds = select_chunks(btasks)
            # rematerialized per-chunk VJP: re-run the chunk forward from
            # the stored inputs, pull the output cotangents back — the
            # stored input is the ONLY residual that outlived the forward
            _, chunk_pb = jax.vjp(
                lambda sk, xx: stage_forward(sk, win, xx), sel, xb)
            sel_ct, x_ct = chunk_pb(g)
            if len(set(rounds)) == 1:
                gstack = jax.tree.map(
                    lambda a, g_: a.at[:, rounds[0]].add(
                        g_.astype(jnp.float32) * inv_m), gstack, sel_ct)
            else:
                ar, ridx = jnp.arange(S), jnp.asarray(rounds)
                gstack = jax.tree.map(
                    lambda a, g_: a.at[ar, ridx].add(
                        g_.astype(jnp.float32) * inv_m), gstack, sel_ct)
            for d, task in enumerate(btasks):
                if task is None:
                    continue
                c, m = task.chunk, task.micro
                if c == 0:
                    # cotangent reaches the inject: embed VJP (device 0)
                    _, emb_pb = jax.vjp(
                        lambda p, _m=m: embed(p, micro_batch(_m)), params)
                    (p_ct,) = emb_pb(x_ct[d])
                    gacc = acc_tree(gacc, p_ct)
                else:
                    gst[(c, m)] = (x_ct, d)

    loss = loss_sum * inv_m
    metrics = jax.tree.map(lambda m_: m_ * inv_m, metric_sum)
    metrics["loss"] = loss
    if schedule_out is not None:
        schedule_out.update(schedule=sched, executed=counts,
                            ticks=makespan(sched))
    if not want_grads:
        assert not xin and not gst
        return loss, metrics
    assert not act and not xin and not gst, (act.keys(), xin.keys(),
                                             gst.keys())
    grads = {k: v_ for k, v_ in gacc.items()}
    grads["stack"] = jax.tree.map(
        lambda a, b: a + _device_major_inverse(b), gacc["stack"], gstack)
    return (loss, metrics), grads


def pipelined_loss(cfg, params, batch, *, stages: int, num_micro: int,
                   interleave: int = 1, dp_axes=("data",),
                   pipe_axis: Optional[str] = PIPE_AXIS, stack_specs=None,
                   rngs=None, microbatch_fn=None, schedule_out=None):
    """1F1B-scheduled pipeline-parallel loss: (loss, metrics).

    Forward slots of the simulated schedule only — microbatch losses are
    taken as each microbatch exits the last chunk, so the value matches
    ``pipelined_value_and_grad`` (and the dp path's
    ``accumulate_gradients`` over the same ``split_microbatches``) exactly.

    ``rngs`` is an optional (num_micro, ...) stack of per-microbatch PRNG
    keys handed to ``microbatch_fn(mb, rng)`` wherever a microbatch is
    materialized — the engine threads its augmentation/preprocess closure
    through here. ``pipe_axis=None`` drops sharding constraints
    (semantics-only mode used by single-device tests).

    Checkpoint note: the engine saves the UNRESHAPED ``params["stack"]``
    leaves — the (L, ...) layout with L sharded over ``pipe`` — so the
    elastic checkpoint layer sees plain sharded arrays. The device-major
    (S, v, L/(S*v), ...) view built here is a transient inside the step;
    restores into a different pp extent just re-slice the L axis via the
    target engine's specs, no pipeline-specific resharding logic needed.
    """
    return _staged_pipeline(
        cfg, params, batch, stages=stages, num_micro=num_micro,
        interleave=interleave, dp_axes=dp_axes, pipe_axis=pipe_axis,
        stack_specs=stack_specs, rngs=rngs, microbatch_fn=microbatch_fn,
        want_grads=False, schedule_out=schedule_out)


def pipelined_value_and_grad(cfg, params, batch, *, stages: int,
                             num_micro: int, interleave: int = 1,
                             dp_axes=("data",),
                             pipe_axis: Optional[str] = PIPE_AXIS,
                             stack_specs=None, rngs=None,
                             microbatch_fn=None, schedule_out=None):
    """((loss, metrics), grads) via manually-staged per-chunk VJPs on the
    1F1B schedule — the memory-bounded replacement for
    ``jax.value_and_grad(pipelined_loss)``.

    Numerically interchangeable with ``accumulate_gradients``: grads are
    the fp32 mean of per-microbatch grads (each pullback cotangent is cast
    to f32 before accumulation — the policy that makes
    ``cast_params_bf16`` legal under pp), the loss is the mean of
    per-microbatch losses, and metrics are microbatch means. Peak
    activation memory is O(S) per-chunk input residuals per device
    (O(v*S) interleaved) instead of the old scan path's O(M) — see the
    module docstring's memory model.
    """
    return _staged_pipeline(
        cfg, params, batch, stages=stages, num_micro=num_micro,
        interleave=interleave, dp_axes=dp_axes, pipe_axis=pipe_axis,
        stack_specs=stack_specs, rngs=rngs, microbatch_fn=microbatch_fn,
        want_grads=True, schedule_out=schedule_out)
