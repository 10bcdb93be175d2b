"""DistributedEngine — the DeepSpeed-engine equivalent (the paper's core
artifact) in JAX.

Owns: batch-size invariant (train_batch_size = micro_batch_per_gpu ×
gradient_accumulation_steps × dp_world), gradient accumulation, ZeRO-stage
sharding specs, optimizer, LR schedule, and the pjit'd train / prefill /
decode step functions. ``lower_*`` methods return jax.stages.Lowered for the
multi-pod dry-run and roofline extraction.

Training flows through an explicit :class:`TrainState` pytree — params,
optimizer state, step (also the LR-schedule position), the data-pipeline
cursor ``(epoch, batch_index)`` naming the NEXT batch to consume, and the
base PRNG key — instead of loose ``(params, opt_state)`` tuples. The whole
state is what the elastic checkpoint layer (``repro.checkpoint``) saves and
restores: because every leaf carries its sharding, saves are shard-local
(each process writes only addressable shards) and restores reshard into
whatever dp×pp×ZeRO layout the restoring engine runs
(``DistributedEngine.restore_state``).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import EngineConfig, ModelConfig
from repro.core import pipeline as pipe
from repro.core import sharding as shd
from repro.core import ulysses
from repro.core.grad_accum import _constrain_tree, accumulate_gradients
from repro.models import shardctx
from repro.models import transformer as model
from repro.optim import make_optimizer, make_schedule


@jax.tree_util.register_pytree_with_keys_class
class TrainState:
    """The complete training state, as one pytree.

    Fields:
      params       model parameters (sharded per ZeRO/tp/pp specs)
      opt_state    optimizer state (OptState; ZeRO-sharded)
      step         int32 optimizer step — also the LR-schedule position
      epoch        int32 data-pipeline epoch of the NEXT batch to consume
      batch_index  int32 within-epoch index of the NEXT batch to consume
      rng          base PRNG key; per-step streams derive via
                   ``fold_in(rng, step)`` so a restored state reproduces
                   the exact future randomness without mutating the key

    The cursor convention makes checkpoints resumable mid-epoch: the saved
    ``(epoch, batch_index)`` names the first batch the resumed run feeds.
    ``step``/``epoch``/``batch_index`` duplicate nothing — ``opt_state.step``
    counts optimizer updates (equal to ``step``), while the cursor is owned
    by the host data loop (`launch/train.py`) and passes through the jitted
    step unchanged.
    """
    _fields = ("params", "opt_state", "step", "epoch", "batch_index", "rng")
    __slots__ = _fields

    def __init__(self, *, params, opt_state, step, epoch, batch_index, rng):
        self.params = params
        self.opt_state = opt_state
        self.step = step
        self.epoch = epoch
        self.batch_index = batch_index
        self.rng = rng

    def replace(self, **kw) -> "TrainState":
        vals = {f: getattr(self, f) for f in self._fields}
        bad = set(kw) - set(self._fields)
        if bad:
            raise TypeError(f"unknown TrainState fields: {sorted(bad)}")
        vals.update(kw)
        return TrainState(**vals)

    def tree_flatten_with_keys(self):
        children = [(jax.tree_util.GetAttrKey(f), getattr(self, f))
                    for f in self._fields]
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(**dict(zip(cls._fields, children)))

    def __repr__(self):
        return ("TrainState(" + ", ".join(
            f"{f}={jax.tree_util.tree_structure(getattr(self, f))}"
            for f in self._fields) + ")")


class DistributedEngine:
    def __init__(self, cfg: ModelConfig, ecfg: EngineConfig, mesh,
                 aug=None, preproc=None):
        """``aug``: optional :class:`repro.data.augment.AugmentConfig` —
        on-device train-time augmentation applied per microbatch inside
        the jitted step, keyed by the TrainState rng convention
        (``fold_in(state.rng, state.step)`` split per microbatch), so a
        resumed run replays the interrupted run's augmentation stream.

        ``preproc``: optional :class:`repro.data.datasets.Preproc` — the
        dataset's normalization stats + native grid. Required when the
        data path ships uint8 batches (every dataset source does): the
        jitted step then finishes the batch on device — nearest-neighbor
        upsample to ``cfg.image_size`` and the fused cast-and-normalize
        (``data/augment.device_preprocess``). Pass
        ``preproc=source.preproc``. Float batches (the synthetic tensor
        workload) need none and pass through untouched."""
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        self.aug = aug.validate() if aug is not None else None
        self.preproc = preproc
        if preproc is not None:
            if cfg.arch_type != "vit":
                raise ValueError(
                    f"image preprocessing only applies to vit archs, not "
                    f"{cfg.arch_type!r}")
            if cfg.image_size % preproc.native_resolution:
                raise ValueError(
                    f"cfg.image_size {cfg.image_size} not an integer "
                    f"multiple of the dataset's native "
                    f"{preproc.native_resolution}px grid — the on-device "
                    f"upsample is nearest-neighbor by integer factors")
        if self.aug is not None and cfg.arch_type != "vit":
            raise ValueError(
                f"image augmentation only applies to vit archs, not "
                f"{cfg.arch_type!r}")
        self.dp_world = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                self.dp_world *= mesh.devices.shape[
                    mesh.axis_names.index(a)]
        ecfg.validate(self.dp_world)
        if ecfg.pipeline_stages > 1:
            pipe.check_supported(cfg)
            # interleaved 1F1B places v chunks per device, so the stack
            # must split into S*v equal contiguous chunks
            pipe.stage_partition(
                cfg.num_layers,
                ecfg.pipeline_stages * ecfg.pipeline_interleave)
            ext = dict(zip(mesh.axis_names, mesh.devices.shape))
            if ext.get(pipe.PIPE_AXIS, 1) != ecfg.pipeline_stages:
                raise ValueError(
                    f"pipeline_stages={ecfg.pipeline_stages} needs a "
                    f"'{pipe.PIPE_AXIS}' mesh axis of that extent; mesh has "
                    f"{dict(ext)}")
        self.optimizer = make_optimizer(
            ecfg.optimizer, weight_decay=ecfg.weight_decay,
            grad_clip=ecfg.grad_clip)
        self.schedule = make_schedule(ecfg.lr_schedule, ecfg.lr,
                                      ecfg.warmup_steps, ecfg.total_steps)
        self.hints = ulysses.make_hints(
            mesh, cfg, sequence_parallel=ecfg.sequence_parallel,
            expert_parallel=ecfg.expert_parallel)

    # ------------------------------------------------------------------
    # sharding specs
    # ------------------------------------------------------------------

    def _pspecs(self, shapes, for_opt_state=False):
        return shd.param_specs(
            shapes, zero_stage=self.ecfg.zero_stage,
            tensor_parallel=self.ecfg.tensor_parallel, mesh=self.mesh,
            dp_axes=shd.dp_axes_of(self.mesh), for_opt_state=for_opt_state,
            embed_sharding=self.ecfg.embed_sharding,
            pipeline_axis=pipe.PIPE_AXIS
            if self.ecfg.pipeline_stages > 1 else None)

    def param_shardings(self, param_shapes):
        return shd.named(self.mesh, self._pspecs(param_shapes))

    def opt_shardings(self, param_shapes):
        from repro.optim.optimizers import OptState
        pspec = self._pspecs(param_shapes, for_opt_state=True)
        mu = shd.named(self.mesh, pspec)
        nu = () if self.ecfg.optimizer == "sgd" else mu
        return OptState(step=NamedSharding(self.mesh, P()), mu=mu, nu=nu)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def init_abstract(self):
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params = jax.eval_shape(lambda k: model.init_params(self.cfg, k), key)
        opt = jax.eval_shape(self.optimizer.init, params)
        return params, opt

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct pytree of the full TrainState (the restore
        template: logical shapes + dtypes, values ignored)."""
        params, opt = self.init_abstract()
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        return TrainState(params=params, opt_state=opt, step=scalar,
                          epoch=scalar, batch_index=scalar,
                          rng=jax.ShapeDtypeStruct((2,), jnp.uint32))

    def state_shardings(self) -> TrainState:
        """NamedSharding pytree for the TrainState under THIS engine's
        layout — the resharding target for elastic restore."""
        pshapes = self.init_abstract()[0]
        rep = NamedSharding(self.mesh, P())
        return TrainState(params=self.param_shardings(pshapes),
                          opt_state=self.opt_shardings(pshapes),
                          step=rep, epoch=rep, batch_index=rep, rng=rep)

    def init_state(self, seed: int = 0) -> TrainState:
        """Sharded init of the full training state on the mesh."""
        sshard = self.state_shardings()

        @functools.partial(jax.jit, out_shardings=sshard)
        def _init(key):
            params = model.init_params(self.cfg, key)
            zero = jnp.int32(0)
            return TrainState(
                params=params, opt_state=self.optimizer.init(params),
                step=zero, epoch=zero, batch_index=zero,
                # distinct stream from the init key so future stochastic
                # regularizers never correlate with the init draw
                rng=jax.random.fold_in(key, 1))

        with self.mesh:
            return _init(jax.random.PRNGKey(seed))

    # ------------------------------------------------------------------
    # checkpointing (elastic, shard-local — repro.checkpoint)
    # ------------------------------------------------------------------

    def save_state(self, ckpt_dir: str, state: TrainState) -> str:
        """Synchronous shard-local save of the full state; the directory
        name is taken from ``state.step``."""
        from repro.checkpoint import save_checkpoint
        return save_checkpoint(ckpt_dir, int(jax.device_get(state.step)),
                               state)

    def restore_state(self, ckpt_dir: str, step: Optional[int] = None
                      ) -> TrainState:
        """Elastic restore: reassemble logical arrays from the shard index
        maps and reshard into THIS engine's layout — the source run may
        have used any dp×pp×ZeRO layout.

        With ``step=None`` the newest VALID checkpoint is restored:
        every candidate is checksum-verified first and a torn/corrupt
        step falls back to the previous one (the auto-resume contract —
        a preempted run must never be wedged by its own torn last
        write). An explicit ``step`` restores exactly that step, with
        verification errors propagating.

        The restore is lazy (shard-overlap): only manifest shards that
        intersect this host's partition of the target shardings are read
        — the per-host byte accounting is printed after the restore."""
        from repro.checkpoint import last_restore_stats, \
            restore_checkpoint, restore_latest_valid
        if step is None:
            state, _ = restore_latest_valid(
                ckpt_dir, self.abstract_state(),
                shardings=self.state_shardings())
        else:
            state = restore_checkpoint(ckpt_dir, step,
                                       self.abstract_state(),
                                       shardings=self.state_shardings())
        stats = last_restore_stats()
        if stats is not None:
            mib = 1024 * 1024
            print(f"[ckpt] lazy restore: read "
                  f"{stats.read_bytes / mib:.1f} MiB "
                  f"({stats.entries_read}/{stats.entries_total} shards) "
                  f"for a {stats.partition_bytes / mib:.1f} MiB local "
                  f"partition of a {stats.logical_bytes / mib:.1f} MiB "
                  f"logical state", flush=True)
        return state

    def make_checkpointer(self):
        """Async double-buffered checkpointer configured from EngineConfig
        (bounded in-flight saves + retention GC; cadence is the caller's
        ``ckpt_every``)."""
        from repro.checkpoint import AsyncCheckpointer
        return AsyncCheckpointer(
            max_in_flight=self.ecfg.ckpt_max_in_flight,
            keep_last_k=self.ecfg.ckpt_keep_last)

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------

    def _preprocess_batch(self, batch):
        """Device-side completion of a host uint8 batch (upsample to
        ``cfg.image_size`` + fused cast-and-normalize); identity on float
        batches. Traced inside the jitted train/eval steps — the
        model-resolution fp32 image tensor never exists on the host."""
        if self.preproc is None:
            return batch
        from repro.data.augment import device_preprocess
        return device_preprocess(batch, self.preproc, self.cfg.image_size)

    def _train_step(self, state: TrainState, batch):
        params, opt_state = state.params, state.opt_state
        # ZeRO-3 §Perf optimization (cast_params_bf16): convert the f32
        # master shards to bf16 BEFORE GSPMD's per-layer all-gather —
        # halves all-gather bytes; master copy/optimizer stay f32.
        compute_params = self._compute_params(params)
        # ZeRO>=2: dp-sharded grad accumulator => per-microstep
        # reduce-scatter instead of a replicated all-reduce
        gspecs = self._pspecs(self.init_abstract()[0],
                              for_opt_state=True) \
            if self.ecfg.zero_stage >= 2 else None
        if self.ecfg.pipeline_stages > 1:
            # 1F1B pipeline route (core/pipeline.py). Runs outside the
            # Ulysses hint context: stage-vectorized activations carry a
            # leading stage axis the (B,S,D) hints don't describe; GSPMD
            # infers layouts from the pipe/dp constraints instead. ZeRO
            # still composes: grads get the same dp-sharded constraint.
            # The staged path threads the SAME fold_in(rng, step)
            # per-microbatch streams as the dp path (augmentation /
            # preprocess run per-microbatch inside the schedule), so a
            # pp run replays the dp run's augmentation stream exactly.
            mb_rngs = jax.random.split(
                jax.random.fold_in(state.rng, state.step),
                self.ecfg.gradient_accumulation_steps)
            grads, metrics = self._pipeline_grads(
                compute_params, batch, gspecs, mb_rngs)
        else:
            with shardctx.use(self.hints):
                # per-step, per-microbatch PRNG streams derived from the
                # state's base key: fold_in(rng, step) makes resumes
                # reproduce future randomness exactly (the key itself
                # never mutates). Deterministic archs ignore them (DCE'd).
                mb_rngs = jax.random.split(
                    jax.random.fold_in(state.rng, state.step),
                    self.ecfg.gradient_accumulation_steps)

                # scoped inside the differentiated function, so its
                # backward reads transpose(jvp(forward)) (repro/obs.py)
                @jax.named_scope(obs.FORWARD)
                def mb_loss(p, mb, rng):
                    if self.aug is not None:
                        # on-device crop/flip/Mixup/CutMix — pure in the
                        # microbatch rng, so the stream is resumable;
                        # uint8 microbatches are upsampled/normalized
                        # inside (composed with the geometric augs)
                        from repro.data.augment import augment_batch
                        mb = augment_batch(rng, mb, self.aug,
                                           preproc=self.preproc,
                                           resolution=self.cfg.image_size)
                    else:
                        # per-MICROBATCH preprocess: only one microbatch's
                        # upsampled fp32 image tensor is live at a time
                        mb = self._preprocess_batch(mb)
                    return model.loss_fn(self.cfg, p, mb)
                grads, metrics = accumulate_gradients(
                    mb_loss, compute_params, batch,
                    self.ecfg.gradient_accumulation_steps, grad_specs=gspecs,
                    rngs=mb_rngs)
        with jax.named_scope(obs.OPTIMIZER):
            new_params, new_opt, new_step, metrics = self._apply_update(
                state, grads, metrics)
        new_state = state.replace(params=new_params, opt_state=new_opt,
                                  step=new_step)
        return new_state, metrics

    def _apply_update(self, state: TrainState, grads, metrics):
        """The step's work after the gradients: lr schedule, clip, update
        and the anomaly guard's selects."""
        params, opt_state = state.params, state.opt_state
        lr = self.schedule(state.step)
        new_params, new_opt, gnorm = self.optimizer.update(
            grads, opt_state, params, lr)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        new_step = state.step + 1
        if self.ecfg.guard_anomalies:
            # anomaly guard (resilience): a non-finite loss or global
            # grad-norm means the candidate update is garbage — select
            # the INPUT params/opt/step instead, so the step is a pure
            # no-op on the TrainState (cursor/rng semantics untouched;
            # the host loop sees step_ok == 0, retries the same cursor
            # batch, and escalates after guard_max_skips skips). The
            # select is exact when ok: guard on/off trajectories are
            # bitwise identical on healthy steps.
            ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)

            def sel(new, ref):
                return jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                                    new, ref)
            new_params = sel(new_params, params)
            new_opt = sel(new_opt, opt_state)
            new_step = jnp.where(ok, new_step, state.step)
            metrics["step_ok"] = ok.astype(jnp.int32)
        return new_params, new_opt, new_step, metrics

    def _pipeline_grads(self, compute_params, batch, gspecs, mb_rngs):
        """Mean grads + metrics via the staged 1F1B pipeline — numerically
        interchangeable with ``accumulate_gradients`` over the same
        microbatches and rng streams (the pp-vs-dp parity invariant).

        Uses ``pipelined_value_and_grad`` (manual per-chunk VJPs, O(S·v)
        residual memory) rather than AD through the schedule; gradients
        come back already accumulated in fp32. Augmentation/preprocess
        happen per-microbatch via ``microbatch_fn`` inside the schedule,
        so only one microbatch's fp32 image tensor is live at a time."""
        pspecs = self._pspecs(self.init_abstract()[0])

        @jax.named_scope(obs.FORWARD)
        def microbatch_fn(mb, rng):
            if self.aug is not None:
                from repro.data.augment import augment_batch
                return augment_batch(rng, mb, self.aug,
                                     preproc=self.preproc,
                                     resolution=self.cfg.image_size)
            return self._preprocess_batch(mb)

        (_, metrics), grads = pipe.pipelined_value_and_grad(
            self.cfg, compute_params, batch,
            stages=self.ecfg.pipeline_stages,
            num_micro=self.ecfg.gradient_accumulation_steps,
            interleave=self.ecfg.pipeline_interleave,
            dp_axes=shd.dp_axes_of(self.mesh),
            pipe_axis=pipe.PIPE_AXIS,
            stack_specs=pipe.stage_stack_specs(pspecs["stack"]),
            rngs=mb_rngs,
            microbatch_fn=microbatch_fn)
        return _constrain_tree(grads, gspecs), metrics

    def jit_train_step(self, batch_shapes=None, donate=True):
        """jit'd ``(TrainState, batch) -> (TrainState, metrics)``. The data
        cursor (epoch/batch_index) passes through unchanged — the host loop
        advances it via ``state.replace`` after each step."""
        sshard = self.state_shardings()
        in_shardings = (sshard,
                        shd.named(self.mesh, shd.batch_specs(
                            self.cfg, batch_shapes, self.mesh))
                        if batch_shapes is not None else None)
        return jax.jit(
            self._train_step,
            in_shardings=in_shardings,
            out_shardings=(sshard, None),
            donate_argnums=(0,) if donate else ())

    def lower_train(self, batch_shapes):
        fn = self.jit_train_step(batch_shapes, donate=False)
        with self.mesh:
            return fn.lower(self.abstract_state(), batch_shapes)

    # ------------------------------------------------------------------
    # evaluation (sharded, padding-mask-aware, layout-invariant)
    # ------------------------------------------------------------------

    def _compute_params(self, params):
        """The train step's compute-dtype view of the params (bf16 gather
        under cast_params_bf16) — eval uses the same view so eval numerics
        match what training actually computes with."""
        if not self.ecfg.cast_params_bf16:
            return params
        return jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)

    def _eval_step(self, state: TrainState, batch):
        """No-grad ``(state, batch) -> metrics``: forward + integer
        top-1/top-5 correct counts and an fp32 NLL sum.

        The counts ARE the cross-``data``/``pipe`` reduction: the batch is
        dp-sharded, so the in-jit integer sums lower to all-reduces over
        the dp axes (exact — integer addition is associative), and the
        pipe/model axes compute replicas of the same value. ``mask`` in
        the batch zeroes the padded tail of a non-divisible final eval
        batch. Works under every layout the engine owns, including pp>1:
        the plain scan-over-L forward just gathers pipe-sharded layer
        params (eval needs no 1F1B schedule)."""
        params = self._compute_params(state.params)
        batch = self._preprocess_batch(batch)
        with shardctx.use(self.hints):
            logits, _, _ = model.forward(self.cfg, params, batch,
                                         mode="train")
        return model.classification_counts(logits, batch["labels"],
                                           batch.get("mask"))

    def jit_eval_step(self, batch_shapes=None):
        """jit'd eval step; state is NOT donated (the caller keeps
        training with it)."""
        sshard = self.state_shardings()
        in_shardings = (sshard,
                        shd.named(self.mesh, shd.batch_specs(
                            self.cfg, batch_shapes, self.mesh))
                        if batch_shapes is not None else None)
        return jax.jit(self._eval_step, in_shardings=in_shardings,
                       out_shardings=None)

    def evaluate(self, state: TrainState, batches, *, eval_step=None):
        """Sharded eval loop over an iterator of (padded) eval batches —
        e.g. ``CIFARSource.eval_batches(b)``. Accumulates the per-batch
        integer counts host-side and returns both the exact counts (the
        layout-invariance assertion surface) and the derived rates."""
        if eval_step is None:
            eval_step = self.jit_eval_step()
        top1 = top5 = count = 0
        loss_sum = 0.0
        bshard = None
        with self.mesh:
            for batch in batches:
                if bshard is None:
                    shapes = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        batch)
                    bshard = shd.named(self.mesh, shd.batch_specs(
                        self.cfg, shapes, self.mesh))
                batch = jax.tree.map(jax.device_put, batch, bshard)
                m = eval_step(state, batch)
                top1 += int(jax.device_get(m["top1"]))
                top5 += int(jax.device_get(m["top5"]))
                count += int(jax.device_get(m["count"]))
                loss_sum += float(jax.device_get(m["loss_sum"]))
        n = max(count, 1)
        return {
            "eval_top1_count": top1, "eval_top5_count": top5,
            "eval_count": count,
            "eval_acc": top1 / n, "eval_top5_acc": top5 / n,
            "eval_loss": loss_sum / n,
        }

    # ------------------------------------------------------------------
    # serving (prefill / decode)
    # ------------------------------------------------------------------

    def _prefill(self, params, batch, cache):
        with shardctx.use(self.hints):
            logits, new_cache, _ = model.forward(
                self.cfg, params, batch, mode="prefill", cache=cache)
        return logits[:, -1:], new_cache

    def _decode_step(self, params, cache, token, index):
        with shardctx.use(self.hints):
            logits, new_cache, _ = model.forward(
                self.cfg, params, {"token": token, "index": index},
                mode="decode", cache=cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return next_tok.astype(jnp.int32), new_cache

    def cache_shardings(self, cache_shapes):
        return shd.named(self.mesh, shd.cache_specs(
            self.cfg, cache_shapes, self.mesh))

    def abstract_cache(self, batch: int, max_len: int,
                       dtype=jnp.bfloat16):
        return jax.eval_shape(
            lambda: model.init_cache(self.cfg, batch, max_len, dtype))

    def jit_decode_step(self, cache_shapes, donate=True):
        pshapes = self.init_abstract()[0]
        pshard = self.param_shardings(pshapes)
        cshard = self.cache_shardings(cache_shapes)
        return jax.jit(
            self._decode_step,
            in_shardings=(pshard, cshard, NamedSharding(self.mesh, P()),
                          NamedSharding(self.mesh, P())),
            out_shardings=(NamedSharding(self.mesh, P()), cshard),
            donate_argnums=(1,) if donate else ())

    def lower_decode(self, batch: int, cache_len: int):
        pshapes = self.init_abstract()[0]
        cache_shapes = self.abstract_cache(batch, cache_len)
        tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        idx = jax.ShapeDtypeStruct((), jnp.int32)
        fn = self.jit_decode_step(cache_shapes, donate=False)
        with self.mesh:
            return fn.lower(pshapes, cache_shapes, tok, idx)

    def jit_prefill(self, batch_shapes, cache_shapes):
        pshapes = self.init_abstract()[0]
        pshard = self.param_shardings(pshapes)
        cshard = self.cache_shardings(cache_shapes)
        bshard = shd.named(self.mesh,
                           shd.batch_specs(self.cfg, batch_shapes, self.mesh))
        return jax.jit(self._prefill,
                       in_shardings=(pshard, bshard, cshard),
                       out_shardings=(None, cshard))

    def lower_prefill(self, batch_shapes, cache_len: Optional[int] = None):
        pshapes = self.init_abstract()[0]
        bsz, slen = _batch_and_seq(self.cfg, batch_shapes)
        cache_shapes = self.abstract_cache(bsz, cache_len or slen)
        fn = self.jit_prefill(batch_shapes, cache_shapes)
        with self.mesh:
            return fn.lower(pshapes, batch_shapes, cache_shapes)


def _batch_and_seq(cfg, batch_shapes: Any):
    if "tokens" in batch_shapes:
        return batch_shapes["tokens"].shape[:2]
    if "features" in batch_shapes:
        return batch_shapes["features"].shape[:2]
    if "images" in batch_shapes:
        return batch_shapes["images"].shape[0], 0
    raise ValueError(list(batch_shapes))
