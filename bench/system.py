"""The system under test, built as ``launch/train.py:main`` builds it, and
the per-step calls its loop makes at its defaults.

``launch/train.py`` runs a fixed ``--steps`` and cannot be handed a
deadline, so this module repeats its construction and its loop body call
for call: ``get_config`` -> the data path (the family's ``build_data``:
``make_source`` -> ``DataPipeline`` for a dataset job) ->
``make_local_mesh`` -> ``EngineConfig`` -> ``DistributedEngine`` ->
``init_state(seed)`` -> ``jit_train_step()``, the ``Prefetcher`` over
``batch_specs`` shardings, and per step ``next(prefetcher)``, the fault
hook, ``step_fn(state, batch)``, the anomaly guard's ``step_ok`` read and
the cursor roll. Each call sits in a host span of the harness
(``jax.profiler.TraceAnnotation``) so the device trace can tell what the
host was doing in an idle gap.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any

INPUT_WAIT, DISPATCH, GUARD_READ = "input_wait", "dispatch", "guard_read"
HOST_SPANS = (INPUT_WAIT, DISPATCH, GUARD_READ)


@dataclass
class Trainer:
    cfg: Any
    ecfg: Any
    mesh: Any
    step_fn: Any
    prefetcher: Any
    state: Any
    step: int = 0
    spans: dict = field(default_factory=lambda: {k: 0.0 for k in HOST_SPANS})
    skips: int = 0

    def close(self):
        self.prefetcher.close()
        self.state = None


def _at(obj, key: str):
    """``obj``'s value at a dotted key, through dicts or attributes."""
    for part in key.split("."):
        obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
    return obj


def model_config(config: dict, shape_keys):
    """The program's ModelConfig for a configuration file, checked
    against the file's published shapes at each of ``shape_keys``, so that
    a registry change shows as a refusal, not as a silently different
    model. An override that is a dict replaces fields of a nested group."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    cfg = cfg.replace(**{
        k: dataclasses.replace(getattr(cfg, k), **v) if isinstance(v, dict)
        else v for k, v in config.get("overrides", {}).items()})
    want = {k: _at(config, k) for k in shape_keys}
    got = {k: _at(cfg, k) for k in shape_keys}
    if got != want:
        raise ValueError(f"the program's {config['arch']} config differs from "
                         f"{config['name']}.json: program {got}, file {want}")
    return cfg


def build(cell, seed: int, devices) -> Trainer:
    """Everything ``launch/train.py:main`` builds for this cell's job, with
    the state initialised on the device from ``seed``."""
    from repro.configs import EngineConfig
    from repro.core import sharding as shd
    from repro.core.engine import DistributedEngine
    from repro.launch.mesh import make_local_mesh

    traffic = cell.traffic
    cfg = model_config(cell.config, cell.family.SHAPE_KEYS)
    pipe, preproc = cell.family.build_data(cfg, traffic, seed)
    mesh = make_local_mesh(devices=devices)
    opt = traffic["optimizer"]
    ecfg = EngineConfig(
        train_batch_size=traffic["global_batch"],
        gradient_accumulation_steps=traffic["accum"],
        zero_stage=traffic["zero"], optimizer=opt["name"], lr=opt["lr"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        lr_schedule=opt["schedule"], total_steps=opt["total_steps"],
        warmup_steps=opt["warmup_steps"], seed=seed,
        guard_anomalies=traffic["guard"])
    eng = DistributedEngine(cfg, ecfg, mesh, preproc=preproc)
    state = eng.init_state(seed=seed)
    step_fn = eng.jit_train_step()
    bshard = shd.named(mesh, shd.batch_specs(cfg, pipe.batch_shapes(), mesh))
    prefetcher = pipe.prefetch(int(state.epoch), int(state.batch_index),
                               shardings=bshard,
                               depth=traffic["prefetch_depth"])
    return Trainer(cfg=cfg, ecfg=ecfg, mesh=mesh, step_fn=step_fn,
                   prefetcher=prefetcher, state=state)


def train_step(t: Trainer):
    """One step of ``launch/train.py``'s loop; returns its metrics (device
    arrays). Adds the host time of each call to ``t.spans``."""
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from repro.resilience import faults

    clock = time.perf_counter
    t0 = clock()
    with TraceAnnotation(INPUT_WAIT):
        _, batch, nxt = next(t.prefetcher)
    t1 = clock()
    t.spans[INPUT_WAIT] += t1 - t0
    skips = 0
    while True:
        fed = faults.poison_batch(batch, t.step, resolution=t.cfg.image_size)
        t1 = clock()
        with TraceAnnotation(DISPATCH):
            state, metrics = t.step_fn(t.state, fed)
        t2 = clock()
        t.state = state
        with TraceAnnotation(GUARD_READ):
            ok = not t.ecfg.guard_anomalies or \
                bool(np.asarray(metrics["step_ok"]))
        t3 = clock()
        t.spans[DISPATCH] += t2 - t1
        t.spans[GUARD_READ] += t3 - t2
        if ok:
            break
        skips += 1
        t.skips += 1
        if skips >= t.ecfg.guard_max_skips:
            raise RuntimeError(f"anomaly guard: {skips} consecutive skipped "
                               f"updates at step {t.step}")
    t.state = t.state.replace(epoch=jnp.int32(nxt[0]),
                              batch_index=jnp.int32(nxt[1]))
    t.step += 1
    return metrics
