"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Every mesh in the repo is built here, with ``AxisType.Auto`` on every axis:
the engine lays out arrays through GSPMD sharding constraints and
``NamedSharding``s, not through explicit-sharding types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 1):
    """16x16 = 256 chips/pod (TPU v5e pod); 2 pods over DCN when multi_pod.

    ``pipe > 1`` carves the pipeline axis out of the data axis (pipeline
    stages talk over the torus ring; dp gradient reductions shrink by the
    same factor) — axis convention ("pod",) + ("data", "pipe", "model").
    """
    assert 16 % pipe == 0, pipe
    shape = (16 // pipe, pipe, 16) if pipe > 1 else (16, 16)
    axes = ("data", "pipe", "model") if pipe > 1 else ("data", "model")
    if multi_pod:
        shape, axes = (2,) + shape, ("pod",) + axes
    return auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(data=16, model=16, pod=2 if multi_pod else 1)


def make_local_mesh(model: int = 1, pipe: int = 1, devices=None):
    """Mesh over ``devices`` (default: every local device — one chip, or
    the host devices a CPU run forces with
    xla_force_host_platform_device_count).

    ``pipe > 1`` inserts the pipeline axis between data and model:
    ("data", "pipe", "model") — dp extent is whatever remains. The pipe
    extent is the number of physical pipeline devices S; interleaved
    virtual stages (EngineConfig.pipeline_interleave) subdivide each
    device's layer range without changing the mesh."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    assert n % (model * pipe) == 0, (n, model, pipe)
    if pipe > 1:
        return auto_mesh((n // (model * pipe), pipe, model),
                         ("data", "pipe", "model"), devices)
    return auto_mesh((n // model, model), ("data", "model"), devices)
