"""Fused RMSNorm Pallas TPU kernels — row-tiled, single HBM pass,
forward + backward, custom VJP.

Unfused XLA emits separate reduce + mul passes over (tokens, d_model); the
fused kernel normalizes and scales one (block_rows, D) VMEM tile per grid
step. Trivial but hot: it runs 2·L times per transformer step, so the
backward matters more than the forward for training throughput.

Backward pass
-------------
``fused_rmsnorm`` is a ``jax.custom_vjp`` built on the shared
``kernels.vjp`` harness. The forward emits the per-row inverse RMS
``rinv = (mean(x²)+eps)^{-1/2}`` (fp32, one scalar per row, stored
lane-broadcast as (rows, LANES) — Mosaic lays a 1-D operand out differently
from XLA) as a residual,
so the backward never redoes the row reduction: one row-tiled pass computes

    dx = rinv · (dy∘scale) − rinv³/D · x · rowsum(dy∘scale∘x)
    dscale = Σ_rows dy ∘ x ∘ rinv

with dscale accumulated across the whole (sequential) grid in an fp32 VMEM
scratch and flushed once at the last row-block. Ragged rows (rows %
block_rows ≠ 0) are masked out of the dscale reduction — OOB tile reads are
undefined (NaN in interpret mode) and would otherwise poison the
accumulator; the corresponding dx rows are clipped by the block writeback.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import vjp


class _Spec(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""
    block_rows: int
    eps: float
    interpret: bool


# ---------------------------------------------------------------------------
# forward kernel (emits per-row inv-rms residual)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, scale_ref, o_ref, rinv_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rinv = 1.0 / jnp.sqrt(var + eps)               # (rows, 1) fp32
    o_ref[...] = ((x * rinv)
                  * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    rinv_ref[...] = vjp.to_lanes(rinv)


def _forward(spec, x, scale):
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for dim in x.shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, d)
    br = min(spec.block_rows, rows)
    grid = (pl.cdiv(rows, br),)

    out, rinv = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=spec.eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, vjp.LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((rows, vjp.LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=spec.interpret,
    )(x2, scale.reshape(1, d))
    return out.reshape(orig_shape), rinv


# ---------------------------------------------------------------------------
# backward kernel (row-tiled dx + grid-accumulated dscale)
# ---------------------------------------------------------------------------

def _bwd_kernel(x_ref, scale_ref, dy_ref, rinv_ref,
                dx_ref, dsc_ref, dsc_scr, *, dinv, rows, block_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dsc_scr[...] = jnp.zeros_like(dsc_scr)

    ok = vjp.row_valid(i, block_rows, rows)
    x = jnp.where(ok, x_ref[...].astype(jnp.float32), 0.0)
    dy = jnp.where(ok, dy_ref[...].astype(jnp.float32), 0.0)
    rinv = jnp.where(ok, vjp.from_lanes(rinv_ref[...]), 0.0)   # (rows, 1)
    s = scale_ref[...].astype(jnp.float32)         # (1, D)

    dys = dy * s
    dot = jnp.sum(dys * x, axis=-1, keepdims=True)
    dx = rinv * dys - (rinv * rinv * rinv * dinv) * x * dot
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dsc_scr[...] += jnp.sum(dy * x * rinv, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _final():
        dsc_ref[...] = dsc_scr[...].astype(dsc_ref.dtype)


def _backward(spec, x, scale, rinv, dy):
    orig_shape = x.shape
    d = x.shape[-1]
    rows = rinv.shape[0]
    x2 = x.reshape(rows, d)
    dy2 = dy.reshape(rows, d)
    br = min(spec.block_rows, rows)
    grid = (pl.cdiv(rows, br),)

    dx, dscale = pl.pallas_call(
        functools.partial(_bwd_kernel, dinv=1.0 / d, rows=rows,
                          block_rows=br),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, vjp.LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), scale.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=spec.interpret,
    )(x2, scale.reshape(1, d), dy2, rinv)
    return dx.reshape(orig_shape), dscale.reshape(d)


# ---------------------------------------------------------------------------
# custom VJP plumbing (shared kernels.vjp harness)
# ---------------------------------------------------------------------------

def _rms_fwd(spec, x, scale):
    out, rinv = _forward(spec, x, scale)
    return out, (x, scale, rinv)


def _rms_bwd(spec, res, dy):
    x, scale, rinv = res
    dx, dscale = _backward(spec, x, scale, rinv, dy)
    return dx, dscale


_rms = vjp.differentiable(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def fused_rmsnorm(x, scale, *, eps=1e-6, block_rows=256, interpret=False):
    """x (..., D) -> rmsnorm(x) * scale, fused. Differentiable (custom VJP,
    row-tiled Pallas backward reusing the saved per-row inv-rms)."""
    spec = _Spec(int(block_rows), float(eps), bool(interpret))
    return _rms(spec, x, scale)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def fused_rmsnorm_fwd(x, scale, *, eps=1e-6, block_rows=256,
                      interpret=False):
    """Forward returning ``(out, rinv)`` — the fp32 per-row inverse-RMS
    residual the backward consumes, one scalar per row (exposed for
    tests/inspection)."""
    spec = _Spec(int(block_rows), float(eps), bool(interpret))
    out, rinv = _forward(spec, x, scale)
    return out, rinv[:, 0]
