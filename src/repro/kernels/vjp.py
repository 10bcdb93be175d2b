"""Shared custom-VJP harness for differentiable Pallas kernels.

Every train-path kernel in this package (flash attention, wkv6, fused
RMSNorm) follows the same pattern, extracted here so new kernels inherit it
instead of hand-rolling the plumbing:

* **Spec-as-nondiff-arg**: each kernel bundles its static configuration
  (block sizes, interpret flag, pruning switches) into a hashable NamedTuple
  passed as argument 0, declared ``nondiff_argnums=(0,)`` on the
  ``jax.custom_vjp`` and ``static_argnums=(0,)`` on the jit wrapper — one
  compiled kernel per spec, gradients never see it.
* **Residual plumbing**: the forward returns ``(primal, residuals)``; the
  harness registers it directly as the VJP fwd rule, so the Pallas forward
  decides exactly what survives to the backward (saved inputs + cheap fp32
  per-row/per-chunk summaries like the flash lse, the rmsnorm inv-rms, or
  the wkv6 entering chunk states) and ``jax.grad`` can never fall back to
  differentiating the interpreter/Mosaic kernel body.
* **fp32 accumulator policy**: backward kernels accumulate in
  ``ACCUM_DTYPE`` (fp32) VMEM scratch regardless of input dtype and cast to
  the primal dtype only at the final flush — ``cast_grads_like`` enforces
  the custom_vjp contract that each cotangent matches its primal's aval.
* **Lane-dense summaries**: Mosaic requires each block's last two
  dimensions to be multiples of (8, 128) or the array's full extent, and
  lays out 1-D operands differently from XLA. So every per-row fp32
  summary (flash lse/Δ, rmsnorm inv-rms) is stored lane-broadcast as
  ``(..., rows, LANES)``; ``to_lanes`` writes it and ``from_lanes`` reads
  column 0 back as a ``(rows, 1)`` column.
* **Interpret auto-detection**: ``auto_interpret(None)`` resolves to
  interpret mode off-TPU (the CPU test backend) and compiled Mosaic on TPU.
* **Block-size defaults from cfg**: ``attn_blocks`` / ``norm_block_rows`` /
  ``wkv_chunk`` pull tile sizes from a ``ModelConfig`` when one is in hand
  (the ops.py dispatch layer threads it through) with kernel-tuned
  fallbacks, so models never hardcode tile shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACCUM_DTYPE = jnp.float32

# lane width of the per-row fp32 summaries (one TPU vreg row)
LANES = 128

# VMEM bound on the wkv6 pairwise-decay tile (chunk, chunk, P); see
# configs/rwkv6_7b.py for the measurement that picked it.
WKV_CHUNK_MAX = 32


def auto_interpret(interpret=None) -> bool:
    """None -> interpret unless running on a real TPU backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def float0_like(x):
    """Zero cotangent for integer/meta operands (e.g. SMEM flag vectors)."""
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


def row_valid(idx, block, limit):
    """(block, 1) bool: rows of tile ``idx`` inside a length-``limit`` axis.
    The shared ragged-tail mask — OOB block reads are undefined (NaN in
    interpret mode), so kernels zero the rows this marks False before any
    reduction/matmul touches them."""
    rows = idx * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return rows < limit


def to_lanes(col):
    """(rows, 1) fp32 column -> (rows, LANES) lane-broadcast block value."""
    return jnp.broadcast_to(col, (col.shape[0], LANES))


def from_lanes(block):
    """(rows, LANES) lane-broadcast block value -> (rows, 1) column."""
    return block[:, :1]


def cast_like(grad, primal):
    """Cast one fp32-accumulated gradient to its primal's dtype."""
    return grad.astype(primal.dtype)


def cast_grads_like(grads, primals):
    """Cast a tuple of fp32-accumulated gradients to the primal dtypes."""
    return tuple(cast_like(g, p) for g, p in zip(grads, primals))


def differentiable(fwd, bwd, primal=None):
    """Build a differentiable kernel op from a forward and a backward.

    ``fwd(spec, *args) -> (primal, residuals)`` — primal may be a pytree;
    residuals are whatever the backward needs (inputs + kernel-emitted
    summaries). ``bwd(spec, residuals, cotangent) -> grads`` — one per arg,
    ``float0_like`` for non-float operands. ``spec`` (argument 0) must be
    hashable; it is excluded from differentiation.

    ``primal(spec, *args) -> primal`` (optional): a residual-free forward
    for the non-differentiated path. Supply it when emitting residuals
    costs real HBM (e.g. the wkv6 per-chunk states) — XLA cannot dead-code
    an output out of a multi-output pallas_call, so eval/decode forwards
    would otherwise pay for residuals no backward ever reads.

    The returned op is NOT jitted — kernels wrap it with
    ``jax.jit(..., static_argnums=(0,))`` at their public entry point.
    """
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def op(spec, *args):
        if primal is not None:
            return primal(spec, *args)
        return fwd(spec, *args)[0]

    op.defvjp(fwd, bwd)
    return op


# ---------------------------------------------------------------------------
# block-size defaults from cfg (the ops.py dispatch layer threads cfg here)
# ---------------------------------------------------------------------------

def attn_blocks(cfg=None, block_q=None, block_k=None):
    """(block_q, block_k) for the flash kernels: explicit > cfg > 128."""
    if block_q is None:
        block_q = cfg.attn_block_q if cfg is not None else 128
    if block_k is None:
        block_k = cfg.attn_block_k if cfg is not None else 128
    return int(block_q), int(block_k)


def norm_block_rows(cfg=None, block_rows=None):
    """Row-tile height for the fused-rmsnorm kernels: explicit > cfg > 256."""
    if block_rows is None:
        block_rows = getattr(cfg, "norm_block_rows", 256) \
            if cfg is not None else 256
    return int(block_rows)


def wkv_chunk(cfg=None, chunk=None):
    """wkv6 chunk length, clamped to the VMEM pairwise-tile bound."""
    if chunk is None:
        chunk = cfg.ssm.chunk_size if cfg is not None and cfg.ssm else \
            WKV_CHUNK_MAX
    return min(int(chunk), WKV_CHUNK_MAX)


__all__ = [
    "ACCUM_DTYPE", "LANES", "WKV_CHUNK_MAX", "attn_blocks",
    "auto_interpret", "cast_grads_like", "cast_like", "differentiable",
    "float0_like", "from_lanes", "norm_block_rows", "row_valid",
    "to_lanes", "wkv_chunk",
]
