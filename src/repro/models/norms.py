"""RMSNorm / LayerNorm / per-head GroupNorm.

``rmsnorm`` is the dispatch point for the fused Pallas kernel: callers pass
``use_pallas=cfg.use_pallas`` (and optionally ``block_rows`` /
``interpret``) and the differentiable ``kernels.ops.fused_rmsnorm`` — with
its row-tiled Pallas backward — takes over the 2·L-per-step hot path;
otherwise the pure-jnp form below runs (fp32 math either way).

``layernorm`` carries its own backward: it keeps the input and the
per-row mean and inverse std, where autodiff would keep three fp32
copies of the row."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def rmsnorm(x, scale, eps, *, use_pallas=False, block_rows=None,
            interpret=None):
    if use_pallas:
        from repro.kernels.ops import fused_rmsnorm
        return fused_rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                             interpret=interpret)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf / jnp.sqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def layernorm(x, scale, bias, eps):
    return _layernorm_fwd(x, scale, bias, eps)[0]


def _layernorm_fwd(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps)
    y = (out * scale.astype(jnp.float32)
         + bias.astype(jnp.float32)).astype(x.dtype)
    return y, (x, mu, jax.lax.rsqrt(var + eps), scale, bias)


def _layernorm_bwd(eps, res, g):
    x, mu, rstd, scale, bias = res
    xhat = (x.astype(jnp.float32) - mu) * rstd
    gf = g.astype(jnp.float32)
    rows = tuple(range(x.ndim - scale.ndim))
    dscale = jnp.sum(gf * xhat, axis=rows)
    dbias = jnp.sum(gf, axis=rows)
    gx = gf * scale.astype(jnp.float32)
    dx = rstd * (gx - jnp.mean(gx, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gx * xhat, axis=-1, keepdims=True))
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(bias.dtype))


layernorm.defvjp(_layernorm_fwd, _layernorm_bwd)


def groupnorm_heads(x, scale, bias, eps):
    """Per-head group norm, x (B, S, H, P), scale/bias (H*P,)."""
    b, s, h, p = x.shape
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = ((xf - mu) / jnp.sqrt(var + eps)).reshape(b, s, h * p)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.reshape(b, s, h, p).astype(x.dtype)
