"""Feed-forward blocks: SwiGLU / GeGLU / GELU / squared-ReLU."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.params import dense_init, zeros


def is_gated(act: str) -> bool:
    return act in ("swiglu", "geglu")


def init_mlp(key, d_model, d_ff, act, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_out": dense_init(ks[2], (d_ff, d_model), dtype)}
    if is_gated(act):
        p["w_gate"] = dense_init(ks[0], (d_model, d_ff), dtype)
        p["w_up"] = dense_init(ks[1], (d_model, d_ff), dtype)
    else:
        p["w_up"] = dense_init(ks[1], (d_model, d_ff), dtype)
        p["b_up"] = zeros((d_ff,), dtype)
        p["b_out"] = zeros((d_model,), dtype)
    return p


_GELU_C = math.sqrt(2.0 / math.pi)
_GELU_A = 0.044715


@jax.custom_vjp
def gelu(u):
    """tanh-approximate GELU, ``jax.nn.gelu``'s default form.

    The backward keeps only the input u, where autodiff would also keep
    intermediates such as u**2, the tanh and the cdf, each (..., d_ff) and
    stacked per layer by the layer scan. The derivative recomputes the tanh
    from u in fp32."""
    return jax.nn.gelu(u)


def _gelu_fwd(u):
    return jax.nn.gelu(u), u


def _gelu_bwd(u, g):
    uf = u.astype(jnp.float32)
    t = jnp.tanh(_GELU_C * (uf + _GELU_A * uf ** 3))
    d = 0.5 * (1.0 + t) + 0.5 * uf * (1.0 - t * t) * _GELU_C * (
        1.0 + 3.0 * _GELU_A * uf * uf)
    return ((g.astype(jnp.float32) * d).astype(u.dtype),)


gelu.defvjp(_gelu_fwd, _gelu_bwd)


def _act(h, act):
    if act in ("swiglu",):
        return jax.nn.silu(h)
    if act in ("geglu", "gelu"):
        return gelu(h)
    if act == "sqrelu":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(act)


def mlp(p, x, act):
    if is_gated(act):
        h = _act(x @ p["w_gate"].astype(x.dtype), act) * (
            x @ p["w_up"].astype(x.dtype))
        return h @ p["w_out"].astype(x.dtype)
    h = _act(x @ p["w_up"].astype(x.dtype) + p["b_up"].astype(x.dtype), act)
    return h @ p["w_out"].astype(x.dtype) + p["b_out"].astype(x.dtype)
