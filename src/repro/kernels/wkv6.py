"""WKV6 (RWKV6 recurrence) Pallas TPU kernels — chunked matmul form,
forward + backward, custom VJP.

TPU adaptation (DESIGN.md §6): the reference CUDA wkv6 kernel serializes one
thread per channel over the whole sequence; here each (batch, head) runs a
sequential grid axis over chunks, carrying the (P, P) state in VMEM scratch,
while the intra-chunk work is two MXU matmuls + one VPU pairwise-decay
contraction. The pairwise decay exp(L_{t-1} - L_j) <= 1 for j < t, so the
kernel is fp32-overflow-safe under arbitrarily strong decay (unlike the
factored r·e^L / k·e^-L formulation).

Backward pass (the training hot path)
-------------------------------------
``wkv6_chunked_kernel`` is a ``jax.custom_vjp`` built on the shared
``kernels.vjp`` harness — training through RWKV6 never differentiates the
interpret/Mosaic forward body. The VJP forward additionally emits the
*entering* state of every chunk (fp32, (B,H,NC,P,P)) as a residual
(non-differentiated forwards — eval, decode — take a residual-free primal
variant that skips this output entirely); the backward kernel
walks the chunk axis **in reverse** (grid index maps flip ci -> NC-1-ci),
carrying the state cotangent ``G_c = dL/dS_c`` in fp32 VMEM scratch via the
reverse recurrence

    G_{c-1} = rdec_cᵀ · dO_c  +  diag(e^{L_end,c}) G_c

and reconstituting the intra-chunk pairwise tensors (bounded, clip-free for
the live strictly-causal triangle) to produce dr/dk/dv/dwlog per chunk plus
the du bonus reduction (accumulated per (B,H) in scratch, summed over batch
outside) and dS0 at the final (= first) chunk. All accumulation is fp32;
gradients are cast to the primal dtypes at the flush (harness policy).

Layout: r/k/v/wlog rearranged to (B, H, NC, CS, P) internally.
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
    chunk: int
    interpret: bool


def _cumsum_rows(x, chunk, *, reverse=False):
    """Inclusive prefix sum over the chunk (row) axis as a triangular
    matmul: Mosaic has no cumsum lowering. ``reverse`` sums rows >= t."""
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    keep = (j_idx >= t_idx) if reverse else (j_idx <= t_idx)
    return jax.lax.dot(keep.astype(jnp.float32), x,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel (chunked state recurrence; emits entering states residual)
# ---------------------------------------------------------------------------

def _fwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, s_out_ref, *refs, chunk, num_chunks, with_states):
    # primal-only forwards (eval/decode) skip the states residual output —
    # XLA can't dead-code an output out of a multi-output pallas_call
    if with_states:
        states_ref, state_scr = refs
    else:
        (state_scr,) = refs
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    if with_states:
        # entering state of this chunk — the backward's residual
        states_ref[0, 0, 0] = state_scr[...]

    r = r_ref[0, 0, 0].astype(jnp.float32)         # (cs, P)
    k = k_ref[0, 0, 0].astype(jnp.float32)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    w = w_ref[0, 0, 0].astype(jnp.float32)         # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)               # (1, P)

    L = _cumsum_rows(w, chunk)                     # inclusive
    lprev = L - w
    state = state_scr[...]

    # carried-state contribution
    o = jax.lax.dot(r * jnp.exp(lprev), state,
                    preferred_element_type=jnp.float32)

    # intra-chunk strictly-causal pairwise term (bounded decay <= 1)
    pair = jnp.exp(jnp.minimum(lprev[:, None, :] - L[None, :, :], 0.0))
    att = jnp.sum(r[:, None, :] * pair * k[None, :, :], axis=-1)  # (cs, cs)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(j_idx < t_idx, att, 0.0)
    o = o + jax.lax.dot(att, v, preferred_element_type=jnp.float32)

    # diagonal bonus
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)
    o = o + diag * v

    # state update: S <- diag(e^{L_end}) S + (k ⊙ e^{L_end - L})^T v
    l_end = L[-1:, :]                              # (1, P)
    k_adv = k * jnp.exp(l_end - L)
    state_scr[...] = (jnp.exp(l_end).T * state
                      + jax.lax.dot(k_adv.T, v,
                                    preferred_element_type=jnp.float32))

    o_ref[0, 0, 0] = o.astype(o_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _final():
        s_out_ref[0, 0] = state_scr[...].astype(s_out_ref.dtype)


def _to_chunked(x, b, nc, cs, h, p):
    return x.reshape(b, nc, cs, h, p).transpose(0, 3, 1, 2, 4)


def _from_chunked(x, b, s, h, p):
    return x.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)


def _forward(spec, r, k, v, wlog, u, s0, *, with_states):
    b, s, h, p = r.shape
    cs = spec.chunk
    nc = s // cs
    assert nc * cs == s, (s, cs)

    rc, kc, vc, wc = (_to_chunked(x, b, nc, cs, h, p)
                      for x in (r, k, v, wlog))

    def rkvw_map(bb, hh, ci):
        return (bb, hh, ci, 0, 0)

    def u_map(bb, hh, ci):
        return (hh, 0, 0)

    def s0_map(bb, hh, ci):
        return (bb, hh, 0, 0)

    out_specs = [
        pl.BlockSpec((1, 1, 1, cs, p), rkvw_map),
        pl.BlockSpec((1, 1, p, p), s0_map),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, nc, cs, p), jnp.float32),
        jax.ShapeDtypeStruct((b, h, p, p), jnp.float32),
    ]
    if with_states:
        out_specs.append(pl.BlockSpec((1, 1, 1, p, p),
                                      lambda bb, hh, ci: (bb, hh, ci, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, p, p), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=cs, num_chunks=nc,
                          with_states=with_states),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, p), rkvw_map),
            pl.BlockSpec((1, 1, 1, cs, p), rkvw_map),
            pl.BlockSpec((1, 1, 1, cs, p), rkvw_map),
            pl.BlockSpec((1, 1, 1, cs, p), rkvw_map),
            pl.BlockSpec((1, 1, p), u_map),
            pl.BlockSpec((1, 1, p, p), s0_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
    )(rc, kc, vc, wc, u.reshape(h, 1, p), s0)

    o = _from_chunked(outs[0], b, s, h, p)
    s_end = outs[1]
    states = outs[2] if with_states else None
    return o, s_end, states


# ---------------------------------------------------------------------------
# backward kernel (reverse-chunk state-gradient recurrence)
# ---------------------------------------------------------------------------

def _bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref, do_ref, dsend_ref,
                dr_ref, dk_ref, dv_ref, dw_ref, ds0_ref, du_ref,
                g_scr, du_scr, *, chunk, num_chunks):
    ci = pl.program_id(2)              # 0..nc-1, index maps reverse it

    @pl.when(ci == 0)
    def _init():
        g_scr[...] = dsend_ref[0, 0].astype(jnp.float32)
        du_scr[...] = jnp.zeros_like(du_scr)

    r = r_ref[0, 0, 0].astype(jnp.float32)         # (cs, P)
    k = k_ref[0, 0, 0].astype(jnp.float32)
    v = v_ref[0, 0, 0].astype(jnp.float32)
    w = w_ref[0, 0, 0].astype(jnp.float32)         # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)               # (1, P)
    state = s_ref[0, 0, 0]                         # entering state (P, P) f32
    do = do_ref[0, 0, 0].astype(jnp.float32)       # (cs, P)
    g = g_scr[...]                                 # dL/dS_out of this chunk

    L = _cumsum_rows(w, chunk)
    lprev = L - w
    l_end = L[-1:, :]                              # (1, P)
    e_lprev = jnp.exp(lprev)
    e_adv = jnp.exp(l_end - L)                     # kadv decay, <= 1
    rdec = r * e_lprev
    kadv = k * e_adv

    # strictly causal (t, j, 1), built 3-D: Mosaic cannot add a lane dim
    # to a 2-D mask by reshape
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, 1), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, 1), 1)
    tri = (j_idx < t_idx).astype(jnp.float32)
    # live-triangle pairwise decay: lprev_t - L_j <= 0 for j < t, so no
    # clip is needed once tri zeroes the upper triangle (and the masked
    # entries' exp can't overflow: min() bounds them at 1)
    pair = tri * jnp.exp(jnp.minimum(
        lprev[:, None, :] - L[None, :, :], 0.0))   # (cs, cs, P)

    # --- intra-chunk attention adjoints ---
    # dA = dO·Vᵀ reduced over lanes with the lane dim kept; pair carries
    # the causal mask
    dA = jnp.sum(do[:, None, :] * v[None, :, :], axis=-1,
                 keepdims=True)                    # (t, j, 1)
    T1 = dA * pair                                 # (t, j, P)
    dr_att = jnp.sum(T1 * k[None, :, :], axis=1)   # (cs, P)
    dk_att = jnp.sum(T1 * r[:, None, :], axis=0)   # (cs, P)
    E = T1 * r[:, None, :] * k[None, :, :]         # dA ∘ ∂A/∂(lprev-L)
    dlprev_pair = jnp.sum(E, axis=1)               # (cs, P) — per t
    dL_pair = -jnp.sum(E, axis=0)                  # (cs, P) — per j

    # --- carried-state contribution o += rdec · S ---
    drdec = jax.lax.dot_general(do, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # do·Sᵀ
    # --- state cotangent entering this chunk ---
    #   S_out = diag(e^{L_end}) S + kadvᵀ v  and  o_t += rdec_t · S
    ds_in = (jax.lax.dot_general(rdec, do, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jnp.exp(l_end).T * g)               # (P, P)

    # --- dv: A' v term + state-update term + diagonal bonus ---
    att = jnp.sum(r[:, None, :] * pair * k[None, :, :], axis=-1)  # (t, j)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)             # (cs, 1)
    dov = jnp.sum(do * v, axis=-1, keepdims=True)                 # (cs, 1)
    dv = (jax.lax.dot_general(att, do, (((0,), (0,)), ((), ())),  # Aᵀ·dO
                              preferred_element_type=jnp.float32)
          + jax.lax.dot(kadv, g, preferred_element_type=jnp.float32)
          + diag * do)

    # --- dk / dr ---
    dkadv = jax.lax.dot_general(v, g, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # v·Gᵀ
    dk = dk_att + dkadv * e_adv + u * r * dov
    dr = dr_att + drdec * e_lprev + u * k * dov
    du_scr[...] += jnp.sum(r * k * dov, axis=0, keepdims=True)

    # --- decay gradients via the cumsum adjoint ---
    # w -> L = cumsum(w) -> {lprev = L - w, l_end = L[-1]}
    dlprev = drdec * rdec + dlprev_pair
    dl_end = (jnp.sum(dkadv * kadv, axis=0, keepdims=True)
              + jnp.exp(l_end) * jnp.sum((state * g).T, axis=0,
                                         keepdims=True))
    last_row = (jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
                == chunk - 1)
    dL_tot = (dL_pair - dkadv * kadv + dlprev
              + jnp.where(last_row, dl_end, 0.0))
    # reverse cumsum: dw_t = Σ_{j>=t} dL_j, minus the direct -w term of lprev
    rev = _cumsum_rows(dL_tot, chunk, reverse=True)
    dw = rev - dlprev

    dr_ref[0, 0, 0] = dr.astype(dr_ref.dtype)
    dk_ref[0, 0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, 0] = dv.astype(dv_ref.dtype)
    dw_ref[0, 0, 0] = dw.astype(dw_ref.dtype)
    g_scr[...] = ds_in

    @pl.when(ci == num_chunks - 1)
    def _final():
        ds0_ref[0, 0] = g_scr[...].astype(ds0_ref.dtype)
        du_ref[0, 0] = du_scr[...].astype(du_ref.dtype)


def _backward(spec, r, k, v, wlog, u, s0, states, do, ds_end):
    b, s, h, p = r.shape
    cs = spec.chunk
    nc = s // cs

    rc, kc, vc, wc, doc = (_to_chunked(x, b, nc, cs, h, p)
                           for x in (r, k, v, wlog, do))

    def rev_map(bb, hh, ci):
        return (bb, hh, nc - 1 - ci, 0, 0)

    def u_map(bb, hh, ci):
        return (hh, 0, 0)

    def pp_map(bb, hh, ci):
        return (bb, hh, 0, 0)

    def states_map(bb, hh, ci):
        return (bb, hh, nc - 1 - ci, 0, 0)

    dr, dk, dv, dw, ds0, du_bh = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=cs, num_chunks=nc),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, p), u_map),
            pl.BlockSpec((1, 1, 1, p, p), states_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, p, p), pp_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, 1, cs, p), rev_map),
            pl.BlockSpec((1, 1, p, p), pp_map),
            pl.BlockSpec((1, 1, 1, p), lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, cs, p), r.dtype),
            jax.ShapeDtypeStruct((b, h, nc, cs, p), k.dtype),
            jax.ShapeDtypeStruct((b, h, nc, cs, p), v.dtype),
            jax.ShapeDtypeStruct((b, h, nc, cs, p), wlog.dtype),
            jax.ShapeDtypeStruct((b, h, p, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((p, p), jnp.float32),
            pltpu.VMEM((1, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
    )(rc, kc, vc, wc, u.reshape(h, 1, p), states, doc, ds_end)

    dr, dk, dv, dw = (_from_chunked(x, b, s, h, p)
                      for x in (dr, dk, dv, dw))
    du = jnp.sum(du_bh, axis=0)[:, 0]              # fold batch outside
    return dr, dk, dv, dw, du, ds0


# ---------------------------------------------------------------------------
# custom VJP plumbing (shared kernels.vjp harness)
# ---------------------------------------------------------------------------

def _wkv_primal(spec, r, k, v, wlog, u, s0):
    o, s_end, _ = _forward(spec, r, k, v, wlog, u, s0, with_states=False)
    return o, s_end


def _wkv_fwd(spec, r, k, v, wlog, u, s0):
    o, s_end, states = _forward(spec, r, k, v, wlog, u, s0,
                                with_states=True)
    return (o, s_end), (r, k, v, wlog, u, s0, states)


def _wkv_bwd(spec, res, ct):
    r, k, v, wlog, u, s0, states = res
    do, ds_end = ct
    dr, dk, dv, dw, du, ds0 = _backward(
        spec, r, k, v, wlog, u, s0, states,
        do.astype(jnp.float32), ds_end.astype(jnp.float32))
    return vjp.cast_grads_like((dr, dk, dv, dw, du, ds0),
                               (r, k, v, wlog, u, s0))


_wkv = vjp.differentiable(_wkv_fwd, _wkv_bwd, primal=_wkv_primal)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked_kernel(r, k, v, wlog, u, s0, *, chunk=32, interpret=False):
    """r/k/v/wlog (B, S, H, P); u (H, P); s0 (B, H, P, P).
    Returns (o (B,S,H,P) f32, s_end (B,H,P,P) f32). S % chunk must be 0
    (ops.py pads). Differentiable: custom VJP, Pallas backward kernel."""
    spec = _Spec(int(chunk), bool(interpret))
    return _wkv(spec, r, k, v, wlog, u, s0)
