"""Attention: MHA/GQA (+bias, sliding window, softcap, M-RoPE) and
DeepSeek-style MLA with latent KV cache (absorbed decode path).

All functions operate on (B, S, H, hd) tensors; per-layer params are plain
dicts so they stack along a leading L axis for scan-over-layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import shardctx
from repro.models.norms import rmsnorm
from repro.models.params import dense_init, zeros
from repro.models.rope import apply_rope, apply_rope_1d

NEG_INF = -2.0 ** 30   # finite: keeps fully-masked rows NaN-free


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg):
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd)),
        "wk": dense_init(ks[1], (d, kh * hd)),
        "wv": dense_init(ks[2], (d, kh * hd)),
        "wo": dense_init(ks[3], (h * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((h * hd,))
        p["bk"] = zeros((kh * hd,))
        p["bv"] = zeros((kh * hd,))
    return p


def init_mla(key, cfg):
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank)),
        "q_norm": jnp.ones((m.q_lora_rank,)),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, h * qk)),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "kv_norm": jnp.ones((m.kv_lora_rank,)),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim)),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, h * m.v_head_dim)),
        "wo": dense_init(ks[5], (h * m.v_head_dim, d)),
    }


# ---------------------------------------------------------------------------
# scaled dot-product attention (GQA-grouped, fp32 softmax)
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, *, causal, window, kv_len_valid=None):
    """Boolean (.., S, T) mask. ``window`` may be a traced scalar (0=full)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    win_ok = jnp.where(window > 0, (qp - kp) < window, True)
    ok &= win_ok
    if kv_len_valid is not None:
        ok &= kp < kv_len_valid
    return ok


def sdpa(q, k, v, mask, *, softcap=0.0):
    """q (B,S,H,hd), k/v (B,T,KH,hd), mask broadcastable to (B,1,1,S,T).
    GQA grouping is internal. fp32 accumulation."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    if mask is not None:
        scores = jnp.where(mask[:, None, None] if mask.ndim == 3
                           else mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, v.shape[-1])    # v head dim may differ (MLA)


# ---------------------------------------------------------------------------
# full GQA attention block (project → rope → sdpa → out)
# ---------------------------------------------------------------------------

def attention_block(p, x, cfg, *, positions, window, cache=None,
                    cache_index=None, layer_slot=None):
    """Returns (out, new_layer_cache).

    cache (for this layer): {"k": (B, Smax, KH, hd), "v": ...} or None.
    cache_index: traced scalar — current length (decode) / 0 (prefill).
    """
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    hints = shardctx.get()

    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)

    if cfg.rope_style != "none":
        q, k = apply_rope(q, k, positions, style=cfg.rope_style,
                          theta=cfg.rope_theta, sections=cfg.mrope_sections)

    new_cache = None
    if cache is not None:
        # decode writes one slot at cache_index; prefill writes the block at
        # position 0 (the causal mask hides the unwritten tail).
        idx = cache_index if s == 1 else 0
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        new_cache = {"k": kc, "v": vc}
        k, v = kc, vc

    # TP/Ulysses resharding (no-op unless hints installed; decode layouts
    # come from the cache shardings instead)
    if s > 1:
        q = shardctx.constrain(q, hints.attn_q)
        k = shardctx.constrain(k, hints.attn_kv)
        v = shardctx.constrain(v, hints.attn_kv)

    t = k.shape[1]
    # the core alone, without the projections (repro/obs.py)
    with jax.named_scope(obs.ATTN_CORE):
        if cache is not None and s == 1:
            # decode: query sits at `cache_index`; valid keys are <= it, within
            # the sliding window when one is set.
            k_pos = jnp.arange(t)
            mask = k_pos <= cache_index
            mask &= jnp.where(window > 0, (cache_index - k_pos) < window, True)
            mask = mask[None, None, None, None]                # (1,1,1,1,T)
            out = sdpa(q, k, v, mask, softcap=cfg.attn_logit_softcap)
        elif cfg.use_pallas and cfg.attn_logit_softcap == 0.0:
            # flash kernel: causal/window masks are positional -> in-kernel;
            # train gradients route through the kernel's custom VJP (Pallas
            # backward passes), so this is the differentiable hot path. Block
            # sizes resolve from cfg inside the ops dispatch layer.
            from repro.kernels.ops import flash_mha
            out = flash_mha(q, k, v, causal=cfg.causal, window=window, cfg=cfg)
        elif cfg.attn_impl == "blockwise" and cfg.attn_logit_softcap == 0.0:
            from repro.models.blockwise import blockwise_attention_qchunked
            out = blockwise_attention_qchunked(q, k, v, window,
                                               causal=cfg.causal,
                                               block_k=cfg.attn_block_k,
                                               block_q=cfg.attn_block_q)
        else:
            q_pos = jnp.arange(s)[None]
            k_pos = jnp.arange(t)[None]
            mask = _mask(q_pos, k_pos, causal=cfg.causal,
                         window=window)[:, None, None]         # (1,1,1,S,T)
            out = sdpa(q, k, v, mask, softcap=cfg.attn_logit_softcap)
    if s > 1:
        out = shardctx.constrain(out, hints.attn_seq)
    out = out.reshape(b, s, h * hd) @ p["wo"].astype(x.dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): latent-compressed KV, absorbed decode
# ---------------------------------------------------------------------------

def mla_block(p, x, cfg, *, positions, cache=None, cache_index=None):
    """Returns (out, new_layer_cache). Cache stores the COMPRESSED latent
    c_kv (B, Smax, kv_lora) + shared rope key (B, Smax, rope_dim) — the MLA
    memory saving (vs. per-head K/V) is num_heads*(nope+v)/(kv_lora+rope)
    ≈ 128*256/576 ≈ 57x."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    cq = rmsnorm(x @ p["w_dq"].astype(x.dtype), p["q_norm"], cfg.norm_eps,
                 use_pallas=cfg.use_pallas, block_rows=cfg.norm_block_rows)
    q = (cq @ p["w_uq"].astype(x.dtype)).reshape(b, s, h, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = x @ p["w_dkv"].astype(x.dtype)
    c_kv = rmsnorm(dkv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps,
                   use_pallas=cfg.use_pallas, block_rows=cfg.norm_block_rows)
    k_rope = dkv[..., m.kv_lora_rank:][:, :, None]         # (B,S,1,rope)

    q_rope, _ = apply_rope(q_rope, q_rope, positions, style="full",
                           theta=cfg.rope_theta)
    k_rope = apply_rope_1d(k_rope, positions, theta=cfg.rope_theta)[:, :, 0]

    new_cache = None
    if cache is not None:
        idx = cache_index if s == 1 else 0
        ckv_c = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, idx, 0))
        kr_c = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
            (0, idx, 0))
        new_cache = {"c_kv": ckv_c, "k_rope": kr_c}
        c_kv, k_rope = ckv_c, kr_c

    scale = (nope + rope_d) ** -0.5
    t = c_kv.shape[1]

    if cache is not None and s == 1:
        # ---- absorbed decode: never materialize per-head K/V ----
        w_uk = p["w_uk"].astype(x.dtype).reshape(m.kv_lora_rank, h, nope)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)   # (B,1,H,kv_lora)
        with jax.named_scope(obs.ATTN_CORE):
            scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshd,btd->bhst", q_rope, k_rope,
                                   preferred_element_type=jnp.float32)
                      ) * scale
            valid = jnp.arange(t)[None, None, None, :] <= cache_index
            scores = jnp.where(valid, scores, NEG_INF)
            w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            ctx = jnp.einsum("bhst,btr->bshr", w, c_kv)  # (B,1,H,kv_lora)
        w_uv = p["w_uv"].astype(x.dtype).reshape(m.kv_lora_rank, h, vd)
        out = jnp.einsum("bshr,rhd->bshd", ctx, w_uv)
    else:
        # ---- train/prefill: materialize K/V from latent ----
        k_nope = (c_kv @ p["w_uk"].astype(x.dtype)).reshape(b, t, h, nope)
        v = (c_kv @ p["w_uv"].astype(x.dtype)).reshape(b, t, h, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                      (b, t, h, rope_d))], -1)
        qfull = jnp.concatenate([q_nope, q_rope], -1)
        with jax.named_scope(obs.ATTN_CORE):
            if cfg.use_pallas:
                from repro.kernels.ops import flash_mha
                out = flash_mha(qfull, k, v, causal=True, window=0, cfg=cfg)
            elif cfg.attn_impl == "blockwise":
                from repro.models.blockwise import \
                    blockwise_attention_qchunked
                out = blockwise_attention_qchunked(
                    qfull, k, v, 0, causal=True, block_k=cfg.attn_block_k,
                    block_q=cfg.attn_block_q)
            else:
                q_pos = jnp.arange(s)[None]
                k_pos = jnp.arange(t)[None]
                mask = _mask(q_pos, k_pos, causal=True,
                             window=0)[:, None, None]
                out = sdpa(qfull, k, v, mask)

    out = out.reshape(b, s, h * vd) @ p["wo"].astype(x.dtype)
    return out, new_cache
