"""Numerical equivalence tests for the model-math building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import mamba2 as m2
from repro.models.blockwise import blockwise_attention
from repro.models.rwkv6 import wkv6_chunked
from repro.kernels.ref import ref_attention, ref_wkv6

KEY = jax.random.PRNGKey(7)


def _mamba_sequential_ref(xh, bmat, cmat, dt, a_log, h0):
    """Definitional per-step SSD recurrence."""
    f32 = jnp.float32
    xh, bmat, cmat, dt = (t.astype(f32) for t in (xh, bmat, cmat, dt))
    A = -jnp.exp(a_log.astype(f32))

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        a_t = jnp.exp(dt_t * A)                     # (B,H)
        h = a_t[..., None, None] * h + jnp.einsum(
            "bhp,bn->bhpn", x_t * dt_t[..., None], b_t)
        y = jnp.einsum("bhpn,bn->bhp", h, c_t)
        return h, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xh, bmat, cmat, dt))
    h_end, ys = jax.lax.scan(step, h0.astype(f32), xs)
    return jnp.moveaxis(ys, 0, 1), h_end


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 32), (32, 32)])
def test_mamba2_chunked_equals_sequential(s, chunk):
    b, h, p, n = 2, 3, 8, 4
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (b, s, h, p))
    bmat = jax.random.normal(ks[1], (b, s, n))
    cmat = jax.random.normal(ks[2], (b, s, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a_log = jax.random.normal(ks[4], (h,)) * 0.3
    h0 = jnp.zeros((b, h, p, n))
    y_c, h_c = m2._ssd_chunk_scan(xh, bmat, cmat, dt, a_log, chunk, h0)
    y_r, h_r = _mamba_sequential_ref(xh, bmat, cmat, dt, a_log, h0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_r), atol=2e-4)


def test_mamba2_chunked_carries_state():
    """Splitting a sequence across two chunked calls == one call."""
    b, s, h, p, n = 1, 64, 2, 8, 4
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (b, s, h, p))
    bmat = jax.random.normal(ks[1], (b, s, n))
    cmat = jax.random.normal(ks[2], (b, s, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a_log = jax.random.normal(ks[4], (h,)) * 0.3
    h0 = jnp.zeros((b, h, p, n))
    y_full, h_full = m2._ssd_chunk_scan(xh, bmat, cmat, dt, a_log, 16, h0)
    y1, h_mid = m2._ssd_chunk_scan(xh[:, :32], bmat[:, :32], cmat[:, :32],
                                   dt[:, :32], a_log, 16, h0)
    y2, h_end = m2._ssd_chunk_scan(xh[:, 32:], bmat[:, 32:], cmat[:, 32:],
                                   dt[:, 32:], a_log, 16, h_mid)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_end), np.asarray(h_full),
                               atol=2e-4)


@pytest.mark.parametrize("s,chunk", [(64, 16), (100, 32)])
def test_wkv6_jnp_chunked_vs_sequential(s, chunk):
    b, h, p = 2, 2, 16
    ks = jax.random.split(KEY, 6)
    r, k, v = (jax.random.normal(ks[i], (b, s, h, p)) for i in range(3))
    wlog = -jnp.exp(jax.random.normal(ks[3], (b, s, h, p)) - 0.5)
    u = 0.3 * jax.random.normal(ks[4], (h, p))
    s0 = 0.1 * jax.random.normal(ks[5], (b, h, p, p))
    o_c, s_c = wkv6_chunked(r, k, v, wlog, u, chunk, s0)
    o_r, s_r = ref_wkv6(r, k, v, wlog, u, s0)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_r), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r), atol=5e-4)


@pytest.mark.parametrize("causal,window,bk", [
    (True, 0, 64), (True, 32, 32), (False, 0, 128), (True, 0, 48),
])
def test_blockwise_attention_fwd_bwd(causal, window, bk):
    b, s, h, kh, d = 2, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))

    def f_block(q, k, v):
        return jnp.sum(jnp.sin(
            blockwise_attention(q, k, v, window, causal=causal, block_k=bk)))

    def f_ref(q, k, v):
        o = ref_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal,
                          window=window)
        return jnp.sum(jnp.sin(o.transpose(0, 2, 1, 3)))

    np.testing.assert_allclose(float(f_block(q, k, v)), float(f_ref(q, k, v)),
                               rtol=1e-5)
    g1 = jax.grad(f_block, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_gemma_window_pattern_affects_logits():
    """Sliding window must actually mask: full-window vs tiny-window logits
    differ for long-range tokens."""
    from repro.models import transformer as model
    cfg = get_smoke_config("gemma3-12b").replace(dtype="float32")
    params = model.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 64), 0, cfg.vocab_size)
    l1, _, _ = model.forward(cfg, params, {"tokens": toks}, mode="train")
    cfg2 = cfg.replace(sliding_window=4)
    l2, _, _ = model.forward(cfg2, params, {"tokens": toks}, mode="train")
    assert float(jnp.max(jnp.abs(l1 - l2))) > 1e-3


def test_mla_latent_cache_is_compressed():
    """The MLA cache must be (kv_lora + rope) wide, not H*(nope+v)."""
    from repro.models import transformer as model
    cfg = get_smoke_config("deepseek-v3-671b")
    cache = jax.eval_shape(
        lambda: model.init_cache(cfg, 2, 32, jnp.bfloat16))
    moe_c = cache["moe"]
    assert moe_c["c_kv"].shape[-1] == cfg.mla.kv_lora_rank
    assert moe_c["k_rope"].shape[-1] == cfg.mla.qk_rope_head_dim
    from repro.configs import get_config
    full_cfg = get_config("deepseek-v3-671b")
    full_kv_width = full_cfg.num_heads * (full_cfg.mla.qk_nope_head_dim
                                          + full_cfg.mla.v_head_dim)
    latent_width = full_cfg.mla.kv_lora_rank + full_cfg.mla.qk_rope_head_dim
    assert full_kv_width / latent_width > 50   # the ~57x saving


# ---------------------------------------------------------------------------
# GELU and LayerNorm custom VJPs: the forward is the plain expression, the
# backward keeps only what it needs (the input, and LayerNorm's row stats)
# ---------------------------------------------------------------------------

def _plain_layernorm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _ln_args(dtype, shape=(3, 5, 64)):
    ks = jax.random.split(KEY, 3)
    x = (2.0 * jax.random.normal(ks[0], shape) + 0.5).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], shape[-1:])
    bias = 0.1 * jax.random.normal(ks[2], shape[-1:])
    return x, scale, bias


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _plain_and_custom(name):
    from repro.models.mlp import gelu
    from repro.models.norms import layernorm
    if name == "layernorm":
        return (lambda x, s, b: _plain_layernorm(x, s, b, 1e-6),
                lambda x, s, b: layernorm(x, s, b, 1e-6))
    return lambda x, s, b: jax.nn.gelu(x), lambda x, s, b: gelu(x)


def _bits(a):
    return np.asarray(a).tobytes()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("name", ["layernorm", "gelu"])
def test_custom_vjp_forward_bit_equal(name, dtype):
    plain, custom = _plain_and_custom(name)
    args = _ln_args(dtype)
    got, want = jax.jit(custom)(*args), jax.jit(plain)(*args)
    assert got.dtype == want.dtype == dtype
    assert _bits(got) == _bits(want)
    # a differentiated call computes the same forward
    total = lambda f: lambda *a: f(*a).astype(jnp.float32).sum()
    value, _ = jax.jit(jax.value_and_grad(total(custom), (0, 1, 2)))(*args)
    assert _bits(value) == _bits(jax.jit(total(plain))(*args))


@pytest.mark.parametrize("name", ["layernorm", "gelu"])
def test_custom_vjp_grads_match_finite_differences(name):
    from jax.test_util import check_grads
    _, custom = _plain_and_custom(name)
    check_grads(custom, _ln_args(jnp.float32, shape=(4, 32)), order=1,
                modes=["rev"])


def _grad_gaps(loss_lo, loss_f32, args_lo, args_f32, argnums):
    """Per leaf, |low-precision grad - fp32 autodiff grad| / |fp32 grad|,
    for the custom VJPs and for plain autodiff at the same inputs."""
    from repro.models import mlp as mlp_mod
    from repro.models import transformer as tmod
    with jax.default_matmul_precision("highest"), \
            pytest.MonkeyPatch.context() as mp:
        mp.setattr(mlp_mod, "gelu", jax.nn.gelu)
        mp.setattr(tmod, "layernorm", _plain_layernorm)
        ref = jax.grad(loss_f32, argnums)(*args_f32)
        autodiff = jax.grad(loss_lo, argnums)(*args_lo)
    custom = jax.grad(loss_lo, argnums)(*args_lo)
    ref = jax.tree.leaves(ref)

    def gaps(g):
        return np.array([_rel(a, b) for a, b in zip(jax.tree.leaves(g), ref)])

    return gaps(custom), gaps(autodiff)


@pytest.mark.parametrize("act", ["gelu", "geglu"])
def test_mlp_bf16_grads_close_to_fp32_autodiff(act):
    from repro.models.mlp import init_mlp, mlp
    ks = jax.random.split(KEY, 3)
    p = init_mlp(ks[0], 64, 256, act)
    p = {k: v + 0.02 * jax.random.normal(ks[1], v.shape) for k, v in p.items()}
    x = jax.random.normal(ks[2], (4, 16, 64))
    w = jnp.cos(jnp.arange(64.0))

    def loss(p, x):
        return jnp.sum(mlp(p, x, act).astype(jnp.float32) * w)

    custom, autodiff = _grad_gaps(loss, loss, (p, x.astype(jnp.bfloat16)),
                                  (p, x), (0, 1))
    assert custom.max() < 3e-2, (custom, autodiff)
    assert np.all(custom <= 1.5 * autodiff + 1e-3), (custom, autodiff)


def test_vit_b16_bf16_grads_close_to_fp32_autodiff():
    """Two full-width vit-b16 layers at 64 px in bf16: the custom VJPs lose
    nothing against plain autodiff, both read against fp32 HIGHEST."""
    from repro.configs import get_config
    from repro.models import transformer as model
    cfg = get_config("vit-b16").replace(num_layers=2, image_size=64)
    params = model.init_params(cfg, KEY)
    batch = {"images": jax.random.normal(KEY, (4, 64, 64, 3)),
             "labels": jnp.arange(4) % cfg.num_classes}

    def loss_for(c):
        return lambda p: model.loss_fn(c, p, batch)[0]

    assert cfg.dtype == "bfloat16"
    custom, autodiff = _grad_gaps(
        loss_for(cfg), loss_for(cfg.replace(dtype="float32")),
        (params,), (params,), 0)
    assert custom.max() < 3e-2, (custom, autodiff)
    assert np.all(custom <= 1.5 * autodiff + 1e-3), (custom, autodiff)
