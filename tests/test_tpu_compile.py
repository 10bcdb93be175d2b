"""Compile the train-path Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib, so it can compile for a v5e that is
described, not attached. Interpret mode (every other kernel test) checks
the math but not Mosaic's tiling and layout rules; these compiles do, at
the real widths: vit-b16 attention (S=197, H=12, D=64, bf16) with the
config's tiles, rmsnorm at d_model 768, and wkv6 at rwkv6-7b's heads.
The vit-b16 gradient is compiled whole to read what its layer scan keeps
for the backward.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and pytest-xdist workers import
every test file. Keep all such compiles in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import vjp
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.kernels.wkv6 import wkv6_chunked_kernel
from repro.models import transformer as model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash_case(grad):
    cfg = get_config("vit-b16")
    bq, bk = vjp.attn_blocks(cfg)
    s = (cfg.image_size // cfg.patch_size) ** 2 + 1        # 197 with cls
    shape = (32, cfg.num_heads, s, cfg.head_dim)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=False, block_q=bq,
                               block_k=bk)

    fn = fwd if not grad else jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    return fn, [(shape, jnp.bfloat16)] * 3


def _rmsnorm_case(grad):
    cfg = get_config("vit-b16")
    rows, d = 32 * 197, cfg.d_model

    def fwd(x, scale):
        return fused_rmsnorm(x, scale, block_rows=cfg.norm_block_rows)

    fn = fwd if not grad else jax.grad(
        lambda x, s: fwd(x, s).astype(jnp.float32).sum(), argnums=(0, 1))
    return fn, [((rows, d), jnp.bfloat16), ((d,), jnp.bfloat16)]


def _wkv6_case(grad):
    cfg = get_config("rwkv6-7b")
    b, s, h, p = 2, 256, cfg.num_heads, cfg.ssm.head_dim
    chunk = vjp.wkv_chunk(cfg)

    def fwd(*args):
        return wkv6_chunked_kernel(*args, chunk=chunk)

    def loss(*args):
        o, s_end = fwd(*args)
        return o.sum() + s_end.sum()

    fn = fwd if not grad else jax.grad(loss, argnums=tuple(range(6)))
    return fn, [((b, s, h, p), jnp.bfloat16)] * 3 + [
        ((b, s, h, p), jnp.float32), ((h, p), jnp.float32),
        ((b, h, p, p), jnp.float32)]


CASES = {"flash": _flash_case, "rmsnorm": _rmsnorm_case,
         "wkv6": _wkv6_case}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, grad, one_chip,
                                 no_persistent_cache):
    fn, args = CASES[kernel](grad)
    shapes = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
              for shape, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    # the Mosaic kernel is in the program (no interpret-mode fallback)
    assert "tpu_custom_call" in compiled.as_text()


def _forward_scan_stacks(hlo_text, layers):
    """(dtype, shape) of every per-layer stack the forward layer scan
    carries: the shapes with a leading ``layers`` axis in the tuple of the
    one ``while`` whose op_name is the forward's (jvp, not transposed)."""
    loops = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = \((.*?)\) while\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and "jvp(" in name.group(1) \
                and "transpose(" not in name.group(1):
            loops.append(m.group(1))
    assert len(loops) == 1, loops
    return [(dt, tuple(int(d) for d in dims.split(",")))
            for dt, dims in re.findall(r"(\w+)\[([\d,]+)\]", loops[0])
            if dims.startswith(f"{layers},")]


def test_vit_b16_layer_scan_keeps_only_needed_residuals(one_chip,
                                                        no_persistent_cache):
    """GELU keeps its input and LayerNorm its input and row stats: no fp32
    (L, B, S, d_model) stack, and of (L, B, S, d_ff) only the GELU input
    and output (autodiff alone keeps six of those and six fp32 rows)."""
    cfg = get_config("vit-b16")
    b, px, layers = 8, cfg.image_size, cfg.num_layers
    s = (px // cfg.patch_size) ** 2 + 1
    params = jax.eval_shape(lambda: model.init_params(cfg, jax.random.key(0)))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    args = (jax.tree.map(lambda a: spec(a.shape, a.dtype), params),
            {"images": spec((b, px, px, 3), jnp.bfloat16),
             "labels": spec((b,), jnp.int32)})
    grad = jax.jit(jax.grad(lambda p, x: model.loss_fn(cfg, p, x)[0]))
    stacks = _forward_scan_stacks(grad.lower(*args).compile().as_text(),
                                  layers)
    rows = [(dt, sh[-1]) for dt, sh in stacks if sh[1:3] == (b, s)]
    assert rows, stacks
    assert ("f32", cfg.d_model) not in rows, stacks
    assert sum(d == cfg.d_ff for _, d in rows) <= 2, stacks
