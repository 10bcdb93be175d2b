"""Multi-device integration tests (subprocess with 8 host devices):
DP loss equivalence across world sizes, ZeRO-stage equivalence, Ulysses SP
equivalence — the invariants behind the paper's scaling claims."""
import pytest

from conftest import run_subprocess

_COMMON = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import concrete_batch

def run_steps(arch, mesh_shape, zero, steps=3, seq_parallel="none",
              accum=1, model_axis_name="model"):
    mesh = make_local_mesh(model=mesh_shape[1],
                           devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    cfg = get_smoke_config(arch).replace(dtype="float32")
    ecfg = EngineConfig(train_batch_size=8, gradient_accumulation_steps=accum,
                        zero_stage=zero, lr=1e-3, total_steps=10,
                        warmup_steps=1, sequence_parallel=seq_parallel)
    eng = DistributedEngine(cfg, ecfg, mesh)
    state = eng.init_state(seed=0)
    step = eng.jit_train_step(donate=False)
    losses = []
    with mesh:
        for i in range(steps):
            batch = concrete_batch(cfg, 8, 32, seed=i)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return losses
"""


@pytest.mark.slow
def test_dp_world_size_invariance():
    """Same global batch -> identical loss trajectory on 1, 2, 8 devices
    (the correctness property behind strong scaling)."""
    out = run_subprocess(_COMMON + r"""
l1 = run_steps("qwen2.5-14b", (1, 1), 0)
l2 = run_steps("qwen2.5-14b", (2, 1), 0)
l8 = run_steps("qwen2.5-14b", (8, 1), 0)
for a, b in zip(l1, l2):
    assert abs(a - b) < 2e-4, (l1, l2)
for a, b in zip(l1, l8):
    assert abs(a - b) < 2e-4, (l1, l8)
print("OK", l1)
""")
    assert "OK" in out


@pytest.mark.slow
def test_zero_stage_equivalence():
    """ZeRO stages change sharding, not math: identical losses 0 vs 1 vs 3."""
    out = run_subprocess(_COMMON + r"""
base = run_steps("granite-moe-3b-a800m", (4, 2), 0)
for z in (1, 3):
    lz = run_steps("granite-moe-3b-a800m", (4, 2), z)
    for a, b in zip(base, lz):
        assert abs(a - b) < 3e-4, (z, base, lz)
print("OK", base)
""")
    assert "OK" in out


@pytest.mark.slow
def test_grad_accum_invariance_distributed():
    """accum x micro == one big batch on a real mesh."""
    out = run_subprocess(_COMMON + r"""
l1 = run_steps("glm4-9b", (4, 2), 3, accum=1)
l2 = run_steps("glm4-9b", (4, 2), 3, accum=2)   # 8 = 1 x 2 x dp4
for a, b in zip(l1, l2):
    assert abs(a - b) < 3e-4, (l1, l2)
print("OK", l1)
""")
    assert "OK" in out


@pytest.mark.slow
def test_ulysses_sequence_parallel_equivalence():
    """Ulysses SP is a layout change: logits must match non-SP run."""
    out = run_subprocess(_COMMON + r"""
la = run_steps("qwen2.5-14b", (2, 4), 3, seq_parallel="none")
lb = run_steps("qwen2.5-14b", (2, 4), 3, seq_parallel="ulysses")
for a, b in zip(la, lb):
    assert abs(a - b) < 3e-4, (la, lb)
print("OK", la)
""")
    assert "OK" in out


@pytest.mark.slow
def test_tensor_parallel_equivalence():
    """model-axis sharding is math-preserving."""
    out = run_subprocess(_COMMON + r"""
la = run_steps("zamba2-2.7b", (8, 1), 0)
lb = run_steps("zamba2-2.7b", (2, 4), 0)
for a, b in zip(la, lb):
    assert abs(a - b) < 3e-4, (la, lb)
print("OK", la)
""")
    assert "OK" in out


@pytest.mark.slow
def test_decode_sharded_cache():
    """Sharded-cache decode on a mesh == single-device decode."""
    out = run_subprocess(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as model

cfg = get_smoke_config("qwen2.5-14b").replace(dtype="float32")
params = model.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 40), 0, cfg.vocab_size)
ref, _, _ = model.forward(cfg, params, {"tokens": toks}, mode="train")

mesh = make_local_mesh(model=4)
eng = DistributedEngine(cfg, EngineConfig(train_batch_size=8), mesh)
with mesh:
    cache = model.init_cache(cfg, 4, 40, jnp.float32)
    cshapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
    prefill = eng.jit_prefill({"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}, cshapes)
    decode = eng.jit_decode_step(cshapes, donate=False)
    last, cache = prefill(params, {"tokens": toks[:, :32]}, cache)
    errs = []
    for i in range(8):
        tok = toks[:, 32 + i:33 + i]
        logits_tok, cache = decode(params, cache, tok, jnp.int32(32 + i))
    print("OK decode ran under sharded cache")
""")
    assert "OK" in out
