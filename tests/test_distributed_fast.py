"""Fast-lane distributed-invariant tests: tiny-shape (2-layer smoke
configs, 4 host devices) variants of the @slow integration invariants in
test_engine_distributed.py, cheap enough for CI's every-push fast job —
DP world-size invariance, ZeRO 0/1/3 equivalence, and pp=2 vs dp-only
loss-trajectory parity. The parallelism-correctness contract is enforced
on every push, not just nightly."""
from conftest import run_subprocess

_COMMON = r"""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import concrete_batch

def run_steps(arch, mesh_shape, zero=0, steps=2, accum=2, pipe=1):
    n = mesh_shape[0] * mesh_shape[1] * pipe
    mesh = make_local_mesh(model=mesh_shape[1], pipe=pipe,
                           devices=jax.devices()[:n])
    cfg = get_smoke_config(arch).replace(dtype="float32")
    ecfg = EngineConfig(train_batch_size=8, gradient_accumulation_steps=accum,
                        zero_stage=zero, lr=1e-3, total_steps=10,
                        warmup_steps=1, pipeline_stages=pipe)
    eng = DistributedEngine(cfg, ecfg, mesh)
    state = eng.init_state(seed=0)
    step = eng.jit_train_step(donate=False)
    losses = []
    with mesh:
        for i in range(steps):
            batch = concrete_batch(cfg, 8, 16, seed=i)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return losses
"""


def test_dp_world_size_invariance_fast():
    """Same global batch -> same loss trajectory on 1 vs 4 dp devices."""
    out = run_subprocess(_COMMON + r"""
l1 = run_steps("vit-b16", (1, 1))
l4 = run_steps("vit-b16", (4, 1))
for a, b in zip(l1, l4):
    assert abs(a - b) < 2e-4, (l1, l4)
print("OK", l1)
""", devices=4, timeout=900)
    assert "OK" in out


def test_zero_stage_equivalence_fast():
    """ZeRO 0/1/3 change sharding, not math (dp2 x tp2)."""
    out = run_subprocess(_COMMON + r"""
base = run_steps("qwen2.5-14b", (2, 2))
for z in (1, 3):
    lz = run_steps("qwen2.5-14b", (2, 2), zero=z)
    for a, b in zip(base, lz):
        assert abs(a - b) < 3e-4, (z, base, lz)
print("OK", base)
""", devices=4, timeout=900)
    assert "OK" in out


def test_pp2_vs_dp_parity_fast():
    """pp=2 (dp2 x pipe2) reproduces the dp-only trajectory — the 1F1B
    pipeline is a schedule change, not a math change."""
    out = run_subprocess(_COMMON + r"""
base = run_steps("vit-b16", (4, 1))
lp = run_steps("vit-b16", (2, 1), pipe=2)
for a, b in zip(base, lp):
    assert abs(a - b) < 3e-4, (base, lp)
print("OK", base)
""", devices=4, timeout=900)
    assert "OK" in out


# ---------------------------------------------------------------------------
# elastic checkpointing (repro.checkpoint): shard-local save + cross-layout
# restore + resume parity, in the fast lane
# ---------------------------------------------------------------------------

_CKPT = r"""
import json, os, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
from repro.checkpoint import checkpoint_size_report
from repro.launch.specs import concrete_batch

CFG = get_smoke_config("vit-b16").replace(dtype="float32")

def make_engine(zero=0, pipe=1):
    mesh = make_local_mesh(pipe=pipe, devices=jax.devices()[:4])
    ecfg = EngineConfig(train_batch_size=8, gradient_accumulation_steps=2,
                        zero_stage=zero, lr=1e-3, total_steps=10,
                        warmup_steps=1, pipeline_stages=pipe)
    return DistributedEngine(CFG, ecfg, mesh)

def run(eng, state, lo, hi):
    step = eng.jit_train_step(donate=False)
    losses = []
    with eng.mesh:
        for i in range(lo, hi):
            state, m = step(state, concrete_batch(CFG, 8, 16, seed=i))
            losses.append(float(m["loss"]))
    return state, losses

def assert_bitwise(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    for (pa, xa), (_, xb) in zip(fa, fb):
        assert np.array_equal(np.asarray(jax.device_get(xa)),
                              np.asarray(jax.device_get(xb))), pa
"""


def test_elastic_restore_from_zero3_fast():
    """Save under dp=4 ZeRO-3; restore into dp2 x pp2 AND into dp4 DDP:
    bitwise param/opt equality, then 3 resumed steps match the
    uninterrupted source-layout trajectory within 1e-5. The size report
    proves the save was shard-local (saved bytes == logical bytes — no
    hidden all-gather, no replica written twice — and ZeRO-3 spreads the
    bytes over all 4 devices)."""
    out = run_subprocess(_CKPT + r"""
src = make_engine(zero=3)
s3, _ = run(src, src.init_state(seed=0), 0, 3)
d = tempfile.mkdtemp()
src.save_state(d, s3)

rep = checkpoint_size_report(d, 3)
assert rep["saved_bytes"] == rep["logical_bytes"], rep
shard_bytes = sum(v for k, v in rep["file_bytes"].items()
                  if k.endswith(".npz"))
assert shard_bytes <= rep["saved_bytes"] * 1.05 + 65536, rep
per_dev = rep["per_device_bytes"]
assert len(per_dev) == 4, per_dev
assert max(per_dev.values()) < 0.5 * rep["saved_bytes"], per_dev

# the manifest records the ZeRO-3 dp sharding the leaves were saved under
man = json.load(open(os.path.join(d, "step_00000003", "manifest.json")))
specs = [m["spec"] for k, m in man["leaves"].items()
         if k.startswith("params/stack/")]
assert any(s and "data" in str(s) for s in specs), specs[:4]

_, ref = run(src, s3, 3, 6)                # uninterrupted continuation
for eng2 in (make_engine(pipe=2), make_engine(zero=0)):
    s2 = eng2.restore_state(d)
    assert int(s2.step) == 3
    assert_bitwise(s3.params, s2.params)
    assert_bitwise(s3.opt_state, s2.opt_state)
    _, res = run(eng2, s2, 3, 6)
    for a, b in zip(ref, res):
        assert abs(a - b) < 1e-5, (ref, res)
print("OK", ref)
""", devices=4, timeout=900)
    assert "OK" in out


def test_elastic_restore_from_pp2_fast():
    """Save under pp=2 (stacked-layer L axis sharded over `pipe`); restore
    into dp-only ZeRO-1 — the pipe-sharded stack reassembles into plain dp
    layouts and the trajectory continues within 1e-5."""
    out = run_subprocess(_CKPT + r"""
src = make_engine(pipe=2)
s3, _ = run(src, src.init_state(seed=0), 0, 3)
d = tempfile.mkdtemp()
src.save_state(d, s3)
man = json.load(open(os.path.join(d, "step_00000003", "manifest.json")))
specs = [m["spec"] for k, m in man["leaves"].items()
         if k.startswith("params/stack/")]
assert any(s and "pipe" in str(s) for s in specs), specs[:4]

_, ref = run(src, s3, 3, 6)
eng2 = make_engine(zero=1)
s2 = eng2.restore_state(d)
assert_bitwise(s3.params, s2.params)
assert_bitwise(s3.opt_state, s2.opt_state)
_, res = run(eng2, s2, 3, 6)
for a, b in zip(ref, res):
    assert abs(a - b) < 1e-5, (ref, res)
print("OK", ref)
""", devices=4, timeout=900)
    assert "OK" in out


# ---------------------------------------------------------------------------
# sharded evaluation (core/engine.py evaluate): layout-invariance of the
# integer metric counts + augmented-training resume parity
# ---------------------------------------------------------------------------

_EVAL = r"""
import jax, numpy as np
from repro.configs import get_smoke_config, EngineConfig
from repro.core.engine import DistributedEngine
from repro.launch.mesh import make_local_mesh
from repro.data import AugmentConfig, CIFARSource, DataPipeline

CFG = get_smoke_config("vit-b16").replace(dtype="float32")
EVAL_SIZE = 52      # 52 % 8 != 0 -> the final eval batch is mask-padded

def source():
    return CIFARSource("cifar10", seed=3, eval_size=EVAL_SIZE)

def make_engine(dp, pipe=1, zero=0, aug=None):
    mesh = make_local_mesh(pipe=pipe, devices=jax.devices()[:dp * pipe])
    ecfg = EngineConfig(train_batch_size=8, gradient_accumulation_steps=2,
                        zero_stage=zero, lr=1e-3, total_steps=10,
                        warmup_steps=1, pipeline_stages=pipe)
    # preproc: the source ships uint8 — the jitted steps normalize/upsample
    return DistributedEngine(CFG, ecfg, mesh, aug=aug,
                             preproc=source().preproc)
"""


def test_eval_counts_layout_invariant_fast():
    """Top-1/top-5 correct counts over a fixed procedural CIFAR split are
    *bitwise-identical integers* across dp1, dp4, and dp2 x pp2 — the
    integer all-reduce makes eval accuracy exactly layout-independent —
    including the mask-padded non-divisible final batch (52 = 6 x 8 + 4).
    The fp32 NLL sum only agrees to reduction-order tolerance."""
    out = run_subprocess(_EVAL + r"""
src = source()
assert src.num_eval_batches(8) * 8 > src.eval_size   # padding exercised

results = []
for dp, pp in ((1, 1), (4, 1), (2, 2)):
    eng = make_engine(dp, pipe=pp)
    state = eng.init_state(seed=0)
    results.append(eng.evaluate(state, src.eval_batches(8)))

base = results[0]
assert base["eval_count"] == EVAL_SIZE, base            # mask excluded pads
assert 0 < base["eval_top5_count"] <= EVAL_SIZE, base
assert base["eval_top1_count"] <= base["eval_top5_count"], base
for res in results[1:]:
    for k in ("eval_top1_count", "eval_top5_count", "eval_count"):
        assert res[k] == base[k], (k, results)          # exact ints
    assert abs(res["eval_loss"] - base["eval_loss"]) < 1e-5, results
print("OK", base)
""", devices=4, timeout=900)
    assert "OK" in out


def test_augmented_resume_replays_stream_fast():
    """Interrupt an *augmented* run (crop/flip/Mixup/CutMix keyed on
    fold_in(state.rng, step)), save, restore into a DIFFERENT layout:
    the resumed run replays the exact augmentation stream — per-step loss
    parity <= 1e-5 against the uninterrupted run — and the final eval
    metrics agree (counts exactly, loss to 1e-5). A second resume into a
    dp2 x pp2 layout checks the staged 1F1B path threads the SAME
    per-microbatch rng streams (parity within the pp-vs-dp 3e-4
    reduction-order contract — a missed augmentation replay would drift
    at the 1e-2 scale)."""
    out = run_subprocess(_EVAL + r"""
import tempfile
AUG = AugmentConfig(num_classes=10)

def run(eng, state, pipe, lo, hi):
    step = eng.jit_train_step(donate=False)
    losses = []
    with eng.mesh:
        for i in range(lo, hi):
            e, ix = int(state.epoch), int(state.batch_index)
            b = pipe.device_put(pipe.batch_at(e, ix))
            state, m = step(state, b)
            state = state.replace(
                epoch=jax.numpy.int32(pipe.next_cursor(e, ix)[0]),
                batch_index=jax.numpy.int32(pipe.next_cursor(e, ix)[1]))
            losses.append(float(m["loss"]))
    return state, losses

def data():
    return DataPipeline(kind="image", global_batch=8, seed=3,
                        source=source())

ref_eng = make_engine(4, aug=AUG)
s, ref = run(ref_eng, ref_eng.init_state(seed=0), data(), 0, 5)
ref_eval = ref_eng.evaluate(s, source().eval_batches(8))

eng1 = make_engine(4, aug=AUG)
s1, head = run(eng1, eng1.init_state(seed=0), data(), 0, 2)
d = tempfile.mkdtemp()
eng1.save_state(d, s1)

eng2 = make_engine(2, zero=1, aug=AUG)      # resume in a different layout
s2 = eng2.restore_state(d)
assert (int(s2.epoch), int(s2.batch_index)) == (int(s1.epoch),
                                                int(s1.batch_index))
s2, tail = run(eng2, s2, data(), 2, 5)
got = head + tail
for a, b in zip(ref, got):
    assert abs(a - b) < 1e-5, (ref, got)
res_eval = eng2.evaluate(s2, source().eval_batches(8))
for k in ("eval_top1_count", "eval_top5_count", "eval_count"):
    assert res_eval[k] == ref_eval[k], (ref_eval, res_eval)
assert abs(res_eval["eval_loss"] - ref_eval["eval_loss"]) < 1e-5

# dp2 x pp2 resume: per-microbatch aug rngs thread through the staged
# 1F1B schedule (pp reduction order admits 3e-4; a missed augmentation
# replay would miss by ~1e-2)
eng3 = make_engine(2, pipe=2, aug=AUG)
s3 = eng3.restore_state(d)
s3, tail_pp = run(eng3, s3, data(), 2, 5)
for a, b in zip(ref[2:], tail_pp):
    assert abs(a - b) < 3e-4, (ref, head + tail_pp)
print("OK", ref, ref_eval["eval_top1_count"])
""", devices=4, timeout=900)
    assert "OK" in out
