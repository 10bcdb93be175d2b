"""The train step names its own work (``repro/obs.py``).

Compiles the smoke vit-b16 train step and reads each HLO instruction's
``op_name``: every matmul sits under the forward (``jvp(forward)``, or
``forward`` on the pipeline's undifferentiated slots), its backward
(``transpose(jvp(forward))``) or the optimizer; the attention core's
matmuls, and only they, carry ``attn_core`` in both directions, naive and
flash alike; the AdamW update and the anomaly guard sit under the
optimizer. At accum 1, accum 2 and on the pp2 route."""
import json
import os
import re

import pytest

from conftest import run_subprocess

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*? ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(
    r"^jit\(_train_step\)/(?:.*/)?"
    r"(jvp\(forward\)|transpose\(jvp\(forward\)\)|forward|optimizer)/")
_MATMUL = ("dot", "convolution")


def step_ops(impl: str, accum: int, pipe: int = 1) -> list:
    """(opcode, op_name or None) of every instruction of the compiled
    train step."""
    import jax
    from repro.configs import EngineConfig, get_smoke_config
    from repro.core.engine import DistributedEngine
    from repro.data import DataPipeline, make_source
    from repro.launch.mesh import make_local_mesh

    cfg = get_smoke_config("vit-b16").replace(use_pallas=impl == "flash")
    mesh = make_local_mesh(pipe=pipe, devices=jax.devices()[:pipe])
    ecfg = EngineConfig(train_batch_size=8, gradient_accumulation_steps=accum,
                        pipeline_stages=pipe, guard_anomalies=True,
                        lr_schedule="cosine", total_steps=100,
                        warmup_steps=10)
    source = make_source("cifar10", seed=0, resolution=cfg.image_size,
                         train_size=64)
    eng = DistributedEngine(cfg, ecfg, mesh, preproc=source.preproc)
    pipe_ = DataPipeline(kind="image", global_batch=8, source=source, seed=0)
    text = eng.lower_train(pipe_.batch_shapes()).compile().as_text()
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out.append((m.group(1), n.group(1) if n else None))
    return out


def phase(op_name):
    m = _PHASE.match(op_name or "")
    return m.group(1) if m else None


CASES = [("naive", 1, 1), ("flash", 1, 1), ("naive", 2, 1),
         ("naive", 2, 2)]


@pytest.mark.parametrize("impl,accum,pipe", CASES,
                         ids=["accum1", "flash", "accum2", "pp2"])
def test_step_scopes(impl, accum, pipe):
    if pipe == 1:
        ops = step_ops(impl, accum)
    else:
        here = os.path.dirname(os.path.abspath(__file__))
        ops = json.loads(run_subprocess(
            f"import json, sys; sys.path.insert(0, {here!r})\n"
            f"from test_obs_scopes import step_ops\n"
            f"print(json.dumps(step_ops({impl!r}, {accum}, {pipe})))",
            devices=pipe).strip().splitlines()[-1])
    names = [n for _, n in ops if n]
    # every matmul lies under a phase. The pipeline's stage-batched
    # matmuls come out of XLA:CPU's dot rewrites without metadata, so
    # there the instructions that kept a matmul's op_name are checked
    matmuls = [n for n in names if n.endswith(("/dot_general",
                                               "/conv_general_dilated"))]
    if pipe == 1:
        assert all(n is not None for k, n in ops if k in _MATMUL)
        matmuls += [n for k, n in ops if k in _MATMUL]
    assert len(matmuls) >= 10
    assert [n for n in matmuls if phase(n) is None] == []
    assert {phase(n) for n in matmuls} >= {
        "transpose(jvp(forward))", "jvp(forward)" if pipe == 1 else "forward"}

    # the attention core, and no projection, in both directions
    core = [n for n in matmuls if "/attn_core/" in n]
    assert any(phase(n) == "transpose(jvp(forward))" for n in core)
    if pipe == 1:   # none of the pipeline's forward ones kept its name
        assert any(phase(n) == "jvp(forward)" for n in core)
    marker = "jit(_flash_call)" if impl == "flash" else "->"
    assert all(marker in n for n in core)
    if impl == "naive":
        assert all("/attn_core/" in n for n in matmuls if "->" in n)

    # AdamW (its sqrt of the second moment) and the guard's selects run
    # under the optimizer; at accum 1 without the pipeline nothing else is
    # left unscoped but constants the compiler hoisted out of the scopes
    # (accumulation adds the microbatches' gradients outside them)
    opt = [n for n in names if phase(n) == "optimizer"]
    assert any(n.endswith("/sqrt") for n in opt)
    assert any(n.endswith("jit(_where)/select_n") for n in opt)
    if accum == 1 and pipe == 1:
        loose = {n for n in names
                 if n.startswith("jit(_train_step)/") and phase(n) is None}
        assert all(n.endswith("/broadcast_in_dim") for n in loose), loose
