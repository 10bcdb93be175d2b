"""A cell, a configuration, a model family and a per-layer metric added
as new files are found by name, with no existing file edited; run.py
refuses a machine without the chip the cell needs."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import cells
import run
import system
from reference import Reference

FILES = ("configs", "workloads", "families", "metrics")


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's files with one more config, cell and
    metric, each added as a file plus its BENCHMARK.json entry."""
    for d in FILES:
        shutil.copytree(cells.BENCH / d, tmp_path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = cells.load_json(cells.ROOT / "BENCHMARK.json")
    cfg = cells.load_json(cells.BENCH / "configs" / "vit-b16.json")
    (tmp_path / "bench" / "configs" / "vit-l16.json").write_text(json.dumps(
        dict(cfg, name="vit-l16", num_layers=24, d_model=1024)))
    traffic = cells.load_json(cells.BENCH / "workloads" / "vit-b16.dp1.json")
    (tmp_path / "bench" / "workloads" / "vit-l16.dp1-b32.json").write_text(
        json.dumps(dict(traffic, global_batch=32)))
    (tmp_path / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(run['steps'])\n")
    bench["configs"].append({"name": "vit-l16", "source": "x",
                             "file": "bench/configs/vit-l16.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "vit-l16.dp1", "config": "vit-l16",
                               "traffic": "vit-l16.dp1-b32", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "samples_per_s",
                               "workloads": ["vit-l16.dp1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_new_files_are_found_by_name(tree):
    cell = cells.load_cell("vit-l16.dp1", root=tree)
    assert cell.config["num_layers"] == 24
    assert cell.traffic["global_batch"] == 32
    assert [m["name"] for m in cell.per_layer][-1] == "steps_seen"
    assert cells.metric_reader("steps_seen", root=tree)({"steps": 3}) == 3.0
    old = cells.load_cell("vit-b16.dp1", root=tree)
    assert "steps_seen" not in [m["name"] for m in old.per_layer]
    assert "collective_exposed_ms" not in [m["name"] for m in old.per_layer]


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = cells.load_json(cells.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    for w in bench["workloads"]:
        assert cells.load_cell(w["name"]).chips == w["chips"]


def test_a_workload_key_the_harness_does_not_read_is_refused(tree):
    path = tree / "bench" / "workloads" / "vit-l16.dp1-b32.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    augment=True)))
    with pytest.raises(ValueError, match="augment"):
        cells.load_cell("vit-l16.dp1", root=tree)


def test_names_cannot_leave_the_directory():
    with pytest.raises(ValueError):
        cells.metric_reader("../run")


def fake(platform, kind, n=1):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("devs", [fake("cpu", "cpu"),
                                  fake("tpu", "TPU v9 nonesuch"),
                                  fake("tpu", "TPU v5 lite", 1)])
def test_refuses_without_the_chip(monkeypatch, devs):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(run.Refused):
        run.chip_devices(4, run.peaks())


def test_command_on_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload",
         "vit-b16.dp1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "measures a TPU" in p.stderr


# A second family, as a later change would add one: a token language model
# on the program's registry MLA + MoE stack, its batches a copy of the
# program's token stream (data/synthetic.py:make_token_batch), its
# reference a one-layer bigram model. Only the finding is tested here: the
# reference does not model the program's network.
LM_FAMILY = '''
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np

SHAPE_KEYS = ("num_layers", "d_model", "vocab_size", "moe.num_experts",
              "moe.top_k", "mla.kv_lora_rank")
TRAFFIC_KEYS = frozenset(("seq_len",))
EPOCH = 1024
SMALL = {}


def build_data(cfg, traffic, seed):
    from repro.data import DataPipeline
    return DataPipeline(kind="token", global_batch=traffic["global_batch"],
                        vocab=cfg.vocab_size, seq_len=traffic["seq_len"],
                        epoch_size=EPOCH, seed=seed), None


def batch(config, traffic, seed, k):
    b, s, v = traffic["global_batch"], traffic["seq_len"], config["vocab_size"]
    cursor = divmod(k, max(1, EPOCH // b))
    rng = np.random.default_rng(
        zlib.crc32(struct.pack("<qqq", seed, *cursor)) % 2 ** 31)
    base = rng.integers(0, v, (b, s))
    mix = rng.random((b, s)) < 0.5
    toks = np.where(mix, (np.roll(base, 1, axis=1) * 31 + 7) % v, base)
    return (toks.astype(np.int32),)


def init_params(config, key):
    d, v = config["d_model"], config["vocab_size"]
    ke, ko = jax.random.split(key)
    return {"embed": jax.random.normal(ke, (v, d), jnp.float32),
            "out": jax.random.normal(ko, (d, v), jnp.float32) / d ** 0.5}


def logits_shape(config, traffic, rows):
    return (rows, traffic["seq_len"] - 1, config["vocab_size"])


def nll_sum(config, traffic, mm, params, tokens, *, shift):
    logits = mm("bsd,dv->bsv", params["embed"][tokens[:, :-1]],
                params["out"]) + shift
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(jnp.mean(jax.nn.logsumexp(logits, -1) - gold, -1))


def train_flops_per_sample(config, traffic):
    return 6.0 * config["d_model"] * config["vocab_size"] \\
        * (traffic["seq_len"] - 1)
'''
LM_SHAPES = {"num_layers": 2, "d_model": 64, "num_heads": 4,
             "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
             "vocab_size": 256}
LM_NESTED = {"moe": {"num_experts": 4, "top_k": 2, "d_ff_expert": 32,
                     "first_dense_layers": 1},
             "mla": {"q_lora_rank": 32, "kv_lora_rank": 16,
                     "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                     "v_head_dim": 16}}


def _digest(path):
    return hashlib.sha256(path.read_bytes()).hexdigest()


@pytest.fixture
def lm_tree(tree):
    """``tree`` with a token-LM family, a configuration and a cell added:
    three new files and two BENCHMARK.json entries."""
    bench = tree / "bench"
    (bench / "families" / "lm.py").write_text(LM_FAMILY)
    (bench / "configs" / "lm-small.json").write_text(json.dumps(dict(
        name="lm-small", family="lm", arch="deepseek-v3-671b",
        overrides=dict(LM_SHAPES, **LM_NESTED), **LM_SHAPES,
        moe={"num_experts": 4, "top_k": 2}, mla={"kv_lora_rank": 16})))
    traffic = cells.load_json(cells.BENCH / "workloads" / "vit-b16.dp1.json")
    for k in ("dataset", "train_size"):
        del traffic[k]
    (bench / "workloads" / "lm-small.dp1.json").write_text(json.dumps(dict(
        traffic, global_batch=4, seq_len=16, ref_rows=2)))
    entries = cells.load_json(tree / "BENCHMARK.json")
    entries["configs"].append({"name": "lm-small", "source": "x",
                               "file": "bench/configs/lm-small.json",
                               "reduced": [], "why": "x"})
    entries["workloads"].append({"name": "lm-small.dp1", "config": "lm-small",
                                 "traffic": "lm-small.dp1", "chips": 1,
                                 "why": "x"})
    (tree / "BENCHMARK.json").write_text(json.dumps(entries))
    return tree


def test_a_new_family_is_found_by_its_files(lm_tree):
    new = {"families/lm.py", "configs/lm-small.json",
           "workloads/lm-small.dp1.json", "configs/vit-l16.json",
           "workloads/vit-l16.dp1-b32.json", "metrics/steps_seen.py"}
    for d in FILES:
        for path in (lm_tree / "bench" / d).iterdir():
            rel = f"{d}/{path.name}"
            if rel not in new:
                assert _digest(path) == _digest(cells.BENCH / rel), rel

    cell = cells.load_cell("lm-small.dp1", root=lm_tree)
    assert cell.family.TRAFFIC_KEYS == {"seq_len"}
    assert cells.load_cell("vit-b16.dp1", root=lm_tree).family \
        .TRAFFIC_KEYS == {"dataset", "train_size"}

    seed = 2 ** 31 + 5
    t = system.build(cell, seed, jax.devices()[:1])
    try:
        assert t.cfg.moe.num_experts == 4 and t.cfg.mla.kv_lora_rank == 16
        fed = next(t.prefetcher)[1]
    finally:
        t.close()
    (tokens,) = cell.family.batch(cell.config, cell.traffic, seed, 0)
    np.testing.assert_array_equal(np.asarray(fed["tokens"]), tokens)

    ref = Reference(cell).readings(seed)
    assert len(ref["losses"]) == cell.traffic["check_steps"]
    assert set(ref["change"]) == {"['embed']", "['out']"}
    assert all(np.isfinite(ref["losses"]))

    mfu = cells.metric_reader("mfu", root=lm_tree)(
        {"cell": cell, "chips": 1, "samples_per_s": 1.0,
         "peak": {"bf16_flops_per_s": 1.0}})
    assert mfu == 100.0 * 6 * 64 * 256 * 15


def test_a_nested_shape_that_differs_is_refused(lm_tree):
    cell = cells.load_cell("lm-small.dp1", root=lm_tree)
    config = dict(cell.config, moe={"num_experts": 8, "top_k": 2})
    with pytest.raises(ValueError, match="moe.num_experts"):
        system.model_config(config, cell.family.SHAPE_KEYS)


def _edit(path, **change):
    data = json.loads(path.read_text())
    data.update(change)
    path.write_text(json.dumps({k: v for k, v in data.items()
                                if v is not None}))


@pytest.mark.parametrize("case,match", [
    ("no_family", "names no family"),
    ("no_module", "rnn"),
    ("other_familys_key", "dataset"),
])
def test_a_cell_the_harness_cannot_read_is_refused(lm_tree, case, match):
    bench = lm_tree / "bench"
    if case == "no_family":
        _edit(bench / "configs" / "lm-small.json", family=None)
    elif case == "no_module":
        _edit(bench / "configs" / "lm-small.json", family="rnn")
    else:   # a ViT key, which the lm family does not read
        _edit(bench / "workloads" / "lm-small.dp1.json", dataset="cifar10")
    with pytest.raises(ValueError, match=match):
        cells.load_cell("lm-small.dp1", root=lm_tree)
