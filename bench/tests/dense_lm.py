"""A token model of dense SwiGLU layers that exists only to size the
float32 reference (``reference.py``): a model family (``cells.py`` says
what one provides) with no program and no cell behind it.

    python bench/tests/dense_lm.py --seed 2147483659 [--bench DIR] [--tiny]

On one chip, with nothing else on it, this trains the reference's
``check_steps`` steps of the full cut and prints one JSON line: the wall
time, the parameter count, the losses and the device's ``memory_stats()``,
or the error that stopped it. ``--bench`` takes ``reference.py`` from the
``bench`` directory of another checkout (an older layout, to show that it
runs out of memory); ``--tiny`` runs the CPU tests' cut.

The full cut has the widths of the d-2048 mixture-of-experts models' dense
layer (DeepSeek-V2-Lite, huggingface.co/deepseek-ai/DeepSeek-V2-Lite:
hidden 2048, intermediate 10944), 10 such layers, each recomputed in the
backward pass, and an eighth of that model's 102400-token vocabulary in
the embedding and the head: 724,850,688 float32 parameters. A batch is two
rows of 8192 tokens, one row to a block, so each step accumulates the
gradient of two blocks.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parents[1]
F32 = jnp.float32

FULL = {"d_model": 2048, "d_ff": 10944, "num_layers": 10,
        "vocab_size": 12800, "seq_len": 8192, "norm_eps": 1e-6}
TINY = {"d_model": 32, "d_ff": 96, "num_layers": 2, "vocab_size": 64,
        "seq_len": 16, "norm_eps": 1e-6}
# the benchmark's AdamW recipe (bench/workloads/vit-b16.dp1.json)
TRAFFIC = {
    "global_batch": 2, "ref_rows": 1, "check_steps": 3,
    "optimizer": {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.01, "grad_clip": 1.0,
                  "schedule": "cosine", "warmup_steps": 1000,
                  "total_steps": 10000, "final_lr_frac": 0.1},
}


def cell(config: dict) -> SimpleNamespace:
    """What ``Reference`` reads of a cell, for this family."""
    return SimpleNamespace(config=config, traffic=TRAFFIC,
                           family=sys.modules[__name__])


def batch(config: dict, traffic: dict, seed: int, k: int):
    """(token ids int32 (B, seq_len + 1),): inputs and next-token labels."""
    rng = np.random.default_rng([seed, k])
    return (rng.integers(0, config["vocab_size"],
                         (traffic["global_batch"], config["seq_len"] + 1),
                         dtype=np.int32),)


def init_params(config: dict, key):
    d, f, L, V = (config["d_model"], config["d_ff"], config["num_layers"],
                  config["vocab_size"])
    ks = jax.random.split(key, 5)

    def dense(key, shape):
        return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])

    return {
        "embed": jax.random.normal(ks[0], (V, d), F32),
        "stack": {"norm": jnp.ones((L, d), F32),
                  "w_gate": dense(ks[1], (L, d, f)),
                  "w_up": dense(ks[2], (L, d, f)),
                  "w_down": dense(ks[3], (L, f, d))},
        "final_norm": jnp.ones((d,), F32),
        "head": dense(ks[4], (d, V)),
    }


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def logits_shape(config: dict, traffic: dict, rows: int) -> tuple:
    return (rows, config["seq_len"], config["vocab_size"])


def nll_sum(config: dict, traffic: dict, mm, params, ids, *, shift):
    """Sum over the rows of each row's mean next-token loss."""
    eps = config["norm_eps"]
    x = params["embed"][ids[:, :-1]]

    def layer(x, lp):
        h = _rmsnorm(x, lp["norm"], eps)
        u = jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"])) \
            * mm("bsd,df->bsf", h, lp["w_up"])
        return x + mm("bsf,fd->bsd", u, lp["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["stack"])
    logits = mm("bsd,dv->bsv", _rmsnorm(x, params["final_norm"], eps),
                params["head"]) + shift
    gold = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold, -1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--bench", default=str(BENCH),
                    help="the bench directory whose reference.py runs")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(args.bench).resolve()))
    from reference import Reference

    config = TINY if args.tiny else FULL
    dev = jax.devices()[0]
    shapes = jax.eval_shape(lambda: init_params(config, jax.random.key(0)))
    params = sum(x.size for x in jax.tree.leaves(shapes))
    t0 = time.perf_counter()
    try:
        losses = Reference(cell(config), device=dev).readings(
            args.seed)["losses"]
        error = None
    except jax.errors.JaxRuntimeError as e:
        losses, error = None, str(e)[:600]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "reference": str(Path(args.bench).resolve() / "reference.py"),
        "params": params, "wall_s": time.perf_counter() - t0,
        "losses": losses, "error": error, "device": dev.device_kind,
        "memory_stats": {k: stats.get(k) for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_in_use",
            "bytes_limit")},
    }), flush=True)
    return 0 if error is None else 1


if __name__ == "__main__":
    sys.exit(main())
