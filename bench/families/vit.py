"""The ViT family: Vision Transformers trained on procedural CIFAR
(``cells.py`` says what a family provides and how it is found).

- ``batch`` is a copy, kept with the benchmark, of the program's
  procedural CIFAR stream (``data/pipeline.py:batch_seed``,
  ``data/synthetic.py:class_conditional_images``,
  ``data/datasets.py:quantize_images``): batch ``k`` of a run with
  ``--seed s`` is the uint8 batch at data cursor
  ``(k // steps_per_epoch, k % steps_per_epoch)``. The reference trains on
  these, so the program's data path is checked with the rest of the step.
- ``init_params`` and ``nll_sum`` are ViT (Dosovitskiy et al. 2021)
  written out in ``jax.numpy`` from the configuration file's shapes, with
  no kernel, no sharding and nothing imported from the program: the
  on-device preprocessing (nearest upsample from the native grid,
  per-channel normalisation), patch embedding, class token and position
  table, the pre-LayerNorm encoder blocks (multi-head attention, tanh-GELU
  MLP), the final LayerNorm and the linear head on the class token.
  Weights are drawn from the seed by the configuration's initialiser: the
  same key splits and truncated-normal draws, so that the reference starts
  where the program starts without taking anything the program made.
"""
from __future__ import annotations

import math
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

SHAPE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "image_size", "patch_size", "num_classes",
              "norm_eps", "act", "dtype", "param_dtype", "use_pallas",
              "attn_impl", "remat", "qkv_bias")
TRAFFIC_KEYS = frozenset(("dataset", "train_size"))

# shapes replacing the configuration file's, a second set the FLOP count is
# also held at, the traffic cut, and limits between the small cell's sound
# readings and its control's and faults' (calibrate.readings at these widths
# on the CPU: sound at most 1.5e-3, 6.2e-3, 4.9e-3; the control at least
# 1.1e-2, 2.9e-2, 2.6e-2)
SMALL = {
    "shapes": {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
               "image_size": 32, "patch_size": 8},
    "shapes_also": {"image_size": 64, "d_ff": 96},
    "traffic": {"global_batch": 8, "ref_rows": 4, "trace_steps": 2},
    "limits": {"loss_gap": 5e-3, "grad_gap": 2e-2, "update_gap": 2e-2},
}


# ---------------------------------------------------------------------------
# the program's data path, and the batches it feeds
# ---------------------------------------------------------------------------

def build_data(cfg, traffic: dict, seed: int):
    """(DataPipeline, preproc) as ``launch/train.py`` builds them for a
    dataset job: a CIFAR source, uint8 at the native grid, upsampled and
    normalised on the device."""
    from repro.data import DataPipeline, make_source
    source = make_source(traffic["dataset"], seed=seed,
                         resolution=cfg.image_size,
                         train_size=traffic["train_size"])
    if source.spec.num_classes != cfg.num_classes:
        raise ValueError(f"{traffic['dataset']} has {source.spec.num_classes} "
                         f"classes, the config {cfg.num_classes}")
    pipe = DataPipeline(kind="image", global_batch=traffic["global_batch"],
                        source=source, seed=seed)
    return pipe, source.preproc


# per-channel mean and std of each dataset, and its class count
DATASETS = {
    "cifar10": {"classes": 10, "native": 32,
                "mean": (0.4914, 0.4822, 0.4465),
                "std": (0.2470, 0.2435, 0.2616)},
    "cifar100": {"classes": 100, "native": 32,
                 "mean": (0.5071, 0.4865, 0.4409),
                 "std": (0.2673, 0.2564, 0.2762)},
}
TEMPLATE_SEED = 1234


def batch_seed(seed: int, epoch: int, index: int) -> int:
    return zlib.crc32(struct.pack("<qqq", seed, epoch, index)) % (2 ** 31)


def cursor(traffic: dict, k: int):
    per_epoch = max(1, traffic["train_size"] // traffic["global_batch"])
    return divmod(k, per_epoch)


def batch(config: dict, traffic: dict, seed: int, k: int):
    """(images uint8 (B, 32, 32, 3), labels int32 (B,)) of step ``k``."""
    ds = DATASETS[traffic["dataset"]]
    n, res = traffic["global_batch"], ds["native"]
    rng = np.random.default_rng(batch_seed(seed, *cursor(traffic, k)))
    labels = rng.integers(0, ds["classes"], (n,))
    templates = np.random.default_rng(TEMPLATE_SEED).normal(
        0, 1, (ds["classes"], 8, 8, 3)).astype(np.float32)
    reps = res // 8 + 1
    x = np.tile(templates[labels], (1, reps, reps, 1))[:, :res, :res]
    x = x + rng.normal(0, 0.7, (n, res, res, 3)).astype(np.float32)
    u = (x.astype(np.float32) * np.asarray(ds["std"], np.float32)
         + np.asarray(ds["mean"], np.float32)) * 255.0
    return (np.clip(np.rint(u), 0, 255).astype(np.uint8),
            labels.astype(np.int32))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _dense(key, shape):
    """Truncated normal on [-2, 2], std 1/sqrt(fan in)."""
    return (1.0 / math.sqrt(shape[-2])) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, F32)


def _norm(d):
    return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}


def init_params(config: dict, key):
    d, dff, L = config["d_model"], config["d_ff"], config["num_layers"]
    h, hd, ps = config["num_heads"], config["head_dim"], config["patch_size"]
    n = (config["image_size"] // ps) ** 2
    keys = jax.random.split(key, 8)

    def layer(key):
        ka, km = jax.random.split(key, 2)
        qkvo = jax.random.split(ka, 4)
        mlp = jax.random.split(km, 3)
        return {
            "ln1": _norm(d), "ln2": _norm(d),
            "attn": {"wq": _dense(qkvo[0], (d, h * hd)),
                     "wk": _dense(qkvo[1], (d, h * hd)),
                     "wv": _dense(qkvo[2], (d, h * hd)),
                     "wo": _dense(qkvo[3], (h * hd, d))},
            "mlp": {"w_out": _dense(mlp[2], (dff, d)),
                    "w_up": _dense(mlp[1], (d, dff)),
                    "b_up": jnp.zeros((dff,), F32),
                    "b_out": jnp.zeros((d,), F32)},
        }

    return {
        "embed": {
            "patch_w": _dense(keys[0], (ps * ps * 3, d)),
            "patch_b": jnp.zeros((d,), F32),
            "cls": jnp.zeros((1, 1, d), F32),
            "pos": 0.02 * jax.random.truncated_normal(
                keys[5], -2.0, 2.0, (n + 1, d), F32),
        },
        "stack": jax.vmap(layer)(jax.random.split(keys[1], L)),
        "final_norm": _norm(d),
        "head": {"w": _dense(keys[3], (d, config["num_classes"])),
                 "b": jnp.zeros((config["num_classes"],), F32)},
    }


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def images_in(config: dict, dataset: str, u8):
    """uint8 (B, 32, 32, 3) -> normalised float32 at the model's size."""
    ds = DATASETS[dataset]
    k = config["image_size"] // ds["native"]
    x = jnp.repeat(jnp.repeat(u8.astype(F32), k, axis=1), k, axis=2)
    return (x / 255.0 - jnp.asarray(ds["mean"], F32)) \
        / jnp.asarray(ds["std"], F32)


def logits_shape(config: dict, traffic: dict, rows: int) -> tuple:
    """The shape of a block's logits, which ``nll_sum``'s ``shift`` has."""
    return (rows, config["num_classes"])


def nll_sum(config: dict, traffic: dict, mm, params, u8, labels, *, shift):
    """Sum over the rows of -log p(label); ``shift`` is added to the
    logits (zero, except where a fault is planted)."""
    d, h, hd = config["d_model"], config["num_heads"], config["head_dim"]
    ps, eps = config["patch_size"], config["norm_eps"]
    x = images_in(config, traffic["dataset"], u8)
    b, n = x.shape[0], config["image_size"] // ps
    patches = x.reshape(b, n, ps, n, ps, 3).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(b, n * n, ps * ps * 3)
    e = params["embed"]
    t = mm("bpk,kd->bpd", patches, e["patch_w"]) + e["patch_b"]
    t = jnp.concatenate([jnp.broadcast_to(e["cls"], (b, 1, d)), t], 1) \
        + e["pos"][None]
    s = t.shape[1]

    def block(t, lp):
        a = _layernorm(t, lp["ln1"], eps)
        at = lp["attn"]
        q = mm("bsd,de->bse", a, at["wq"]).reshape(b, s, h, hd)
        k = mm("bsd,de->bse", a, at["wk"]).reshape(b, s, h, hd)
        v = mm("bsd,de->bse", a, at["wv"]).reshape(b, s, h, hd)
        scores = mm("bshd,bthd->bhst", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(scores, axis=-1)
        o = mm("bhst,bthd->bshd", w, v).reshape(b, s, h * hd)
        t = t + mm("bse,ed->bsd", o, at["wo"])
        m = _layernorm(t, lp["ln2"], eps)
        ml = lp["mlp"]
        u = _gelu(mm("bsd,df->bsf", m, ml["w_up"]) + ml["b_up"])
        return t + mm("bsf,fd->bsd", u, ml["w_out"]) + ml["b_out"], None

    t, _ = jax.lax.scan(jax.checkpoint(block), t, params["stack"])
    cls = _layernorm(t[:, 0], params["final_norm"], eps)
    logits = mm("bd,dc->bc", cls, params["head"]["w"]) \
        + params["head"]["b"] + shift
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def tokens(config: dict) -> int:
    return (config["image_size"] // config["patch_size"]) ** 2 + 1


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """What the forward and backward passes require, counted as matrix
    multiplications: 2 FLOPs per multiply-add in the forward pass and twice
    that in the backward pass (the gradient with respect to the input and
    to the weight). Recomputation does not count. Elementwise work (norms,
    softmax, GELU, the optimizer) is left out, as in the usual 6·N·D count.

    - Patch projection: 4 per parameter per patch. The images take no
      gradient, so its backward has only the weight's half.
    - Attention projections and MLP: 6 per parameter per token, all tokens.
    - Attention core: QK^T and PV, 4·S²·H·hd forward, 12·S²·H·hd with the
      backward, per layer.
    - Head: 6 per parameter, on the class token only.
    """
    d, dff, L = config["d_model"], config["d_ff"], config["num_layers"]
    h, kh, hd = config["num_heads"], config["num_kv_heads"], config["head_dim"]
    ps, c = config["patch_size"], config["num_classes"]
    s = tokens(config)
    patch = 4 * (ps * ps * 3 * d) * (s - 1)
    proj = 2 * d * h * hd + 2 * d * kh * hd          # wq, wo; wk, wv
    mlp = 2 * d * dff
    layers = L * (6 * (proj + mlp) * s + 12 * s * s * h * hd)
    head = 6 * d * c
    return float(patch + layers + head)
