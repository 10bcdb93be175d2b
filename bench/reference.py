"""Plain float32 reference of a cell's training steps.

ViT (Dosovitskiy et al. 2021) written out in ``jax.numpy`` from the
configuration file's shapes and the workload file's recipe, with no kernel,
no sharding and nothing imported from the program: the on-device
preprocessing (nearest upsample from the native grid, per-channel
normalisation), patch embedding, class token and position table, the
pre-LayerNorm encoder blocks (multi-head attention, tanh-GELU MLP), the
final LayerNorm, the linear head on the class token, mean cross-entropy,
the global-norm gradient clip and AdamW with its warm-up/cosine schedule.

Weights are drawn from the seed by the configuration's initialiser: the
same key splits and truncated-normal draws, so that the reference starts
where the program starts without taking anything the program made.

Every matrix product runs at ``Precision.HIGHEST`` in float32. The control
(``precision="fp8"``) rounds both operands of every product to float8
e4m3 and the cotangents of the backward pass to e5m2, each with a scale
per tensor, as an fp8 training step would: it is the step below the
configuration's bfloat16 that a later change could be tempted to take, and
the check has to refuse it.

A batch is processed in blocks of ``ref_rows`` rows, each layer
recomputed in the backward pass, so that the reference fits on one chip
next to nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import traffic as traffic_mod

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2


# ---------------------------------------------------------------------------
# matrix products: float32, or the fp8 control
# ---------------------------------------------------------------------------

def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def _fp8(x, dtype):
    """Round to ``dtype`` with one scale for the tensor (amax -> max)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, a, b):
    return _einsum(eq, _fp8(a, E4M3), _fp8(b, E4M3))


def _einsum_fp8_fwd(eq, a, b):
    qa, qb = _fp8(a, E4M3), _fp8(b, E4M3)
    return _einsum(eq, qa, qb), (qa, qb)


def _einsum_fp8_bwd(eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(eq, x, y), qa, qb)
    return vjp(_fp8(g, E5M2))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)

PRODUCTS = {"float32": _einsum, "fp8": _einsum_fp8}


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
    ds = traffic_mod.DATASETS[dataset]
    k = config["image_size"] // ds["native"]
    x = jnp.repeat(jnp.repeat(u8.astype(F32), k, axis=1), k, axis=2)
    return (x / 255.0 - jnp.asarray(ds["mean"], F32)) \
        / jnp.asarray(ds["std"], F32)


def nll_sum(config: dict, dataset: str, mm, params, u8, labels, shift):
    """Sum over the rows of -log p(label); ``shift`` is added to the
    logits (zero, except where a fault is planted)."""
    d, h, hd = config["d_model"], config["num_heads"], config["head_dim"]
    ps, eps = config["patch_size"], config["norm_eps"]
    x = images_in(config, dataset, u8)
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
# optimizer
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, step: int) -> float:
    warm, total, lr = opt["warmup_steps"], opt["total_steps"], opt["lr"]
    if step < warm:
        return lr * (step + 1.0) / max(1.0, warm)
    frac = min(max((step - warm) / max(1.0, total - warm), 0.0), 1.0)
    if opt["schedule"] != "cosine":
        raise ValueError(f"no reference for schedule {opt['schedule']!r}")
    f = opt["final_lr_frac"]
    return lr * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * frac)))


def _adamw(opt, params, m, v, grads, step, lr):
    """Clip by global norm, then one AdamW step (decoupled decay)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-9)),
        grads)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    bc1 = 1 - b1 ** (step + 1.0)
    bc2 = 1 - b2 ** (step + 1.0)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1 / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v, grads


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in xs]


def leaf_norms(tree) -> dict:
    """{key path: float32 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _norms([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def grad_norms_from_moments(mu, nu, opt: dict) -> tuple:
    """Per-leaf norms of the gradient that one AdamW step from zero
    moments took, read back from its first moment (``mu / (1 - b1)``) and
    from its second (``sqrt(nu / (1 - b2))``, which a wrong ``b2``
    scales)."""
    b1, b2 = opt["b1"], opt["b2"]
    g1 = {k: v / (1 - b1) for k, v in leaf_norms(mu).items()}
    g2 = {k: v / math.sqrt(1 - b2)
          for k, v in leaf_norms(jax.tree.map(jnp.sqrt, nu)).items()}
    return g1, g2


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

class Reference:
    """The reference for one cell: ``readings(seed)`` trains the cell's
    first ``check_steps`` batches from the seed's weights and returns what
    the check compares (see ``check.py``)."""

    def __init__(self, config: dict, traffic: dict, precision="float32",
                 device=None, step_opt=None):
        """``step_opt``: optimizer settings the steps take instead of the
        workload's (a fault planted); the readings still take the
        workload's."""
        self.config, self.traffic = config, traffic
        self.opt = traffic["optimizer"]
        self.device = device or jax.devices()[0]
        mm = PRODUCTS[precision]
        loss = functools.partial(nll_sum, config, traffic["dataset"], mm)
        self._grad = jax.jit(jax.value_and_grad(loss))
        self._init = jax.jit(functools.partial(init_params, config))
        self._step = jax.jit(functools.partial(_adamw, step_opt or self.opt))

    def readings(self, seed: int, *, rows=None, logit_shift=0.0) -> dict:
        """``rows``: train on the first ``rows`` rows of each batch only,
        the mean taken over them; ``logit_shift`` is added to the first
        row's first logit. Both plant faults; the defaults are the sound reference."""
        steps, block = self.traffic["check_steps"], self.traffic["ref_rows"]
        c = self.config["num_classes"]
        with jax.default_device(self.device), \
                jax.default_matmul_precision("highest"):
            params = self._init(jax.random.PRNGKey(seed))
            p0 = params
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
            losses = []
            for k in range(steps):
                u8, labels = traffic_mod.batch(self.traffic, seed, k)
                n = rows or len(labels)
                total, grads = 0.0, None
                for lo in range(0, n, block):
                    hi = min(lo + block, n)
                    shift = np.zeros((hi - lo, c), np.float32)
                    if lo == 0:
                        shift[0, 0] += logit_shift
                    val, g = self._grad(params, u8[lo:hi], labels[lo:hi],
                                        shift)
                    total = total + val
                    grads = g if grads is None else \
                        jax.tree.map(jnp.add, grads, g)
                grads = jax.tree.map(lambda g: g / n, grads)
                losses.append(float(total) / n)
                lr = learning_rate(self.opt, k)
                params, m, v, clipped = self._step(params, m, v, grads, k, lr)
                if k == 0:
                    grad_norms = leaf_norms(clipped)
                    grad2 = grad_norms_from_moments(m, v, self.opt)[1]
            change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        return {"losses": losses, "grad": grad_norms, "grad2": grad2,
                "change": change}
