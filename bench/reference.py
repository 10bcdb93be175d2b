"""Plain float32 reference of a cell's training steps.

The cell's model family (``families/<family>.py``) gives the model: its
weights drawn from the seed as the program draws its own, its batches made
again from the seed, and its loss written out in ``jax.numpy`` with no
kernel, no sharding and nothing imported from the program. This module
gives what is the same for every family: the mean of that loss over the
batch, the global-norm gradient clip, AdamW with its warm-up/cosine
schedule, and the readings the check compares.

Every matrix product runs at ``Precision.HIGHEST`` in float32. The control
(``precision="fp8"``) rounds both operands of every product to float8
e4m3 and the cotangents of the backward pass to e5m2, each with a scale
per tensor, as an fp8 training step would: it is the step below the
configuration's bfloat16 that a later change could be tempted to take, and
the check has to refuse it.

A batch is processed in blocks of ``ref_rows`` rows, each layer
recomputed in the backward pass, and the device holds at most three float32
copies of the parameters (12 B a parameter) besides one leaf and a block's
activations: the parameters, one gradient accumulator, and the current
block's gradient or, in the update, one leaf's two moments. The first
weights and Adam's moments stay in host memory; the update runs leaf by
leaf, in place, and each leaf's change is taken against its first weights
uploaded alone. ``tests/test_reference.py`` holds the reference to that
bound on the CPU, after every jitted call. For a model of 0.7e9
parameters it comes to 8.7 GB of the 16 GB of one v5e, beside a block's
activations (``tests/dense_lm.py`` runs that model on the chip).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

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


def _clip_scale(opt, grads):
    """The global-norm clip's factor for the whole gradient tree."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    return jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-9))


def _adamw_leaf(opt, p, m, v, g, scale, step, lr):
    """One AdamW step (decoupled decay) of one leaf, its gradient clipped
    by ``scale``; returns the new leaf, its moments and the clipped
    gradient."""
    g = g * scale
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    bc1 = 1 - b1 ** (step + 1.0)
    bc2 = 1 - b2 ** (step + 1.0)
    p = p - lr * (m / bc1 / (jnp.sqrt(v / bc2) + eps) + wd * p)
    return p, m, v, g


# the accumulator is updated in place: the device never holds two
@functools.partial(jax.jit, donate_argnums=0)
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=0)
def _mean(acc, n):
    return jax.tree.map(lambda g: g / n, acc)


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


@jax.jit
def _norms(xs):
    return [_norm(x) for x in xs]


@jax.jit
def _root_norms(xs):
    # the square root is taken inside the reduction: no rooted copy
    return [_norm(jnp.sqrt(x)) for x in xs]


@jax.jit
def _change_norm(p, p0):
    return _norm(p - p0)


def leaf_norms(tree, root=False) -> dict:
    """{key path: float32 norm} of every leaf, or of its square root."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = (_root_norms if root else _norms)([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def grad2_norm(root_nu_norm: float, opt: dict) -> float:
    """The norm of the gradient that one AdamW step from zero moments
    took, read from the norm of its second moment's square root:
    ``sqrt(nu / (1 - b2))``, which a wrong ``b2`` scales."""
    return root_nu_norm / math.sqrt(1 - opt["b2"])


def grad_norms_from_moments(mu, nu, opt: dict) -> tuple:
    """Per-leaf norms of the gradient that one AdamW step from zero
    moments took, read back from its first moment (``mu / (1 - b1)``) and
    from its second (``grad2_norm``)."""
    g1 = {k: v / (1 - opt["b1"]) for k, v in leaf_norms(mu).items()}
    g2 = {k: grad2_norm(v, opt)
          for k, v in leaf_norms(nu, root=True).items()}
    return g1, g2


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

class Reference:
    """The reference for one cell: ``readings(seed)`` trains the cell's
    first ``check_steps`` batches from the seed's weights and returns what
    the check compares (see ``check.py``)."""

    def __init__(self, cell, precision="float32", device=None,
                 step_opt=None):
        """``step_opt``: optimizer settings the steps take instead of the
        workload's (a fault planted); the readings still take the
        workload's."""
        self.config, self.traffic = cell.config, cell.traffic
        self.family = cell.family
        self.opt = self.traffic["optimizer"]
        self.device = device or jax.devices()[0]
        mm = PRODUCTS[precision]
        loss = functools.partial(self.family.nll_sum, self.config,
                                 self.traffic, mm)
        self._grad = jax.jit(jax.value_and_grad(loss))
        self._init = jax.jit(functools.partial(self.family.init_params,
                                               self.config))
        opt = step_opt or self.opt
        self._clip = jax.jit(functools.partial(_clip_scale, opt))
        self._step = jax.jit(functools.partial(_adamw_leaf, opt),
                             donate_argnums=(0, 1, 2, 3))

    def readings(self, seed: int, *, rows=None, logit_shift=0.0) -> dict:
        """``rows``: train on the first ``rows`` rows of each batch only,
        the mean taken over them; ``logit_shift`` is added to the first
        row's first logit. Both plant faults; the defaults are the sound reference."""
        steps, block = self.traffic["check_steps"], self.traffic["ref_rows"]
        fam = self.family
        with jax.default_device(self.device), \
                jax.default_matmul_precision("highest"):
            params = self._init(jax.random.PRNGKey(seed))
            keys = [jax.tree_util.keystr(p) for p, _ in
                    jax.tree_util.tree_flatten_with_path(params)[0]]
            leaves, tree = jax.tree.flatten(params)
            del params
            p0 = [np.array(x) for x in leaves]
            m = [np.zeros_like(x) for x in p0]
            v = [np.zeros_like(x) for x in p0]
            losses, grad2 = [], {}
            for k in range(steps):
                arrays = fam.batch(self.config, self.traffic, seed, k)
                n = rows or len(arrays[0])
                total, grads = 0.0, None
                for lo in range(0, n, block):
                    hi = min(lo + block, n)
                    shift = np.zeros(fam.logits_shape(
                        self.config, self.traffic, hi - lo), np.float32)
                    if lo == 0:
                        shift[(0,) * shift.ndim] += logit_shift
                    val, g = self._grad(jax.tree.unflatten(tree, leaves),
                                        *(a[lo:hi] for a in arrays),
                                        shift=shift)
                    total = total + val
                    grads = g if grads is None else _add(grads, g)
                    del g
                grads = jax.tree.leaves(_mean(grads, n))
                losses.append(float(total) / n)
                lr = learning_rate(self.opt, k)
                scale = self._clip(grads)
                for i in range(len(leaves)):
                    leaves[i], mi, vi, grads[i] = self._step(
                        leaves[i], jax.device_put(m[i]),
                        jax.device_put(v[i]), grads[i], scale, k, lr)
                    m[i], v[i] = np.array(mi), np.array(vi)
                    if k == 0:
                        grad2[keys[i]] = grad2_norm(
                            float(_root_norms([vi])[0]), self.opt)
                    del mi, vi
                if k == 0:
                    grad_norms = dict(zip(keys, map(float, _norms(grads))))
                del grads
            change = {key: float(_change_norm(p, jax.device_put(q)))
                      for key, p, q in zip(keys, leaves, p0)}
        return {"losses": losses, "grad": grad_norms, "grad2": grad2,
                "change": change}
