"""Model FLOPs of one training sample, from a configuration file's shapes.

What the forward and backward passes require, counted as matrix
multiplications: 2 FLOPs per multiply-add in the forward pass and twice
that in the backward pass (the gradient with respect to the input and to
the weight). Recomputation does not count. Elementwise work (norms,
softmax, GELU, the optimizer) is left out, as in the usual 6·N·D count.

- Patch projection: 4 per parameter per patch. The images take no
  gradient, so its backward has only the weight's half.
- Attention projections and MLP: 6 per parameter per token, all tokens.
- Attention core: QK^T and PV, 4·S²·H·hd forward, 12·S²·H·hd with the
  backward, per layer.
- Head: 6 per parameter, on the class token only.
"""
from __future__ import annotations


def tokens(config: dict) -> int:
    return (config["image_size"] // config["patch_size"]) ** 2 + 1


def train_flops_per_sample(config: dict) -> float:
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
