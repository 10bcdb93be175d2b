"""The training batches a cell's job feeds, made again from the seed.

A copy, kept with the benchmark, of the program's procedural CIFAR stream
(``data/pipeline.py:batch_seed``, ``data/synthetic.py:
class_conditional_images``, ``data/datasets.py:quantize_images``): batch
``k`` of a run with ``--seed s`` is the uint8 batch at data cursor
``(k // steps_per_epoch, k % steps_per_epoch)``. The reference trains on
these, so the program's data path is checked with the rest of the step.
"""
from __future__ import annotations

import struct
import zlib

import numpy as np

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


def batch(traffic: dict, seed: int, k: int):
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
