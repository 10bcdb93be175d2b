"""ViT-B/16 — the paper's own model [Dosovitskiy et al., 2021].

86M-parameter encoder used for CIFAR-10/100 classification in the paper's
evaluation. The classification variant patchifies images directly (conv
patch embed implemented, not stubbed — this is the paper's actual workload).
"""
from repro.configs.base import ModelConfig

ARCH_ID = "vit-b16"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        arch_type="vit",
        num_layers=12,
        d_model=768,
        num_heads=12,
        num_kv_heads=12,
        head_dim=64,
        d_ff=3072,
        vocab_size=0,
        causal=False,
        rope_style="none",
        image_size=224,
        patch_size=16,
        num_classes=10,              # CIFAR-10 default; overridden per dataset
        norm_eps=1e-6,
        act="gelu",
        # 197 tokens (196 patches + cls) fit one 256 flash tile; the
        # default 512 tile would compute 6.7x the live score entries
        attn_block_q=256,
        attn_block_k=256,
    )


def smoke() -> ModelConfig:
    return config().replace(
        name=ARCH_ID + "-smoke", num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=4, head_dim=32, d_ff=256,
        image_size=32, patch_size=4, num_classes=10)
