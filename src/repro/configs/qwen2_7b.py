"""qwen2-7b [dense]: 28L d=3584 28H (GQA kv=4) ff=18944 vocab=152064.

GQA with QKV bias [arXiv:2407.10671; hf].  long_500k SKIPPED.
"""
import dataclasses

from .base import ArchConfig

CONFIG = ArchConfig(
    name="qwen2-7b", family="dense",
    n_layers=28, d_model=3584, n_heads=28, n_kv_heads=4, d_ff=18944,
    vocab=152_064, head_dim=128, qkv_bias=True, rope_theta=1_000_000.0,
    tie_embeddings=False,
)

# One stage of a two-stage pipeline deployment: every width as published,
# half the depth.  All 28 layers are 15.2 GB of bf16 weights, more than a
# 16 GB TPU v5e holds beside a KV cache; 14 layers are about 8.7 GB, which
# leaves room for an 8-slot x 4096-token bf16 cache (about 0.9 GB).
STAGE_LAYERS = 14
STAGE_SLOTS = 8          # decode batch
STAGE_MAX_LEN = 4096     # tokens per slot


def one_chip_stage() -> ArchConfig:
    """The published widths at :data:`STAGE_LAYERS` layers."""
    return dataclasses.replace(CONFIG, name=f"qwen2-7b-{STAGE_LAYERS}L",
                               n_layers=STAGE_LAYERS)
