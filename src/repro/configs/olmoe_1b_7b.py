"""olmoe-1b-7b [moe]: OLMoE-1B-7B-0924 as published [arXiv:2409.02060;
huggingface.co/allenai/OLMoE-1B-7B-0924 config.json].

16 layers, each a mixture of experts: d=2048, 16 heads and 16 KV heads of
128, 64 experts of width 1024 with 8 routed per token, vocab 50304,
untied head, rope theta 1e4, RMS eps 1e-5.  Two things set its attention
and gating apart: the q and k projections are RMS-normed over their whole
width (2048 each) before rotary (``qk_proj_norm``), and the top-8 router
probabilities gate the experts as they are, not renormalised to sum to
one (``norm_topk_prob`` false in the published config).

FO=8 dispatch crossbar: the paper's fan-out metric literally sizes the
expert all-to-all.  long_500k SKIPPED.
"""
import dataclasses

from .base import ArchConfig

CONFIG = ArchConfig(
    name="olmoe-1b-7b", family="moe",
    n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=1024,
    vocab=50_304, head_dim=128, tie_embeddings=False, qk_proj_norm=True,
    norm_eps=1e-5, n_experts=64, top_k=8, moe_d_ff=1024,
    shared_expert=False, norm_topk_prob=False,
)

# One stage of a two-stage pipeline deployment: every width as published,
# half the depth.  Eight layers are 7.1 GB of bf16 weights with the
# embedding and head, which leaves a 16 GB TPU v5e room for an 8-slot x
# 4096-token bf16 cache (2.15 GB) and the step's undonated copy of it.
STAGE_LAYERS = 8
STAGE_SLOTS = 8          # decode batch
STAGE_MAX_LEN = 4096     # tokens per slot


def one_chip_stage() -> ArchConfig:
    """The published widths at :data:`STAGE_LAYERS` layers."""
    return dataclasses.replace(CONFIG, name=f"olmoe-1b-7b-{STAGE_LAYERS}L",
                               n_layers=STAGE_LAYERS)
