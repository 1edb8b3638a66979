"""Operations and bytes that a served step and the banked kernels need,
worked out from shapes.

What is counted is what the work needs, not what an implementation
happens to move: the weights once, the keys and values of each request's
own tokens (not the whole ``max_len`` buffer, and not a copy of an
undonated cache), the new key/value row, and for a mixture of experts
only the experts that the batch's tokens are routed to.  The banked
kernels are counted by the logical rows and elements they read and
write, not by the tile padding.  So a count never depends on how the
program implements a step, and a share of the roofline never exceeds
100%.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .modelspec import ModelSpec

BF16 = 2
F32 = 4
INT32 = 4


def _attn_weights(s: ModelSpec) -> int:
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    n = s.d_model * (q + 2 * kv) + q * s.d_model
    if s.qkv_bias:
        n += q + 2 * kv
    return n + 2 * s.d_model          # two norm gains


def _expert_weights(s: ModelSpec) -> int:
    return 3 * s.d_model * s.expert_ff


def active_params(s: ModelSpec) -> int:
    """Parameters one token multiplies by (the embedding is a lookup and
    does not count; the output head does)."""
    per_layer = _attn_weights(s)
    if s.moe:
        per_layer += s.d_model * s.experts + s.top_k * _expert_weights(s)
    else:
        per_layer += 3 * s.d_model * s.d_ff
    return s.layers * per_layer + s.vocab * s.d_model + s.d_model


def step_cost(s: ModelSpec, lengths: Sequence[int],
              experts_used: Optional[Sequence[int]] = None
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one model step that advances each listed
    request by one token; ``lengths[i]`` is that request's own tokens so
    far, the new one included.  ``experts_used[l]`` is how many distinct
    experts layer ``l`` routes those tokens to (MoE only)."""
    n = len(lengths)
    if n == 0:
        return 0.0, 0.0
    kv_row = 2 * s.kv_heads * s.head_dim                  # k and v
    ctx = sum(lengths)
    flops = 2.0 * active_params(s) * n
    flops += s.layers * 2 * 2 * s.heads * s.head_dim * ctx   # qk and pv
    weights = s.layers * _attn_weights(s) * BF16
    if s.moe:
        if experts_used is None or len(experts_used) != s.layers:
            raise ValueError("a MoE step needs the experts used per layer")
        weights += s.layers * s.d_model * s.experts * F32    # router
        weights += sum(experts_used) * _expert_weights(s) * BF16
    else:
        weights += s.layers * 3 * s.d_model * s.d_ff * BF16
    weights += (s.vocab * s.d_model + s.d_model) * BF16      # head, ln_f
    weights += n * s.d_model * BF16                          # embed rows
    cache = s.layers * kv_row * BF16 * (ctx - n)             # read
    cache += s.layers * kv_row * BF16 * n                    # new rows
    return flops, float(weights + cache)


def gather_bytes(rows: int, row_width: int) -> float:
    """The banked gather: each logical row read once and written out."""
    return 2.0 * rows * row_width * INT32


def record_write_bytes(elements: int) -> float:
    """The per-slot record write: one int32 element each."""
    return float(elements * INT32)
