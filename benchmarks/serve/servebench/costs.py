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

The weights are counted from the configuration's leaf tree
(``weights.model_leaves``), so a leaf that a configuration's reference
adds is counted with the others: every layer leaf is read once a step,
except the expert stacks (``we_*``, shaped layers x experts x ...), of
which a token multiplies by ``top_k`` experts and a step reads the experts
its tokens are routed to; of the top-level leaves, the embedding is a
lookup of the rows fed and every other leaf (the head, the final norm) is
read once.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

from .modelspec import ModelSpec
from .weights import model_leaves

BF16 = 2
INT32 = 4
EXPERT_PREFIX = "we_"
EMBED = "embed"


def _weights(s: ModelSpec) -> Tuple[int, int, int, int, int]:
    """Elements and bytes read once a step (every leaf but the embedding
    and the expert stacks), elements and bytes of one expert in one
    layer, and the bytes of one embedding row."""
    import jax.numpy as jnp

    tree = model_leaves(s)
    once = once_b = expert = expert_b = 0
    for name, (shape, dt, _) in tree["layers"].items():
        size = jnp.dtype(dt).itemsize
        if name.startswith(EXPERT_PREFIX):
            n = math.prod(shape[2:])
            expert, expert_b = expert + n, expert_b + n * size
        else:
            n = math.prod(shape)
            once, once_b = once + n, once_b + n * size
    for name, (shape, dt, _) in tree["top"].items():
        if name != EMBED:
            n = math.prod(shape)
            once, once_b = once + n, once_b + n * jnp.dtype(dt).itemsize
    shape, dt, _ = tree["top"][EMBED]
    return once, once_b, expert, expert_b, \
        math.prod(shape[1:]) * jnp.dtype(dt).itemsize


def active_params(s: ModelSpec) -> int:
    """Parameters one token multiplies by (the embedding is a lookup and
    does not count; the output head does)."""
    once, _, expert, _, _ = _weights(s)
    return once + s.layers * s.top_k * expert


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
    _, once_b, expert, expert_b, embed_row_b = _weights(s)
    kv_row = 2 * s.kv_heads * s.head_dim                  # k and v
    ctx = sum(lengths)
    flops = 2.0 * active_params(s) * n
    flops += s.layers * 2 * 2 * s.heads * s.head_dim * ctx   # qk and pv
    weights = once_b + n * embed_row_b
    if expert:
        if experts_used is None or len(experts_used) != s.layers:
            raise ValueError("a MoE step needs the experts used per layer")
        weights += sum(experts_used) * expert_b
    cache = s.layers * kv_row * BF16 * (ctx - n)             # read
    cache += s.layers * kv_row * BF16 * n                    # new rows
    return flops, float(weights + cache)


def gather_bytes(rows: int, row_width: int) -> float:
    """The banked gather: each logical row read once and written out."""
    return 2.0 * rows * row_width * INT32


def record_write_bytes(elements: int) -> float:
    """The per-slot record write: one int32 element each."""
    return float(elements * INT32)
