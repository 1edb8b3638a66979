"""Flash attention Pallas kernel (TPU target, BlockSpec VMEM tiling).

Grid: (batch*heads, q_blocks, k_blocks) with the k dimension innermost and
marked 'arbitrary' so the VMEM scratch accumulators (m, l, acc) carry
across k steps -- the online-softmax recurrence.  Causal and sliding-window
masks are applied from absolute positions derived from program ids; GQA is
handled in ops.py by folding the q-head group into the leading axis so each
kernel instance reads one kv head.

Block shapes default to (128, 128): MXU-aligned (128x128 systolic array),
and the VMEM working set per step is
q(128xD) + k/v(128xD) + acc(128xD) + scores(128x128) floats -- ~0.5 MB for
D=256, comfortably inside the ~16 MB/core VMEM budget.

Validated in interpret mode against ``ref.mha_reference`` (pure jnp) over a
shape/dtype sweep in tests/test_kernels.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_len: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kb == nkb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, kv_len=None,
                    scale=None, block_q=128, block_k=128,
                    interpret=False):
    """q: (BH, Sq, D), k/v: (BH, Sk, D) -- heads pre-folded into batch.

    Sliding-window masking uses absolute positions (q row i attends to
    [i-window+1, i]).  ``kv_len`` masks a padded KV buffer (decode).
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    kv_len = Sk if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
