"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` resolves through :func:`default_interpret`, the one place
that decides it: on a TPU backend the kernels compile through Mosaic, and
anywhere else (the CPU test suite) their bodies run in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.artifact import as_compiled
from .flash_attention import flash_attention
from .moe_dispatch import moe_combine, moe_dispatch
from .ssd_chunk import ssd_chunk


def default_interpret() -> bool:
    """Interpret kernel bodies unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def mha(q, k, v, *, causal=True, window=0, kv_len=None,
        block_q=128, block_k=128, interpret=None):
    """Multi-head attention via the flash kernel.

    q: (B, Sq, H, Dh); k/v: (B, Sk, Hkv, Dh).  GQA is folded: each kv head
    serves H//Hkv query heads through the leading grid axis.
    """
    interpret = default_interpret() if interpret is None else interpret
    B, Sq, H, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Sk, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Sk, Dh)
    out = flash_attention(qf, kf, vf, causal=causal, window=window,
                          kv_len=kv_len, block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)


def gather_banked(table, indices, compiled, *, interpret=None):
    """Gather logical rows from a bank-major table through a compiled
    banking artifact (``plan.compile()``); its strength-reduced resolution
    arithmetic addresses the kernel's row DMAs (see kernels/banked_gather.py).

    ``indices`` may be a flat ``(T,)`` vector or a stacked ``(T, R)``
    matrix of row-sets (one decode tick's reads for every active
    sequence): the batched form issues ONE ``pallas_call`` for the whole
    tick and returns ``(T, R, D)``.

    Accepts a ``CompiledBankingPlan`` or a ``BankingPlan``; passing a raw
    ``BankingSolution`` still works but is deprecated."""
    return as_compiled(compiled).gather(table, indices, interpret=interpret)


def scatter_banked(table, indices, values, compiled, *, col=None,
                   interpret=None):
    """Write logical rows into a bank-major table through a compiled
    banking artifact -- the scatter analogue of :func:`gather_banked`.

    ``indices`` is a flat ``(T,)`` vector of logical addresses.  With
    ``col=None``, ``values`` is ``(T, D)`` replacement rows; with
    ``col`` a ``(T,)`` vector of column indices, ``values`` is ``(T,)``
    scalars -- the serving runtime's batched per-slot token-record
    write.  Returns the updated table; the resolution arithmetic
    addresses the kernel's row DMAs (see kernels/banked_gather.py)."""
    return as_compiled(compiled).scatter(table, indices, values, col=col,
                                         interpret=interpret)


def pack_banked(flat, compiled):
    """Layout conversion: logical (A, D) rows -> bank-major (N, V, D) per
    the compiled artifact's physical layout (reference Eq. 1-2 placement --
    tests assert the kernel's transformed arithmetic agrees with it)."""
    return as_compiled(compiled).pack(flat)


def dispatch(x, slot_token, *, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    x_padded = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return moe_dispatch(x_padded, slot_token, interpret=interpret)


def ssd(x, dt, bm, cm, cum, s_prev, *, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return ssd_chunk(x, dt, bm, cm, cum, s_prev, interpret=interpret)


__all__ = ["default_interpret", "dispatch", "gather_banked", "mha",
           "moe_combine", "pack_banked", "scatter_banked", "ssd"]
