"""SSD chunk kernel (Mamba2 state-space duality) in Pallas.

Computes one chunk of the SSD recurrence for a block of heads:

    y_intra = ((C B^T) .* L) (dt .* X)        -- Q x Q matmul form (MXU)
    y_inter = diag(exp(cum)) C S_prev^T
    S_new   = exp(cum_Q) S_prev + B^T diag(exp(cum_Q - cum) dt) X

Grid: (batch, heads) -- each instance owns one (Q, P) x (Q, N) working set.
VMEM: Q=256, N=128, P=64 fp32 => CB^T (256x256) 256 KB + operands ~0.5 MB,
well inside VMEM.  The inter-chunk scan (carrying S) stays in JAX
(models/ssm.py); the kernel is the per-chunk compute hot spot.

Oracle: ref.ssd_chunk_reference == one scan step of models.ssm.ssd_chunked.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, cum_ref, s_ref,
                y_ref, snew_ref):
    # blocks: x (1,1,Q,P), dt/cum (1,1,Q), b/c (1,Q,N), s (1,1,P,N)
    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q,)
    bm = b_ref[0].astype(jnp.float32)          # (Q, N)
    cm = c_ref[0].astype(jnp.float32)          # (Q, N)
    cum = cum_ref[0, 0].astype(jnp.float32)    # (Q,)
    s_prev = s_ref[0, 0].astype(jnp.float32)   # (P, N)

    Q = x.shape[0]
    rel = cum[:, None] - cum[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    rel = jnp.where(causal, rel, -jnp.inf)  # mask before exp
    Lmat = jnp.exp(rel)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    W = scores * Lmat                          # (Q, Q)
    xdt = x * dt[:, None]                      # (Q, P)
    y_intra = jax.lax.dot(W, xdt, preferred_element_type=jnp.float32)
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cm, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (Q, P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    decay_to_end = jnp.exp(cum[-1] - cum)      # (Q,)
    # S_new = exp(cum_Q) * S_prev + (xdt * decay)^T B   -> (P, N)
    s_add = jax.lax.dot_general(
        xdt * decay_to_end[:, None], bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    snew_ref[0, 0] = jnp.exp(cum[-1]) * s_prev + s_add


def ssd_chunk(x, dt, bm, cm, cum, s_prev, *, interpret=False):
    """One SSD chunk for all (batch, head) pairs.

    x: (B, H, Q, P); dt/cum: (B, H, Q); bm/cm: (B, Q, N);
    s_prev: (B, H, P, N).  Returns (y (B, H, Q, P), s_new (B, H, P, N)).
    """
    B, H, Q, P = x.shape
    N = bm.shape[-1]
    y, s_new = pl.pallas_call(
        _ssd_kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, dt, bm, cm, cum, s_prev)
    return y, s_new
