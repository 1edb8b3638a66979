"""Plain float32 reference of OLMoE-1B-7B-0924 (arXiv:2409.02060), and its
float8 control.

The reference imports nothing of the program.  It shares ``reference``'s
helpers (matrix products at ``Precision.HIGHEST``, the RMS norm, rotary,
blocked causal attention, the expert layer with its route margins, the
blocked head) and differs from that decoder in its attention: the q and k
projections are RMS-normed over their whole width, all heads at once,
before they are split into heads and rotated.  Layer by layer:

    h = x * rsqrt(mean(x^2) + eps) * (1 + g1)          (gains stored as g)
    q = rms-norm(h Wq) with gain q_norm                 (over all H * dh)
    k = rms-norm(h Wk) with gain k_norm                 (over all Hkv * dh)
    v = h Wv                                            (no biases)
    q, k = rotary(q), rotary(k)                         (per head of dh)
    x = x + softmax(q k^T / sqrt(dh) + causal mask) v Wo
    h = rms-norm(x) with gain g2
    x = x + sum_{e in top-k(softmax(h R))} p_e FFN_e(h) (p as the softmax
                                                         gives it; the top k
                                                         are renormalised only
                                                         if the configuration's
                                                         norm_topk_prob says)
    FFN_e(h) = (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = rms-norm(x) with gain g_f, times W_head^T  (head not tied)

The control computes the same with every linear layer's weights and
inputs rounded to float8 (e4m3), as ``reference`` describes; attention and
the norms, the q/k norm among them, stay in float32.

Departures from the published model: the weights are random, seeded, in
bfloat16 (the router's in float32, where the checkpoint stores bfloat16);
the depth is the configuration's, cut from 16 layers; every width, the
64 experts and the top 8 are as published.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .modelspec import ModelSpec
from .reference import (N_BLOCK, Q_BLOCK, T_BLOCK, _attention, _experts,
                        _head, _mm, _pad, _rms, _rope)
from .weights import NORM_STD, leaves as _base_leaves


def leaves(s: ModelSpec):
    """The benchmark's tree with the two q/k norm gains per layer."""
    tree = _base_leaves(s)
    L = s.layers
    tree["layers"]["q_norm"] = ((L, s.heads * s.head_dim), "bfloat16",
                                NORM_STD)
    tree["layers"]["k_norm"] = ((L, s.kv_heads * s.head_dim), "bfloat16",
                                NORM_STD)
    return tree


def _hidden(s: ModelSpec, params, rows, quant: bool):
    """Final-normed hidden states (B, P, D) and the routing of every layer
    (experts (L, B*P, k), route margins and bfloat16 shifts (L, B*P)), as
    ``reference._hidden`` gives them for a mixture of experts."""
    import jax
    import jax.numpy as jnp

    B, P = rows.shape
    H, hkv, dh, eps = s.heads, s.kv_heads, s.head_dim, s.norm_eps
    x = params["embed"][rows].astype(jnp.float32)

    def body(x, lp):
        h = _rms(x, lp["ln1"], eps)
        q = _rms(_mm(h, lp["wq"], quant), lp["q_norm"], eps)
        k = _rms(_mm(h, lp["wk"], quant), lp["k_norm"], eps)
        v = _mm(h, lp["wv"], quant).reshape(B, P, hkv, dh)
        q = _rope(q.reshape(B, P, H, dh), s.rope_theta)
        k = _rope(k.reshape(B, P, hkv, dh), s.rope_theta)
        x = x + _mm(_attention(q, k, v), lp["wo"], quant)
        delta, route = _experts(s, _rms(x, lp["ln2"], eps), lp, quant)
        return x + delta, route

    x, routing = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["ln_f"], eps), routing


def compare(s: ModelSpec, params, rows: np.ndarray, calls: np.ndarray,
            slots: np.ndarray, tokens: np.ndarray, control: bool = False
            ) -> Dict[str, Optional[np.ndarray]]:
    """``reference.compare`` for this model: per served token (row
    ``slots[i]``, position ``calls[i]``, token ``tokens[i]``) the gap of
    its reference logit below the reference's best (``gap``), with
    ``control`` the gap of the float8 control's first choice
    (``control_gap``), the reference's experts per layer, row and position
    (``routing``), and per served token the smallest route margin over
    layers (``margin``) and the largest bfloat16 shift (``route_shift``)."""
    import jax
    import jax.numpy as jnp

    B, P = rows.shape
    Pp = _pad(P, max(Q_BLOCK, T_BLOCK))
    padded = np.zeros((B, Pp), np.int32)
    padded[:, :P] = rows
    n = len(tokens)
    Np = _pad(n, N_BLOCK)
    sel = np.zeros((Np, 2), np.int32)
    sel[:n, 0], sel[:n, 1] = slots, calls
    tok = np.zeros(Np, np.int32)
    tok[:n] = tokens

    @jax.jit
    def ref_pass(params, rows, sel, tok):
        h, (routing, margin, shift) = _hidden(s, params, rows, False)
        logits = _head(params, h[sel[:, 0], sel[:, 1]], False)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        at = (slice(None), sel[:, 0], sel[:, 1])
        margin = jnp.min(margin.reshape(-1, B, Pp)[at], axis=0)
        shift = jnp.max(shift.reshape(-1, B, Pp)[at], axis=0)
        return best - got, best, logits, routing, margin, shift

    @jax.jit
    def ctrl_pass(params, rows, sel):
        h, _ = _hidden(s, params, rows, True)
        return jnp.argmax(_head(params, h[sel[:, 0], sel[:, 1]], True),
                          axis=-1)

    with jax.default_matmul_precision("highest"):
        gap, best, logits, routing, margin, shift = ref_pass(
            params, jnp.asarray(padded), jnp.asarray(sel), jnp.asarray(tok))
        out = {"gap": np.asarray(gap)[:n], "control_gap": None,
               "routing": np.asarray(routing).reshape(
                   s.layers, B, Pp, -1)[:, :, :P],
               "margin": np.asarray(margin)[:n],
               "route_shift": np.asarray(shift)[:n]}
        if control:
            ctok = ctrl_pass(params, jnp.asarray(padded), jnp.asarray(sel))
            cgap = best - jnp.take_along_axis(logits, ctok[:, None],
                                              axis=-1)[:, 0]
            out["control_gap"] = np.asarray(cgap)[:n]
    return out
