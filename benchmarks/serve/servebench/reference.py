"""Plain float32 reference of the served decoder, and its lower-precision
control.

The reference imports nothing of the program.  It takes the benchmark's
own weights and the token rows the served step was fed, and runs a
causal decoder over each whole row at once: position ``p`` of a row is
the token fed at the ``p``-th step call, rotated by ``p``.  Layer by
layer, with every matrix product at ``Precision.HIGHEST``:

    h = x * rsqrt(mean(x^2) + eps) * (1 + g)           (gain stored as g)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv           (GQA, rotary on q, k)
    x = x + softmax(q k^T / sqrt(d) + causal mask) v Wo
    h = rms-norm(x) with the second gain
    x = x + (silu(h Wg) * (h Wu)) Wd                    (dense)
    x = x + sum_{e in top-k(softmax(h R))} p_e FFN_e(h) (mixture of experts;
                                                         p renormalised over
                                                         the top k when the
                                                         configuration says)
    logits = rms-norm(x) W_head^T

The control is the same computation with every linear layer's weights
and inputs rounded to float8 (e4m3, scaled by their absolute maximum per
output column and per row): the step below bfloat16 that a later change
might be tempted to take.  Attention and the norms stay in float32.

For a mixture of experts the reference also reports, at each served
token's position, how near its routes are to a tie: the smallest margin,
over layers, between the k-th and the (k+1)-th router probability, and
the largest change of any router probability when the router's input is
rounded to bfloat16 (what a bf16 program's own rounding can move).  A
bf16 program may take the other side of a near-tied route, and its token
is then judged against another mixture than the reference's.

A configuration names this file, or a copy of it, under ``"reference"``;
a copy may define ``leaves(spec)`` (see ``weights.leaves``) and read keys
of its own from ``spec.raw``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from .modelspec import ModelSpec

Q_BLOCK = 256        # query rows of attention per block
T_BLOCK = 512        # tokens per block of the expert layer
N_BLOCK = 256        # output rows of the head per block
FP8_MAX = 448.0


def _fp8(x, axis):
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, quant, spec="...k,kn->...n", w_axis=-2):
    import jax
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        a = _fp8(a, -1)
        w = _fp8(w, w_axis)
    return jnp.einsum(spec, a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    import jax.numpy as jnp

    P, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(P, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    import jax
    import jax.numpy as jnp

    B, P, H, dh = q.shape
    hkv = k.shape[2]
    rep = H // hkv
    nb = P // Q_BLOCK
    qb = q.reshape(B, nb, Q_BLOCK, hkv, rep, dh).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(P)
    hi = jax.lax.Precision.HIGHEST

    def one(args):
        qi, i = args
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qi, k, precision=hi)
        s = s / math.sqrt(dh)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhrqk,bkhd->bqhrd", p, v, precision=hi)

    o = jax.lax.map(one, (qb, jnp.arange(nb)))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, P, H * dh)


def _experts(s: ModelSpec, h, lp, quant):
    import jax
    import jax.numpy as jnp

    B, P, D = h.shape
    T = B * P
    x = h.reshape(T, D)
    logits = _mm(x, lp["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    margin, shift = _route_ties(s, x, lp["router"], probs)
    top_p, top_i = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gates = jnp.zeros((T, s.experts), jnp.float32).at[
        jnp.arange(T)[:, None], top_i].set(top_p)

    def block(args):
        xb, gb = args
        g = _mm(xb, lp["we_gate"], quant, "td,edf->tef")
        u = _mm(xb, lp["we_up"], quant, "td,edf->tef")
        y = _mm(jax.nn.silu(g) * u, lp["we_down"], quant, "tef,efd->ted")
        return jnp.einsum("ted,te->td", y, gb,
                          precision=jax.lax.Precision.HIGHEST)

    nb = T // T_BLOCK
    out = jax.lax.map(block, (x.reshape(nb, T_BLOCK, D),
                              gates.reshape(nb, T_BLOCK, s.experts)))
    return out.reshape(B, P, D), (top_i, margin, shift)


def _route_ties(s: ModelSpec, x, router, probs):
    """Per token (T,): the k-th less the (k+1)-th router probability, and
    the largest change of a router probability when the router's input
    ``x`` is rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    if s.top_k < s.experts:
        near = jax.lax.top_k(probs, s.top_k + 1)[0]
        margin = near[:, -2] - near[:, -1]
    else:
        margin = jnp.full(probs.shape[:1], jnp.inf, jnp.float32)
    rounded = jax.nn.softmax(_mm(x.astype(jnp.bfloat16), router, False), -1)
    return margin, jnp.max(jnp.abs(rounded - probs), axis=-1)


def _hidden(s: ModelSpec, params, rows, quant: bool):
    """Final-normed hidden states (B, P, D) and, for a mixture of
    experts, the experts each token is routed to (L, B*P, k), with the
    route margins and bfloat16 shifts of ``_route_ties`` (L, B*P); a
    dense model's are empty."""
    import jax
    import jax.numpy as jnp

    B, P = rows.shape
    H, hkv, dh = s.heads, s.kv_heads, s.head_dim
    x = params["embed"][rows].astype(jnp.float32)

    def body(x, lp):
        h = _rms(x, lp["ln1"], s.norm_eps)
        q = _mm(h, lp["wq"], quant)
        k = _mm(h, lp["wk"], quant)
        v = _mm(h, lp["wv"], quant)
        if s.qkv_bias:
            q = q + lp["bq"].astype(jnp.float32)
            k = k + lp["bk"].astype(jnp.float32)
            v = v + lp["bv"].astype(jnp.float32)
        q = _rope(q.reshape(B, P, H, dh), s.rope_theta)
        k = _rope(k.reshape(B, P, hkv, dh), s.rope_theta)
        v = v.reshape(B, P, hkv, dh)
        x = x + _mm(_attention(q, k, v), lp["wo"], quant)
        h = _rms(x, lp["ln2"], s.norm_eps)
        if s.moe:
            delta, route = _experts(s, h, lp, quant)
        else:
            a = jax.nn.silu(_mm(h, lp["w_gate"], quant)) \
                * _mm(h, lp["w_up"], quant)
            delta = _mm(a, lp["w_down"], quant)
            none = jnp.zeros((B * P, 0), jnp.float32)
            route = (jnp.zeros((B * P, 0), jnp.int32), none, none)
        return x + delta, route

    x, routing = jax.lax.scan(body, x, params["layers"])
    return _rms(x, params["ln_f"], s.norm_eps), routing


def _head(params, h_sel, quant):
    """Logits of the selected hidden rows, in blocks: (N, V)."""
    import jax

    n, D = h_sel.shape

    def one(hb):
        return _mm(hb, params["lm_head"], quant, "nd,vd->nv", w_axis=-1)

    out = jax.lax.map(one, h_sel.reshape(n // N_BLOCK, N_BLOCK, D))
    return out.reshape(n, -1)


def _pad(n: int, block: int) -> int:
    return max(block, -(-n // block) * block)


def compare(s: ModelSpec, params, rows: np.ndarray, calls: np.ndarray,
            slots: np.ndarray, tokens: np.ndarray, control: bool = False
            ) -> Dict[str, Optional[np.ndarray]]:
    """For each served token (row ``slots[i]``, position ``calls[i]``,
    token ``tokens[i]``): how far its reference logit lies below the
    reference's best (``gap``).  With ``control``, also the gap of the
    token the float8 control puts first (``control_gap``).  ``routing``
    is the reference's expert choice per layer, position and row.  For a
    mixture of experts, per served token, ``margin`` is the smallest
    route margin over layers at its position and ``route_shift`` the
    largest bfloat16 shift (``_route_ties``); both are None for a dense
    model."""
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
        if s.moe:
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
               "routing": None, "margin": None, "route_shift": None}
        if s.moe:
            out.update(routing=np.asarray(routing).reshape(
                s.layers, B, Pp, -1)[:, :, :P],
                margin=np.asarray(margin)[:n],
                route_shift=np.asarray(shift)[:n])
        if control:
            ctok = ctrl_pass(params, jnp.asarray(padded), jnp.asarray(sel))
            cgap = best - jnp.take_along_axis(logits, ctok[:, None],
                                              axis=-1)[:, 0]
            out["control_gap"] = np.asarray(cgap)[:n]
    return out
