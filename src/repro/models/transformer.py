"""Decoder-only transformer LM (dense + GQA), scan-over-layers.

Covers gemma3 (5:1 local:global sliding window), deepseek/qwen2/internlm2
(plain GQA; qwen2 adds QKV bias), and chameleon (early-fusion VLM: the VQ
image tokens share the text vocabulary, frontend stubbed to token ids).

Layer parameters are stacked on a leading L axis and consumed by
``jax.lax.scan`` so compiled HLO size is O(1) in depth (95-layer deepseek
compiles like a 1-layer model).  Each scanned body is wrapped in
``jax.checkpoint`` (full remat) so training activation memory is the
residual stream only.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..parallel.hints import hint
from .layers import (apply_rope, chunked_attention, dense_init, rms_norm,
                     split_keys, swiglu)

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig) -> np.ndarray:
    """Per-layer attention window (0 = global) for local:global patterns."""
    if cfg.sliding_window and cfg.local_global_ratio:
        period = cfg.local_global_ratio + 1
        return np.array(
            [0 if (i + 1) % period == 0 else cfg.sliding_window
             for i in range(cfg.n_layers)], dtype=np.int32)
    if cfg.sliding_window:
        return np.full(cfg.n_layers, cfg.sliding_window, dtype=np.int32)
    return np.zeros(cfg.n_layers, dtype=np.int32)


def init_dense_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 10)
    lk = split_keys(ks[0], L)

    def stack(f):
        return jnp.stack([f(k) for k in lk])

    p: Params = {
        "embed": dense_init(ks[1], (V, D), scale=0.02, dtype=dtype),
        "ln_f": jnp.zeros((D,), dtype),
        "layers": {
            "ln1": jnp.zeros((L, D), dtype),
            "ln2": jnp.zeros((L, D), dtype),
            "wq": stack(lambda k: dense_init(k, (D, H * Dh), dtype=dtype)),
            "wk": stack(lambda k: dense_init(k, (D, Hkv * Dh), dtype=dtype)),
            "wv": stack(lambda k: dense_init(k, (D, Hkv * Dh), dtype=dtype)),
            "wo": stack(lambda k: dense_init(k, (H * Dh, D), dtype=dtype)),
            "w_gate": stack(lambda k: dense_init(k, (D, F), dtype=dtype)),
            "w_up": stack(lambda k: dense_init(k, (D, F), dtype=dtype)),
            "w_down": stack(lambda k: dense_init(k, (F, D), dtype=dtype)),
        },
    }
    if cfg.qkv_bias:
        p["layers"]["bq"] = jnp.zeros((L, H * Dh), dtype)
        p["layers"]["bk"] = jnp.zeros((L, Hkv * Dh), dtype)
        p["layers"]["bv"] = jnp.zeros((L, Hkv * Dh), dtype)
    if cfg.qk_proj_norm:
        p["layers"]["q_norm"] = jnp.zeros((L, H * Dh), dtype)
        p["layers"]["k_norm"] = jnp.zeros((L, Hkv * Dh), dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], (V, D), scale=0.02, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Attention block (shared by train / prefill / decode)
# ---------------------------------------------------------------------------


def _project_q(cfg: ArchConfig, lp, x, q_offset):
    """Rotated queries (B, S, H, Dh).  With ``cfg.qk_proj_norm`` the whole
    projection (all H * Dh of it, not each head) is RMS-normed first, as
    OLMoE does."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, lp["wq"])
    if "bq" in lp:
        q = q + lp["bq"]
    if cfg.qk_proj_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
    q = q.reshape(B, S, cfg.n_heads, cfg.hd)
    return apply_rope(q, jnp.arange(S) + q_offset, cfg.rope_theta)


def _attn(cfg: ArchConfig, lp, x, *, k_full, v_full, window, q_offset,
          kv_len, block_k=1024):
    B, S = x.shape[:2]
    q = _project_q(cfg, lp, x, q_offset)
    out = chunked_attention(
        q, k_full, v_full, causal=True, window=window,
        q_offset=q_offset, kv_len=kv_len, block_k=block_k)
    return out.reshape(B, S, cfg.n_heads * cfg.hd) @ lp["wo"]


def _project_kv(cfg, lp, x, q_offset):
    """Rotated keys and values (B, S, Hkv, Dh); keys are normed over the
    whole projection width first with ``cfg.qk_proj_norm``."""
    B, S, _ = x.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.hd
    k = jnp.einsum("bsd,dh->bsh", x, lp["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, lp["wv"])
    if "bk" in lp:
        k, v = k + lp["bk"], v + lp["bv"]
    if cfg.qk_proj_norm:
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    k = apply_rope(k, jnp.arange(S) + q_offset, cfg.rope_theta)
    return k, v


def attention_block(cfg: ArchConfig, lp, x, window, *, cache_kv=None,
                    pos=0, block_k=1024):
    """The attention half of a transformer block: the residual stream after
    attention, the normed input of the feed-forward, and the new (k, v).
    cache_kv=(k,v) full-length buffers for decode; otherwise
    self-attention over the current sequence."""
    h = hint(rms_norm(x, lp["ln1"], cfg.norm_eps), "block_in")
    if cache_kv is None:
        k, v = _project_kv(cfg, lp, h, pos)
        attn = _attn(cfg, lp, h, k_full=k, v_full=v, window=window,
                     q_offset=pos, kv_len=None, block_k=block_k)
        new_kv = (k, v)
    else:
        k_new, v_new = _project_kv(cfg, lp, h, pos)
        k_buf, v_buf = cache_kv
        k_buf = jax.lax.dynamic_update_slice(k_buf, k_new.astype(k_buf.dtype),
                                             (0, pos, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(v_buf, v_new.astype(v_buf.dtype),
                                             (0, pos, 0, 0))
        attn = _attn(cfg, lp, h, k_full=k_buf, v_full=v_buf, window=window,
                     q_offset=pos, kv_len=pos + x.shape[1], block_k=block_k)
        new_kv = (k_buf, v_buf)
    x = x + attn
    return x, hint(rms_norm(x, lp["ln2"], cfg.norm_eps), "block_in"), new_kv


def dense_layer(cfg: ArchConfig, lp, x, window, *, cache_kv=None, pos=0,
                block_k=1024):
    """One transformer block: :func:`attention_block`, then the SwiGLU
    feed-forward."""
    x, h, new_kv = attention_block(cfg, lp, x, window, cache_kv=cache_kv,
                                   pos=pos, block_k=block_k)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), new_kv


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------


def _scan_layers(cfg: ArchConfig, params: Params, x: Array, body):
    windows = jnp.asarray(layer_windows(cfg))
    lp = params["layers"]

    def wrapped(carry, xs):
        return body(carry, xs)

    wrapped = jax.checkpoint(wrapped, prevent_cse=False)
    carry, ys = jax.lax.scan(wrapped, x, (lp, windows))
    return carry, ys


def forward(cfg: ArchConfig, params: Params, tokens: Array,
            block_k: int = 1024) -> Array:
    """Training/prefill forward to final hidden states (B, S, D)."""
    x = hint(params["embed"].astype(jnp.bfloat16)[tokens], "residual")

    def body(x, xs):
        lp, window = xs
        x, _ = dense_layer(cfg, lp, x, window, block_k=block_k)
        return hint(x, "residual"), None

    x, _ = _scan_layers(cfg, params, x, body)
    return rms_norm(x, params["ln_f"], cfg.norm_eps)


def logits_fn(cfg: ArchConfig, params: Params, h: Array) -> Array:
    w = params.get("lm_head", params["embed"])
    return jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype))


def chunked_xent(cfg: ArchConfig, params: Params, h: Array, labels: Array,
                 chunk: int = 512) -> Array:
    """Cross-entropy without materializing (B, S, V) logits at once.

    The gold logit is extracted with a one-hot contraction (not
    take_along_axis) so a vocab-sharded lm_head reduces with one small
    all-reduce instead of gathering the logits chunk.
    """
    from ..parallel.hints import hint

    B, S, D = h.shape
    w = params.get("lm_head", params["embed"]).astype(jnp.float32)
    nchunks = max(1, S // chunk)
    hs = h.reshape(B, nchunks, S // nchunks, D)
    ls = labels.reshape(B, nchunks, S // nchunks)

    def one(args):
        hc, lc = args  # (B, c, D), (B, c)
        logits = jnp.einsum("bcd,vd->bcv", hc.astype(jnp.float32), w)
        logits = hint(logits, "logits")
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = (lc[..., None] ==
                  jnp.arange(logits.shape[-1])[None, None, :])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        return lse - gold

    losses = jax.lax.map(one, (hs.transpose(1, 0, 2, 3), ls.transpose(1, 0, 2)))
    return losses.mean()


def lm_loss(cfg: ArchConfig, params: Params, batch: Dict[str, Array]) -> Array:
    h = forward(cfg, params, batch["tokens"])
    return chunked_xent(cfg, params, h, batch["labels"])


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with a fixed-capacity KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: Array   # (L, B, Smax, Hkv, Dh)
    v: Array
    pos: Array  # scalar int32: number of valid tokens


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def decode_step(cfg: ArchConfig, params: Params, cache: KVCache,
                tokens: Array, block_k: int = 1024
                ) -> Tuple[Array, KVCache]:
    """One decode step: tokens (B, 1) -> logits (B, V), updated cache."""
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(layer_windows(cfg))
    lp = params["layers"]
    pos = cache.pos

    def body(x, xs):
        lp_l, window, kc, vc = xs
        x, (kc, vc) = dense_layer(cfg, lp_l, x, window, cache_kv=(kc, vc),
                                  pos=pos, block_k=block_k)
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(body, x, (lp, windows, cache.k, cache.v))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(cfg, params, h)[:, 0]
    return logits, KVCache(k_new, v_new, pos + 1)


def prefill(cfg: ArchConfig, params: Params, tokens: Array, max_len: int,
            block_k: int = 1024) -> Tuple[Array, KVCache]:
    """Prefill the cache with a full prompt; returns last-token logits."""
    B, S = tokens.shape
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(layer_windows(cfg))
    lp = params["layers"]

    def body(x, xs):
        lp_l, window = xs
        x, (k, v) = dense_layer(cfg, lp_l, x, window, block_k=block_k)
        x = hint(x, "residual")
        pad = max_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, (k, v)

    body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs) = jax.lax.scan(body, x, (lp, windows))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(cfg, params, h[:, -1:])[:, 0]
    return logits, KVCache(ks, vs, jnp.asarray(S, jnp.int32))


# ---------------------------------------------------------------------------
# Grouped decode for local:global architectures (gemma3) -- OPTIMIZED PATH
#
# Beyond-paper Perf iteration (EXPERIMENTS.md §Perf): local-attention layers
# keep only a ``window``-sized RING cache.  The ring slot index is
# ``pos mod window`` -- a hyperplane bank address (Eq. 1) with N = window,
# B = 1, and window a power of two, so the Sec-3.4 transform reduces the
# bank-resolution to a single AND mask.  Capacity and HBM traffic for the
# 5-of-6 local layers drop from O(S_ctx) to O(window).
# ---------------------------------------------------------------------------


class GroupedKVCache(NamedTuple):
    k_local: Array   # (G, R, B, W, Hkv, Dh) ring buffers (R local layers/group)
    v_local: Array
    k_global: Array  # (G, B, Smax, Hkv, Dh)
    v_global: Array
    pos: Array


def grouped_layout(cfg: ArchConfig) -> Tuple[int, int]:
    """(groups, locals_per_group); requires the 5:1-style layer pattern."""
    assert cfg.sliding_window and cfg.local_global_ratio
    period = cfg.local_global_ratio + 1
    assert cfg.n_layers % period == 0
    return cfg.n_layers // period, cfg.local_global_ratio


def init_grouped_cache(cfg: ArchConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16) -> GroupedKVCache:
    G, R = grouped_layout(cfg)
    W = cfg.sliding_window
    Hkv, Dh = cfg.n_kv_heads, cfg.hd
    return GroupedKVCache(
        jnp.zeros((G, R, batch, W, Hkv, Dh), dtype),
        jnp.zeros((G, R, batch, W, Hkv, Dh), dtype),
        jnp.zeros((G, batch, max_len, Hkv, Dh), dtype),
        jnp.zeros((G, batch, max_len, Hkv, Dh), dtype),
        jnp.zeros((), jnp.int32),
    )


def _grouped_params(cfg: ArchConfig, params: Params):
    """Restack (L, ...) layer params into local (G, R, ...) + global (G, ...)."""
    G, R = grouped_layout(cfg)
    period = R + 1
    lp = params["layers"]

    def split(x):
        xg = x.reshape((G, period) + x.shape[1:])
        return xg[:, :R], xg[:, R]

    local, glob = {}, {}
    for k, v in lp.items():
        l, g = split(v)
        local[k], glob[k] = l, g
    return local, glob


def grouped_decode_step(cfg: ArchConfig, params: Params,
                        cache: GroupedKVCache, tokens: Array,
                        block_k: int = 1024) -> Tuple[Array, GroupedKVCache]:
    """One decode step with ring-buffered local layers."""
    from ..parallel.hints import hint
    W = cfg.sliding_window
    Hkv, Dh, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    pos = cache.pos
    slot = jax.lax.rem(pos, W)  # ring bank address: pos & (W-1) once lowered
    local_p, global_p = _grouped_params(cfg, params)

    def local_layer(x, lp, kc, vc):
        B, S, D = x.shape
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        k_new, v_new = _project_kv(cfg, lp, h, pos)
        kc = jax.lax.dynamic_update_slice(kc, k_new.astype(kc.dtype),
                                          (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v_new.astype(vc.dtype),
                                          (0, slot, 0, 0))
        # ring holds the last W tokens; absolute position of ring row r is
        # recovered from the bank equation -- attention over W rows, masked
        # by true recency.  kv_len = min(pos+1, W): all rows valid once full.
        q = _project_q(cfg, lp, h, pos)
        # positions of ring rows: row r came from pos' = r + W*floor(...) --
        # reconstruct: rows (slot-W, slot] hold positions (pos-W, pos]
        row = jnp.arange(W)
        age = jax.lax.rem(slot - row + W, W)          # 0 = newest
        k_pos = pos - age
        valid = (k_pos >= 0) & (k_pos > pos - W)
        from .layers import NEG_INF
        q5 = (q.astype(jnp.float32) / (Dh ** 0.5)).reshape(
            B, S, Hkv, H // Hkv, Dh)
        s = jnp.einsum("bqhrd,bkhd->bqhrk", q5, kc.astype(jnp.float32))
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        o = jnp.einsum("bqhrk,bkhd->bqhrd", p, vc.astype(jnp.float32))
        o = (o / jnp.maximum(p.sum(-1)[..., None], 1e-30)).reshape(B, S, H * Dh)
        x = x + o.astype(x.dtype) @ lp["wo"]
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]), kc, vc

    def group_body(x, xs):
        lpl, lpg, klg, vlg, kgg, vgg = xs

        def inner(x, ys):
            lp_i, kc, vc = ys
            x, kc, vc = local_layer(x, lp_i, kc, vc)
            return x, (kc, vc)

        x, (kl_new, vl_new) = jax.lax.scan(inner, x, (lpl, klg, vlg))
        x, (kg_new, vg_new) = dense_layer(cfg, lpg, x, 0,
                                          cache_kv=(kgg, vgg), pos=pos,
                                          block_k=block_k)
        return x, (kl_new, vl_new, kg_new, vg_new)

    x, (kl, vl, kg, vg) = jax.lax.scan(
        group_body, x,
        (local_p, global_p, cache.k_local, cache.v_local,
         cache.k_global, cache.v_global))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(cfg, params, h)[:, 0]
    return logits, GroupedKVCache(kl, vl, kg, vg, pos + 1)


# ---------------------------------------------------------------------------
# int8-quantized KV cache (beyond-paper Perf iteration)
#
# Banking view: the cache word width is the solver's ``word_bits`` -- halving
# it halves both bank capacity and the bytes every decode step must stream.
# Per-(token, head) max-abs scales keep the attention error ~0.5%.
# ---------------------------------------------------------------------------


class QuantKVCache(NamedTuple):
    k_q: Array    # (L, B, Smax, Hkv, Dh) int8
    v_q: Array
    k_s: Array    # (L, B, Smax, Hkv) f32 scales
    v_s: Array
    pos: Array


def init_quant_cache(cfg: ArchConfig, batch: int, max_len: int
                     ) -> QuantKVCache:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return QuantKVCache(
        jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape[:-1], jnp.float32), jnp.zeros(shape[:-1], jnp.float32),
        jnp.zeros((), jnp.int32))


def _quant_rows(x: Array):
    """x (B, S, Hkv, Dh) -> int8 rows + per-(token, head) scales."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def decode_step_quant(cfg: ArchConfig, params: Params, cache: QuantKVCache,
                      tokens: Array, block_k: int = 1024
                      ) -> Tuple[Array, QuantKVCache]:
    """decode_step against an int8 cache: new rows quantized on write, the
    whole buffer dequantized lazily on read (XLA streams int8 from HBM and
    fuses the scale multiply into the attention contraction)."""
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(layer_windows(cfg))
    lp = params["layers"]
    pos = cache.pos

    def body(x, xs):
        lp_l, window, kq, vq, ks, vs = xs
        h = hint(rms_norm(x, lp_l["ln1"], cfg.norm_eps), "block_in")
        k_new, v_new = _project_kv(cfg, lp_l, h, pos)
        knq, kns = _quant_rows(k_new)
        vnq, vns = _quant_rows(v_new)
        kq = jax.lax.dynamic_update_slice(kq, knq, (0, pos, 0, 0))
        vq = jax.lax.dynamic_update_slice(vq, vnq, (0, pos, 0, 0))
        ks = jax.lax.dynamic_update_slice(ks, kns, (0, pos, 0))
        vs = jax.lax.dynamic_update_slice(vs, vns, (0, pos, 0))
        k_deq = kq.astype(jnp.bfloat16) * ks[..., None].astype(jnp.bfloat16)
        v_deq = vq.astype(jnp.bfloat16) * vs[..., None].astype(jnp.bfloat16)
        attn = _attn(cfg, lp_l, h, k_full=k_deq, v_full=v_deq, window=window,
                     q_offset=pos, kv_len=pos + x.shape[1], block_k=block_k)
        x = x + attn
        h = hint(rms_norm(x, lp_l["ln2"], cfg.norm_eps), "block_in")
        x = x + swiglu(h, lp_l["w_gate"], lp_l["w_up"], lp_l["w_down"])
        return x, (kq, vq, ks, vs)

    x, (kq, vq, ks, vs) = jax.lax.scan(
        body, x, (lp, windows, cache.k_q, cache.v_q, cache.k_s, cache.v_s))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(cfg, params, h)[:, 0]
    return logits, QuantKVCache(kq, vq, ks, vs, pos + 1)
