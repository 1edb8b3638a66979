"""Mixture-of-Experts transformer (llama4-maverick, olmoe).

Expert dispatch is a *banking problem* (DESIGN.md Sec 2): experts are banks,
the router emits the access pattern, capacity is the port count, and the
token->expert crossbar is the FO/FI fan the paper's metrics size.  The
banking solver picks the expert-parallel layout (see parallel/sharding.py);
here we implement the datapath.

Two implementations:

* ``dense``  -- every expert runs on every token, outputs mixed by routing
  probability.  Exact (no capacity drops); O(T*E*F) -- the smoke/oracle path
  and the reference for the moe_dispatch Pallas kernel.
* ``sorted`` -- production path: top-k routing, argsort tokens by expert,
  capacity-bounded scatter into an (E, C, D) buffer (the all-to-all when E
  is sharded over the model axis), per-expert SwiGLU, weighted scatter-add
  back.  Tokens past capacity are dropped, exactly like Switch/GShard.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..parallel.hints import hint
from .layers import dense_init, rms_norm, split_keys, swiglu
from . import transformer as tfm

Array = jax.Array
Params = Dict[str, Any]


def init_moe_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    p = tfm.init_dense_params(cfg, key, dtype)
    L, D, E, Fm = cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = split_keys(jax.random.fold_in(key, 7), 4)
    lyr = p["layers"]
    if not cfg.shared_expert:
        # routed experts replace the dense FFN entirely
        for k in ("w_gate", "w_up", "w_down"):
            del lyr[k]
    lyr["router"] = dense_init(ks[0], (L, D, E), scale=0.02, dtype=jnp.float32)
    lyr["we_gate"] = dense_init(ks[1], (L, E, D, Fm), scale=1 / math.sqrt(D), dtype=dtype)
    lyr["we_up"] = dense_init(ks[2], (L, E, D, Fm), scale=1 / math.sqrt(D), dtype=dtype)
    lyr["we_down"] = dense_init(ks[3], (L, E, Fm, D), scale=1 / math.sqrt(Fm), dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Routing + dispatch
# ---------------------------------------------------------------------------


def _route(cfg: ArchConfig, router_w: Array, xt: Array):
    """xt (T, D) -> (probs (T, K), idx (T, K), aux load-balance loss).
    The top-k probabilities are renormalised to sum to one only with
    ``cfg.norm_topk_prob``; OLMoE gates by the raw softmax."""
    logits = xt.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss: E * sum_e f_e * p_e
    E = probs.shape[-1]
    me = probs.mean(0)
    one_hot = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32)
    fe = one_hot.mean(0)
    aux = E * jnp.sum(fe * me)
    return top_p, top_i, aux


def moe_ffn_dense(cfg: ArchConfig, lp, h: Array
                  ) -> Tuple[Array, Array, Array]:
    """Oracle path: run all experts on all tokens (small shapes only).
    Like every dispatch of :data:`MOE_IMPLS` it returns the layer's
    output, its aux loss and the experts each token was routed to
    (T, K)."""
    B, S, D = h.shape
    xt = h.reshape(-1, D)
    top_p, top_i, aux = _route(cfg, lp["router"], xt)
    gates = jnp.zeros((xt.shape[0], cfg.n_experts), jnp.float32)
    gates = gates.at[jnp.arange(xt.shape[0])[:, None], top_i].set(top_p)
    g = jnp.einsum("td,edf->tef", xt, lp["we_gate"])
    u = jnp.einsum("td,edf->tef", xt, lp["we_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, lp["we_down"])
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), gates)
    return out.reshape(B, S, D).astype(h.dtype), aux, top_i


def capacity(cfg: ArchConfig, T: int) -> int:
    c = int(math.ceil(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)


def moe_ffn_sorted(cfg: ArchConfig, lp, h: Array
                   ) -> Tuple[Array, Array, Array]:
    """Production path: sort-based capacity dispatch (see module doc)."""
    B, S, D = h.shape
    T = B * S
    K, E = cfg.top_k, cfg.n_experts
    C = capacity(cfg, T)
    xt = h.reshape(T, D)
    top_p, top_i, aux = _route(cfg, lp["router"], xt)

    flat_e = top_i.reshape(-1)                      # (T*K,)
    order = jnp.argsort(flat_e)                     # stable
    sorted_e = flat_e[order]
    tok = order // K                                # source token per slot
    # rank within expert group = index - first index of this expert value
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * K) - first
    keep = rank < C
    slot = jnp.where(keep, rank, C)                 # overflow -> dropped row

    buf = jnp.zeros((E, C + 1, D), h.dtype)
    buf = buf.at[sorted_e, slot].set(xt[tok], mode="drop")
    buf = hint(buf[:, :C], "expert_buffer")         # (E, C, D)

    g = jnp.einsum("ecd,edf->ecf", buf, lp["we_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, lp["we_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, lp["we_down"])

    w = top_p.reshape(-1)[order]
    y_tok = y[sorted_e, jnp.minimum(slot, C - 1)]   # (T*K, D)
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    out = jnp.zeros((T, D), jnp.float32)
    out = out.at[tok].add(y_tok.astype(jnp.float32) * w[:, None])
    return out.reshape(B, S, D).astype(h.dtype), aux, top_i


def moe_ffn_a2a(cfg: ArchConfig, lp, h: Array
                ) -> Tuple[Array, Array, Array]:
    """Expert-parallel dispatch via shard_map (Perf iteration, see
    EXPERIMENTS.md §Perf olmoe/llama4).

    Banking view: experts are banks on the 'model' mesh axis; the dispatch
    crossbar is *local selection* (tokens are already replicated across the
    model axis by the block-input all-gather the attention path pays
    anyway), and the combine crossbar is one ``psum_scatter`` that lands
    the output directly in the sequence-sharded residual layout.  Per-layer
    collective bytes drop from O(E*C*D) buffer all-reduces to one
    (T_local x D) reduce-scatter.

    Requires a live mesh in the hint policy; falls back to the sorted
    implementation otherwise (single-device smoke tests).
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.hints import policy_value

    mesh = policy_value("__mesh__")
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return moe_ffn_sorted(cfg, lp, h)
    n_model = mesh.shape["model"]
    E, K = cfg.n_experts, cfg.top_k
    if E % n_model or h.shape[1] % n_model:
        return moe_ffn_sorted(cfg, lp, h)
    E_loc = E // n_model
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp_weights = bool(policy_value("__fsdp__", False)) and "data" in dp
    Bg, S, D = h.shape

    def local_fn(h_loc, router_w, we_gate, we_up, we_down):
        # h_loc: (B_loc, S, D) -- replicated over 'model' within a data row
        # FSDP mode: expert weights arrive still cut on their F dim; gather
        # HERE (inside the remat'd layer body) so the gathered copies are
        # transient per layer instead of living across the whole scan.
        if fsdp_weights:
            we_gate = jax.lax.all_gather(we_gate, "data", axis=2, tiled=True)
            we_up = jax.lax.all_gather(we_up, "data", axis=2, tiled=True)
            we_down = jax.lax.all_gather(we_down, "data", axis=1, tiled=True)
        m = jax.lax.axis_index("model")
        B_loc = h_loc.shape[0]
        T = B_loc * S
        xt = h_loc.reshape(T, D)
        top_p, top_i, aux = _route(cfg, router_w, xt)
        C = capacity(cfg, T)

        flat_e = top_i.reshape(-1)
        mine = (flat_e // E_loc) == m
        local_e = jnp.clip(flat_e - m * E_loc, 0, E_loc - 1)
        key = jnp.where(mine, local_e, E_loc)     # foreign slots sort last
        order = jnp.argsort(key)
        skey = key[order]
        tok = order // K
        first = jnp.searchsorted(skey, skey, side="left")
        rank = jnp.arange(T * K) - first
        keep = (skey < E_loc) & (rank < C)
        slot = jnp.where(keep, rank, C)
        e_idx = jnp.minimum(skey, E_loc - 1)

        buf = jnp.zeros((E_loc, C + 1, D), h_loc.dtype)
        buf = buf.at[jnp.where(keep, e_idx, E_loc - 1), slot].set(
            xt[tok], mode="drop")
        buf = buf[:, :C]

        g = jnp.einsum("ecd,edf->ecf", buf, we_gate)
        u = jnp.einsum("ecd,edf->ecf", buf, we_up)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, we_down)

        w = top_p.reshape(-1)[order]
        y_tok = y[e_idx, jnp.minimum(slot, C - 1)]
        y_tok = jnp.where(keep[:, None], y_tok.astype(jnp.float32), 0.0)
        out = jnp.zeros((T, D), jnp.float32)
        out = out.at[tok].add(y_tok * w[:, None])
        out = out.reshape(B_loc, S, D)
        # combine crossbar: sum each token's expert contributions across the
        # model axis AND land sequence-sharded (the residual layout)
        out = jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                   tiled=True)
        aux = jax.lax.pmean(aux, "model")
        return out.astype(h_loc.dtype), aux, top_i

    w_up_spec = P("model", None, "data" if fsdp_weights else None)
    w_dn_spec = P("model", "data" if fsdp_weights else None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  w_up_spec, w_up_spec, w_dn_spec),
        out_specs=(P(dp, "model", None), P(), P(dp, None)),
        check_vma=False,
    )
    return fn(h, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])


MOE_IMPLS = {"dense": moe_ffn_dense, "sorted": moe_ffn_sorted,
             "a2a": moe_ffn_a2a}


# ---------------------------------------------------------------------------
# Forward passes (mirror transformer.py, threading aux loss through scan)
# ---------------------------------------------------------------------------


def moe_layer(cfg: ArchConfig, impl: str, lp, x, window, *, cache_kv=None,
              pos=0, block_k=1024):
    """One MoE transformer block: the dense attention half, then the
    ``impl`` expert dispatch (plus the shared expert).  Returns the
    residual stream, the new (k, v), the aux loss and the experts each
    token was routed to (T, K)."""
    x, h, new_kv = tfm.attention_block(cfg, lp, x, window, cache_kv=cache_kv,
                                       pos=pos, block_k=block_k)
    delta, aux, top_i = MOE_IMPLS[impl](cfg, lp, h)
    if cfg.shared_expert:
        delta = delta + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + delta, new_kv, aux, top_i


def forward(cfg: ArchConfig, params: Params, tokens: Array,
            impl: str = "sorted", block_k: int = 1024
            ) -> Tuple[Array, Array]:
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(tfm.layer_windows(cfg))
    lp = params["layers"]

    def body(carry, xs):
        x, aux = carry
        lp_l, window = xs
        x, _, aux_l, _ = moe_layer(cfg, impl, lp_l, x, window,
                                   block_k=block_k)
        return (hint(x, "residual"), aux + aux_l), None

    body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (lp, windows))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return h, aux / cfg.n_layers


def lm_loss(cfg: ArchConfig, params: Params, batch: Dict[str, Array],
            impl: str = "sorted", aux_weight: float = 0.01) -> Array:
    h, aux = forward(cfg, params, batch["tokens"], impl=impl)
    return tfm.chunked_xent(cfg, params, h, batch["labels"]) + aux_weight * aux


# the route counts of each layer, in the order ``route_counts`` gives them;
# ``Server`` puts them on its spans and counters under these names
ROUTE_COUNTS = ("moe_experts_routed", "moe_experts_read", "moe_dropped")


class MoECache(NamedTuple):
    """The dense KV cache of an MoE model, and the expert traffic of the
    step that last wrote it."""
    k: Array        # (L, B, Smax, Hkv, Dh)
    v: Array
    pos: Array      # scalar int32: number of valid tokens
    routes: Array   # (L, 3) int32: route_counts of each layer


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> MoECache:
    kv = tfm.init_cache(cfg, batch, max_len, dtype)
    return MoECache(kv.k, kv.v, kv.pos,
                    jnp.zeros((cfg.n_layers, len(ROUTE_COUNTS)), jnp.int32))


def route_counts(cfg: ArchConfig, impl: str, top_i: Array) -> Array:
    """One layer's expert traffic for a step whose T rows routed to
    ``top_i`` (T, K), int32 in the order of :data:`ROUTE_COUNTS`: the
    distinct experts routed to; the experts whose weights the ``impl``
    dispatch multiplies (every one: the einsums of ``dense`` and
    ``sorted``, and of ``a2a`` summed over its shards, cover all E); and
    the token-expert pairs dropped past capacity (none for ``dense``;
    ``a2a`` counts as its one-device fallback, ``sorted``)."""
    E = cfg.n_experts
    per_expert = jnp.zeros((E,), jnp.int32).at[top_i.reshape(-1)].add(1)
    routed = jnp.sum(per_expert > 0)
    dropped = (jnp.zeros((), jnp.int32) if impl == "dense" else jnp.sum(
        jnp.maximum(per_expert - capacity(cfg, top_i.shape[0]), 0)))
    return jnp.stack([routed, jnp.asarray(E), dropped]).astype(jnp.int32)


def decode_step(cfg: ArchConfig, params: Params, cache: MoECache,
                tokens: Array, impl: str = "sorted", block_k: int = 1024
                ) -> Tuple[Array, MoECache]:
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(tfm.layer_windows(cfg))
    lp = params["layers"]
    pos = cache.pos

    def body(x, xs):
        lp_l, window, kc, vc = xs
        x, (kc, vc), _, top_i = moe_layer(cfg, impl, lp_l, x, window,
                                          cache_kv=(kc, vc), pos=pos,
                                          block_k=block_k)
        return x, (kc, vc, route_counts(cfg, impl, top_i))

    x, (k_new, v_new, routes) = jax.lax.scan(
        body, x, (lp, windows, cache.k, cache.v))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = tfm.logits_fn(cfg, params, h)[:, 0]
    return logits, MoECache(k_new, v_new, pos + 1, routes)


def prefill(cfg: ArchConfig, params: Params, tokens: Array, max_len: int,
            impl: str = "sorted", block_k: int = 1024):
    B, S = tokens.shape
    x = params["embed"].astype(jnp.bfloat16)[tokens]
    windows = jnp.asarray(tfm.layer_windows(cfg))
    lp = params["layers"]

    def body(x, xs):
        lp_l, window = xs
        x, (k, v), _, top_i = moe_layer(cfg, impl, lp_l, x, window,
                                        block_k=block_k)
        pad = max_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x, (k, v, route_counts(cfg, impl, top_i))

    body = jax.checkpoint(body, prevent_cse=False)
    x, (ks, vs, routes) = jax.lax.scan(body, x, (lp, windows))
    h = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = tfm.logits_fn(cfg, params, h[:, -1:])[:, 0]
    return logits, MoECache(ks, vs, jnp.asarray(S, jnp.int32), routes)
