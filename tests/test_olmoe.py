"""OLMoE's two mechanisms in the program, and the expert traffic its step
reports: the q/k RMS norm over the whole projection width, top-k gates
that are not renormalised, the route counts of each layer, and their
spans and counters in ``Server``.  Both fields at their defaults leave a
dense model as it was."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.steps import make_serve_step
from repro.models import get_model
from repro.models import moe

HI = jax.lax.Precision.HIGHEST


def _tiny_olmoe(**kw):
    cfg = get_arch("olmoe_1b_7b").reduced()
    assert cfg.qk_proj_norm and not cfg.norm_topk_prob
    return dataclasses.replace(cfg, **kw)


def _seeded(params, seed, scale=0.1):
    """Every leaf moved by seeded noise, so that the zero-initialised
    gains and the small router matter."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        (x.astype(jnp.float32) + scale * jax.random.normal(k, x.shape))
        .astype(x.dtype) for x, k in zip(leaves, keys)])


# -- a plain float32 forward pass of OLMoE's equations ------------------------


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + g)


def _rope(x, theta):
    S, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward(cfg, params, tokens, per_head=False):
    """Logits (B, S, V) of a whole row at once, in float32.  The q/k norm
    runs over all heads' projection together, or, with ``per_head``,
    over each head alone (the wrong variant)."""
    f = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    B, S = tokens.shape
    H, Hkv, dh, eps = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.norm_eps
    x = f["embed"][tokens]
    mask = np.tril(np.ones((S, S), bool))

    def qk(y, g, heads):
        if per_head:
            return _rms(y.reshape(B, S, heads, dh), g.reshape(heads, dh), eps)
        return _rms(y, g, eps).reshape(B, S, heads, dh)

    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], f["layers"])
        h = _rms(x, lp["ln1"], eps)
        q = _rope(qk(jnp.dot(h, lp["wq"], precision=HI), lp["q_norm"], H),
                  cfg.rope_theta)
        k = _rope(qk(jnp.dot(h, lp["wk"], precision=HI), lp["k_norm"], Hkv),
                  cfg.rope_theta)
        v = jnp.dot(h, lp["wv"], precision=HI).reshape(B, S, Hkv, dh)
        k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
        x = x + jnp.dot(o.reshape(B, S, H * dh), lp["wo"], precision=HI)
        h = _rms(x, lp["ln2"], eps)
        probs = jax.nn.softmax(jnp.dot(h, lp["router"], precision=HI), -1)
        top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
        gates = jnp.sum(jax.nn.one_hot(top_i, cfg.n_experts) *
                        top_p[..., None], axis=-2)            # raw softmax
        g = jnp.einsum("bsd,edf->bsef", h, lp["we_gate"], precision=HI)
        u = jnp.einsum("bsd,edf->bsef", h, lp["we_up"], precision=HI)
        y = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, lp["we_down"],
                       precision=HI)
        x = x + jnp.einsum("bsed,bse->bsd", y, gates, precision=HI)
    h = _rms(x, f["ln_f"], eps)
    return jnp.einsum("bsd,vd->bsv", h, f["lm_head"], precision=HI)


def _served_logits(cfg, params, tokens):
    """The serving path's logits for every position of ``tokens`` (B, S):
    each fed through the jitted serve step and the cache, one position
    a call, as ``Server`` prefills and decodes."""
    model = get_model(cfg)
    step = jax.jit(make_serve_step(model))
    cache = model.init_cache(tokens.shape[0], 32)
    out = []
    for t in range(tokens.shape[1]):
        _, logits, cache = step(params, cache, jnp.asarray(tokens[:, t:t + 1]))
        out.append(np.asarray(logits, np.float32))
    return np.stack(out, axis=1)


@pytest.fixture(scope="module")
def qk_norm_case():
    cfg = _tiny_olmoe()
    params = _seeded(get_model(cfg).init(jax.random.PRNGKey(1)), seed=2)
    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab, (2, 12)).astype(np.int32)
    return cfg, params, tokens, _served_logits(cfg, params, tokens)


# The program's activations are bfloat16 (8 bits of mantissa, a relative
# rounding of 0.4% a step): on these seeds its logits, of standard
# deviation 1.15, lie at most 0.048 from the float32 pass's over the 24
# positions; the per-head norm's lie up to 1.2 away.
LOGIT_TOL = 0.1


def test_qk_norm_decode_through_the_cache_matches_a_float32_forward(
        qk_norm_case):
    cfg, params, tokens, served = qk_norm_case
    want = np.asarray(_forward(cfg, params, tokens))
    assert np.abs(served - want).max() < LOGIT_TOL


def test_a_q_k_norm_per_head_is_not_the_program(qk_norm_case):
    cfg, params, tokens, served = qk_norm_case
    wrong = np.asarray(_forward(cfg, params, tokens, per_head=True))
    assert np.abs(served - wrong).max() > 5 * LOGIT_TOL


# -- gates --------------------------------------------------------------------


@pytest.mark.parametrize("norm", [False, True])
def test_route_renormalises_the_top_k_only_when_asked(norm):
    cfg = _tiny_olmoe(norm_topk_prob=norm)
    xt = jax.random.normal(jax.random.PRNGKey(4), (6, cfg.d_model))
    router = 0.05 * jax.random.normal(jax.random.PRNGKey(5),
                                      (cfg.d_model, cfg.n_experts))
    top_p, top_i, _ = moe._route(cfg, router, xt)
    probs = np.asarray(jax.nn.softmax(xt @ router, axis=-1))
    raw = np.take_along_axis(probs, np.asarray(top_i), axis=-1)
    np.testing.assert_array_equal(np.asarray(top_i),
                                  np.argsort(-probs, axis=-1)[:, :cfg.top_k])
    want = raw / raw.sum(-1, keepdims=True) if norm else raw
    np.testing.assert_allclose(np.asarray(top_p), want, rtol=1e-5)
    assert (np.asarray(top_p).sum(-1) < 1 - 1e-3).all() != norm


# -- a dense model keeps its program ------------------------------------------

# qwen2's smoke configuration's serve-step logits (first six of each row)
# and tokens after four calls, on `_seeded` weights, at the commit before
# the q/k norm and the gate option existed
PARENT_DENSE_LOGITS = [
    [0.474609375, 0.42578125, 0.47265625, 0.328125, -0.74609375,
     -0.50390625],
    [-0.02734375, -0.8515625, -0.208984375, 0.07080078125,
     -0.027099609375, 0.5234375]]
PARENT_DENSE_TOKENS = [372, 206]


def test_default_fields_leave_a_dense_model_as_it_was():
    cfg = get_arch("qwen2_7b").reduced()
    assert not cfg.qk_proj_norm and cfg.norm_topk_prob
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    assert not {"q_norm", "k_norm"} & set(params["layers"])
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        (x.astype(jnp.float32) + 0.05 * jax.random.normal(k, x.shape))
        .astype(x.dtype) for x, k in zip(leaves, keys)])
    step = jax.jit(make_serve_step(model))
    cache = model.init_cache(2, 16)
    for t in ([5, 17], [300, 2], [41, 99], [7, 7]):
        out = step(params, cache, jnp.asarray(t, jnp.int32)[:, None])
        nxt, logits, cache = out
    np.testing.assert_array_equal(np.asarray(logits, np.float32)[:, :6],
                                  PARENT_DENSE_LOGITS)
    assert np.asarray(nxt)[:, 0].tolist() == PARENT_DENSE_TOKENS
    assert not hasattr(cache, "routes")


# -- route counts -------------------------------------------------------------


def _count(top_i, E, C):
    per = np.bincount(np.asarray(top_i).reshape(-1), minlength=E)
    return [int((per > 0).sum()), E, int(np.maximum(per - C, 0).sum())]


@pytest.mark.parametrize("impl", ["sorted", "dense"])
def test_route_counts_equal_a_count_of_the_top_k_ids(impl):
    cfg = _tiny_olmoe()
    rng = np.random.default_rng(6)
    for T in (1, 8, 64):
        top_i = np.stack([rng.permutation(cfg.n_experts)[:cfg.top_k]
                          for _ in range(T)])
        top_i[: T // 2] = [0, 1]            # crowd two experts
        want = _count(top_i, cfg.n_experts, moe.capacity(cfg, T))
        if impl == "dense":
            want[2] = 0                      # no capacity, nothing dropped
        got = moe.route_counts(cfg, impl, jnp.asarray(top_i))
        assert np.asarray(got).tolist() == want


def _decode_routes(cfg, params, tokens):
    model = get_model(cfg)
    cache = model.init_cache(tokens.shape[0], 8)
    _, new = jax.jit(model.decode)(params, cache, jnp.asarray(tokens))
    return np.asarray(new.routes)


def _first_layer_top_i(cfg, params, tokens):
    """The experts the first layer of a first decode call routes
    ``tokens`` (B, 1) to, routed here from its own attention half."""

    @jax.jit
    def first_layer(params, tokens):
        kv = moe.init_cache(cfg, tokens.shape[0], 8)
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        x = params["embed"].astype(jnp.bfloat16)[tokens]
        _, h, _ = moe.tfm.attention_block(
            cfg, lp, x, 0, cache_kv=(kv.k[0], kv.v[0]), pos=kv.pos)
        return moe._route(cfg, lp["router"], h.reshape(-1, h.shape[-1]))[1]

    return np.asarray(first_layer(params, jnp.asarray(tokens)))


def test_the_step_reports_every_layers_routes_and_drops_past_capacity():
    cfg = _tiny_olmoe()
    params = _seeded(get_model(cfg).init(jax.random.PRNGKey(1)), seed=2)
    params["layers"]["router"] = params["layers"]["router"] * 20.0
    # 8 distinct rows: the sorted dispatch multiplies every expert and
    # drops nothing (capacity is at least 8 rows)
    rows = np.arange(8, dtype=np.int32)[:, None] * 37
    routes = _decode_routes(cfg, params, rows)
    assert routes.shape == (cfg.n_layers, len(moe.ROUTE_COUNTS))
    assert (routes[:, 1] == cfg.n_experts).all()
    assert (routes[:, 2] == 0).all()
    top_i = _first_layer_top_i(cfg, params, rows)
    assert routes[0].tolist() == _count(top_i, cfg.n_experts, 8)
    # 64 copies of one row route alike: top_k experts take 64 pairs each
    # against a capacity of 40, and 24 of each are dropped
    C = moe.capacity(cfg, 64)
    assert C == 40
    routes = _decode_routes(cfg, params, np.full((64, 1), 11, np.int32))
    assert routes[:, 0].tolist() == [cfg.top_k] * cfg.n_layers
    assert routes[:, 2].tolist() == [cfg.top_k * (64 - C)] * cfg.n_layers


# -- spans and counters in the server -----------------------------------------


def test_server_puts_the_route_counts_on_its_spans_and_metrics():
    from repro.core.service import PlanService
    from repro.runtime.server import Request, Server, page_ticket

    cfg = _tiny_olmoe()
    svc = PlanService(workers=1)
    tr = svc.enable_tracing()
    try:
        ticket = page_ticket(cfg, 32, page=8, readers=2, service=svc)
        server = Server(get_model(cfg), max_batch=2, max_len=32,
                        kv_plan=ticket)
        rng = np.random.default_rng(0)
        for uid in range(2):
            server.submit(Request(uid=uid, prompt=rng.integers(
                2, cfg.vocab, 3).astype(np.int32), max_new=3))
        while server.queue or server.active:
            server.tick()
        assert server._tracer()[0] is tr
        spans = tr.spans(server._tracer()[1])
    finally:
        svc.shutdown()
    steps = [s for s in spans if s.name == "serve.step"]
    admits = [s for s in spans if s.name == "serve.admit"]
    assert steps and len(admits) == 2
    per_layer = cfg.n_layers * cfg.n_experts
    for s in steps:
        assert s.attrs["moe_experts_read"] == per_layer
        assert cfg.n_layers * cfg.top_k <= s.attrs["moe_experts_routed"] \
            <= per_layer
        assert s.attrs["moe_dropped"] == 0
    for s in admits:   # three prefill calls each
        assert s.attrs["moe_experts_read"] == 3 * per_layer
    text = tr.metrics.prometheus()
    for name in moe.ROUTE_COUNTS:
        total = sum(s.attrs[name] for s in steps + admits)
        assert tr.metrics.counter(name) == total
        assert f"# TYPE {name} counter" in text
    # a dense model's step spans carry none of them
    dense = get_arch("qwen2_7b").reduced()
    server = Server(get_model(dense), max_batch=2, max_len=32)
    server.submit(Request(uid=9, prompt=np.arange(2, 5, dtype=np.int32),
                          max_new=2))
    while server.queue or server.active:
        server.tick()
    tr, tid = server._tracer()
    recorded = [s for s in tr.spans(tid) if s.name == "serve.step"]
    assert recorded and not any(set(moe.ROUTE_COUNTS) & set(s.attrs)
                                for s in recorded)
