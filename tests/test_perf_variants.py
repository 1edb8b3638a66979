"""Correctness of the Perf-iteration code paths (EXPERIMENTS.md §Perf)."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import ArchConfig


def _gemma_like() -> ArchConfig:
    return ArchConfig(
        name="g-mini", family="dense", n_layers=6, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, head_dim=16,
        sliding_window=8, local_global_ratio=2,  # 2 local : 1 global
    )


@pytest.mark.slow
def test_grouped_ring_decode_matches_dense_decode():
    """Ring-banked local caches must be bit-compatible with the full-buffer
    decode (window masking == ring retention), including past wrap-around."""
    from repro.models import transformer as tfm

    cfg = _gemma_like()
    key = jax.random.PRNGKey(0)
    params = tfm.init_dense_params(cfg, key)
    B, steps = 2, 14  # > window (8): exercises ring wrap
    max_len = 32

    cache_full = tfm.init_cache(cfg, B, max_len)
    cache_ring = tfm.init_grouped_cache(cfg, B, max_len)
    tok = jnp.full((B, 1), 3, jnp.int32)
    dec_full = jax.jit(lambda p, c, t: tfm.decode_step(cfg, p, c, t))
    dec_ring = jax.jit(lambda p, c, t: tfm.grouped_decode_step(cfg, p, c, t))
    for step in range(steps):
        lf, cache_full = dec_full(params, cache_full, tok)
        lr, cache_ring = dec_ring(params, cache_ring, tok)
        np.testing.assert_allclose(
            np.asarray(lf, np.float32), np.asarray(lr, np.float32),
            atol=0.05, rtol=0.02), step
        tok = jnp.argmax(lf, -1).astype(jnp.int32)[:, None]


def test_moe_a2a_fallback_without_mesh():
    """Without a mesh policy, a2a must equal the sorted implementation."""
    from repro.models import moe as moe_mod

    cfg = dataclasses.replace(get_arch("olmoe_1b_7b").reduced(),
                              capacity_factor=8.0)
    params = moe_mod.init_moe_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    h = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, cfg.d_model)),
                    jnp.float32)
    ys, _, routed_s = moe_mod.moe_ffn_sorted(cfg, lp, h)
    ya, _, routed_a = moe_mod.moe_ffn_a2a(cfg, lp, h)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ya), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(routed_s), np.asarray(routed_a))


@pytest.mark.slow
def test_moe_a2a_matches_oracle_on_mesh():
    """4-device subprocess: shard_map dispatch == dense oracle."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    code = textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import moe as moe_mod
        from repro.parallel.hints import sharding_policy

        cfg = dataclasses.replace(get_arch("olmoe_1b_7b").reduced(),
                                  n_experts=4, top_k=2, capacity_factor=8.0)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        params = moe_mod.init_moe_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        h = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 16, cfg.d_model)), jnp.float32)
        yd, _, routed_d = moe_mod.moe_ffn_dense(cfg, lp, h)
        with mesh, sharding_policy({"__mesh__": mesh}):
            ya, _, routed_a = jax.jit(
                lambda l, x: moe_mod.moe_ffn_a2a(cfg, l, x))(lp, h)
        np.testing.assert_allclose(np.asarray(yd, np.float32),
                                   np.asarray(ya, np.float32), atol=3e-2)
        # each token's experts, gathered from the data shards in order
        np.testing.assert_array_equal(np.asarray(routed_d),
                                      np.asarray(routed_a))
        print("A2A-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "A2A-OK" in out.stdout


def test_adamw_bf16_moments():
    from repro.optim import adamw

    params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
    st = adamw.init(params, moment_dtype=jnp.bfloat16)
    assert st.m["w"].dtype == jnp.bfloat16
    cfg = adamw.AdamWConfig(total_steps=10, warmup_steps=1)
    grads = {"w": jnp.full((8, 8), 0.1, jnp.float32)}
    new_p, st2 = adamw.update(cfg, grads, st, params)
    assert st2.m["w"].dtype == jnp.bfloat16
    # the fp32 master must move even when the bf16 live copy rounds back
    assert float(jnp.abs(st2.master["w"] - 1.0).max()) > 0
    assert new_p["w"].dtype == jnp.bfloat16


def test_zero_pod_axis_specs():
    from repro.parallel import sharding as shd

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    import jax
    from repro.models import get_model
    cfg = dataclasses.replace(get_arch("llama4_maverick"), n_layers=2)
    shapes = jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.PRNGKey(0)))
    specs = shd.param_specs(shapes, FakeMesh(), fsdp=True,
                            fsdp_axes=("data", "pod"))
    # expert F dim cut across BOTH pure-DP axes (32-way ZeRO)
    assert specs["layers"]["we_gate"][3] == ("data", "pod")


def test_int8_kv_decode_close_to_exact():
    """Quantized-cache decode must track the exact decode closely."""
    from repro.models import transformer as tfm

    cfg = get_arch("qwen2_7b").reduced()
    params = tfm.init_dense_params(cfg, jax.random.PRNGKey(0))
    B, steps, max_len = 2, 6, 16
    cache = tfm.init_cache(cfg, B, max_len)
    cache_q = tfm.init_quant_cache(cfg, B, max_len)
    tok = jnp.full((B, 1), 3, jnp.int32)
    dec = jax.jit(lambda p, c, t: tfm.decode_step(cfg, p, c, t))
    dec_q = jax.jit(lambda p, c, t: tfm.decode_step_quant(cfg, p, c, t))
    for _ in range(steps):
        lf, cache = dec(params, cache, tok)
        lq, cache_q = dec_q(params, cache_q, tok)
        pf = jax.nn.softmax(lf.astype(jnp.float32))
        pq = jax.nn.softmax(lq.astype(jnp.float32))
        # distributions must stay close (int8 cache error ~0.5%)
        assert float(jnp.abs(pf - pq).max()) < 0.05
        tok = jnp.argmax(lf, -1).astype(jnp.int32)[:, None]
