"""Fault tolerance: checkpoint/restart, bitwise resume, elastic re-shard,
straggler detection, data determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_arch
from repro.data.pipeline import DataConfig, get_batch, PrefetchingLoader
from repro.models import get_model
from repro.optim import adamw
from repro.runtime.trainer import TrainConfig, train


def _tiny():
    cfg = get_arch("qwen2_7b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                              vocab=128, n_heads=2, n_kv_heads=2, head_dim=32)
    return cfg


def test_data_determinism_and_rank_slicing():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=8, seed=3)
    a = get_batch(cfg, 5)
    b = get_batch(cfg, 5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    shards = [get_batch(cfg, 5, rank=r, world=4)["tokens"] for r in range(4)]
    np.testing.assert_array_equal(np.concatenate(shards), a["tokens"])
    c = get_batch(cfg, 6)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_prefetch_loader_state():
    cfg = DataConfig(vocab=50, seq_len=8, global_batch=4)
    ld = PrefetchingLoader(cfg, start_step=0)
    b0 = next(ld)
    b1 = next(ld)
    assert ld.state == 2
    ld.close()
    # resume from state reproduces the stream
    ld2 = PrefetchingLoader(cfg, start_step=1)
    b1b = next(ld2)
    ld2.close()
    np.testing.assert_array_equal(b1["tokens"], b1b["tokens"])


def test_ckpt_atomic_save_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"a": jnp.arange(5, dtype=jnp.float32),
             "nested": {"b": jnp.ones((2, 3))}}
    for step in (10, 20, 30):
        mgr.save(step, state, {"data_state": step})
    assert mgr.all_steps() == [20, 30]  # keep=2 GC
    restored, meta = mgr.restore(state)
    np.testing.assert_array_equal(restored["a"], state["a"])
    assert meta["step"] == 30


def test_ckpt_elastic_reshard(tmp_path):
    """Save, then restore with explicit shardings on the current devices --
    the elastic-rescale path (logical state is mesh-independent)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mgr.save(1, state)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    shardings = {"w": NamedSharding(mesh, P("model", None))}
    restored, _ = mgr.restore(state, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert restored["w"].sharding == shardings["w"]


@pytest.mark.slow
def test_train_restart_bitwise_identical(tmp_path):
    """Kill at step 17, restart, final state == uninterrupted run."""
    cfg = _tiny()
    model = get_model(cfg)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=1)
    tc = lambda d: TrainConfig(total_steps=24, ckpt_every=8, log_every=100,
                               ckpt_dir=str(d))
    oc = adamw.AdamWConfig(total_steps=24, warmup_steps=4)

    # uninterrupted reference
    ref = train(model, dc, tc(tmp_path / "ref"), oc)

    # interrupted: die at step 17 (after the step-16 checkpoint)
    class Boom(Exception):
        pass

    def killer(step):
        if step == 17 and not os.environ.get("_RESUMED"):
            raise Boom()

    with pytest.raises(Boom):
        train(model, dc, tc(tmp_path / "ft"), oc, failure_hook=killer)
    os.environ["_RESUMED"] = "1"
    try:
        out = train(model, dc, tc(tmp_path / "ft"), oc)
    finally:
        del os.environ["_RESUMED"]

    # bitwise-identical final params
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the loss tail matches the reference trajectory
    np.testing.assert_allclose(ref["losses"][-4:], out["losses"][-4:],
                               rtol=0, atol=0)


def test_straggler_detection():
    from repro.runtime.trainer import StragglerMonitor
    mon = StragglerMonitor(window=10, factor=3.0)
    flagged = [mon.record(i, 0.1) for i in range(8)]
    assert not any(flagged)
    assert mon.record(8, 1.0)  # 10x median -> straggler
    assert mon.flagged == [8]


def test_compressed_psum_error_feedback():
    """int8 EF-compression: accumulated mean error stays bounded and the
    residual carries exactly the quantization error."""
    from repro.parallel.collectives import (dequantize_int8,
                                            quantize_int8)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256,)).astype(np.float32)
    residual = np.zeros_like(x)
    drift = []
    for _ in range(20):
        xt = x + residual
        q, s = quantize_int8(jnp.asarray(xt))
        deq = np.asarray(dequantize_int8(q, s))
        residual = xt - deq
        drift.append(np.abs(residual).max())
    # error feedback keeps the residual bounded by one quantization step
    assert drift[-1] <= float(np.abs(x).max() / 127.0 * 2)
