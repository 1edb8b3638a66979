"""Fault tolerance: checkpoint/restart, bitwise resume, elastic re-shard,
straggler detection, data determinism; the serve loop's spans."""

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


# -- the serve loop's spans ------------------------------------------------------


def _tiny_server(service, max_new=8):
    """A tiny dense server on its solved KV layout, two requests queued."""
    import dataclasses

    from repro.runtime.server import Request, Server, page_ticket
    cfg = dataclasses.replace(_tiny(), n_layers=1, d_model=32, d_ff=64,
                              vocab=64, head_dim=16)
    ticket = page_ticket(cfg, 32, page=8, readers=2, service=service)
    assert ticket.wait(120)
    server = Server(get_model(cfg), max_batch=2, max_len=32, kv_plan=ticket)
    for uid in range(2):
        server.submit(Request(uid=uid, prompt=np.arange(2, 5 + uid,
                                                        dtype=np.int32),
                              max_new=max_new))
    return server


@pytest.fixture(scope="module")
def first_tick():
    """The spans of a tiny server's first tick, recorded through the
    process's serve tracer (the plan service has none)."""
    from repro.core.service import PlanService
    from repro.core.tracing import serve_tracer
    svc = PlanService(workers=1)
    server = _tiny_server(svc)
    server.tick()
    tr, tid = server._tracer()
    assert tr is serve_tracer()
    yield server, tr.spans(tid)
    svc.shutdown()


def test_one_tick_gives_the_serve_span_tree(first_tick):
    _, spans = first_tick
    by_id = {s.span_id: s for s in spans}
    edges = {(s.name, by_id[s.parent_id].name if s.parent_id else None)
             for s in spans}
    assert edges == {
        ("serve.tick", None), ("serve.queue_wait", None),
        ("serve.admit", "serve.tick"), ("serve.gather", "serve.tick"),
        ("serve.scatter", "serve.gather"),
        ("serve.gather.wait", "serve.gather"),
        ("serve.inputs", "serve.tick"), ("serve.step", "serve.tick"),
        ("serve.step.wait", "serve.step"), ("serve.emit", "serve.tick"),
        ("serve.scatter", "serve.tick")}
    for s in spans:
        if s.parent_id:
            p = by_id[s.parent_id]
            assert p.start <= s.start <= s.end <= p.end, s.name
    tick = next(s for s in spans if s.name == "serve.tick")
    assert (tick.attrs["tick"], tick.attrs["slots"],
            tick.attrs["tokens"]) == (0, 2, 2)
    admits = sorted((s.attrs["uid"], s.attrs["slot"],
                     s.attrs["prompt_tokens"])
                    for s in spans if s.name == "serve.admit")
    assert admits == [(0, 0, 3), (1, 1, 4)]
    assert sorted(s.attrs["uid"] for s in spans
                  if s.name == "serve.queue_wait") == [0, 1]


def test_a_tick_s_compiles_land_on_the_banked_call_that_ran_them(
        first_tick):
    _, spans = first_tick
    tick = next(s for s in spans if s.name == "serve.tick")
    assert "compiles" not in tick.attrs and "build_s" not in tick.attrs
    banked = [s for s in spans if s.name in ("serve.gather", "serve.scatter")
              and s.attrs.get("compiles", 0) >= 1]
    assert banked
    for s in banked:
        assert 0 < s.attrs["build_s"] <= s.end - s.start
        assert s.attrs["build_s"] == pytest.approx(
            s.attrs.get("trace_s", 0) + s.attrs.get("lower_s", 0)
            + s.attrs["compile_s"])


def test_later_ticks_reuse_the_banked_kernels_built_by_the_first():
    """Each banked kernel is built once per layout and shape: after the
    first decode tick at a steady batch, ticks compile nothing in the
    gather or the record write, the served artifact's build counts stay
    put while its call counts grow, and every gathered token still
    equals the token the host recorded."""
    from repro.core.service import PlanService
    svc = PlanService(workers=1)
    try:
        server = _tiny_server(svc)
        server.tick()
        tr, tid = server._tracer()
        metrics = tr.metrics
        builds0 = {op: metrics.gauge("banked_kernel_builds", op=op)
                   for op in ("gather", "scatter_elems")}
        calls0 = {op: metrics.gauge("banked_kernel_calls", op=op)
                  for op in ("gather", "scatter_elems")}
        assert builds0["gather"] == 1 and builds0["scatter_elems"] >= 1
        n_first = len(tr.spans(tid))
        for _ in range(3):
            server.tick()
        later = tr.spans(tid)[n_first:]
    finally:
        svc.shutdown()
    assert sum(s.name == "serve.tick" for s in later) == 3
    banked = [s for s in later if s.name in ("serve.gather",
                                             "serve.scatter")]
    assert len(banked) >= 6
    for s in banked:
        assert s.attrs.get("compiles", 0) == 0, (s.name, s.attrs)
    for op in ("gather", "scatter_elems"):
        assert metrics.gauge("banked_kernel_builds", op=op) == builds0[op]
        assert metrics.gauge("banked_kernel_calls", op=op) == \
            calls0[op] + 3
    assert server.record_mismatches == 0
    assert sum(server.record_checks.values()) == 2 * 4


def test_serve_spans_are_on_the_profiler_host_plane(first_tick, tmp_path):
    server, _ = first_tick
    with jax.profiler.trace(str(tmp_path)):
        server.tick()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"serve.tick", "serve.gather", "serve.step.wait"} <= names


def test_serve_metrics_reach_the_service_endpoint_without_tick_telemetry():
    """A plan service with tracing and telemetry on: the serve loop
    records through the service's tracer, so ``serve_tick_ms`` reaches
    its ``/metrics``; the telemetry hub logs gathers and scatters only."""
    import urllib.request

    from repro.core.service import PlanService
    from repro.core.tracing import start_observability_server
    svc = PlanService(workers=1)
    svc.enable_tracing()
    hub = svc.enable_telemetry()
    server = _tiny_server(svc, max_new=2)
    server.run(max_ticks=4)
    assert server.ticks >= 2
    http = start_observability_server(svc.metrics, svc.recorder,
                                      tracer=svc.tracer, port=0)
    try:
        host, port = http.server_address[:2]
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10).read().decode()
    finally:
        http.shutdown()
        svc.shutdown()
    for name in ("serve_tick_ms_count", "serve_gather_ms_count",
                 "serve_scatter_ms_count", "serve_queue_wait_ms_count",
                 "serve_active_slots", "serve_queue_depth", "compiles",
                 'banked_kernel_builds{op="gather"}',
                 'banked_kernel_calls{op="scatter_elems"}'):
        assert name in body, name
    ops = {r.op for r in hub.log.records()}
    assert "gather" in ops and "tick" not in ops
