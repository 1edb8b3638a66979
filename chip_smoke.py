"""Bring-up smoke run: serve qwen2-7b on one TPU chip through ``Server``.

    python chip_smoke.py

Drives the serving path as ``launch/serve.py`` does -- ``page_ticket`` ->
``Server`` -> ``submit`` -> ``run`` -- with qwen2-7b at every published
width, cut to the 14 layers of one pipeline stage (random weights from a
fixed seed).  Eight requests with 16-128 token prompts decode 16 tokens
each in an 8-slot batch over a 4096-token cache.

The KV plan's cold solve is held until the first tick has served from the
trivial single-bank fallback layout, then released; the server hot-swaps
to the solved multi-bank layout between ticks.  So the banked gather and
the per-slot record write run through Mosaic on both layouts.

It fails (exit 1) unless JAX's first device is a TPU, the step fits the
chip's memory, every request finishes, the solved layout lands, every
token gathered through the banked table equals the token the host
recorded, every decode step's logits are finite, and nothing compiles
after the first tick on the solved layout (each banked kernel is built
once per layout and shape).  The last line of
standard output is a JSON object naming the device.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
SEED = 0
REQUESTS = 8
PROMPT_LEN = (16, 128)
NEW_TOKENS = 16
PAGE = 128
SOLVE_TIMEOUT_S = 300.0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def main() -> None:
    if not (SRC / "repro").is_dir():
        fail(f"the repro package is not at {SRC / 'repro'}; run this "
             "script from a checkout of the repository")
    sys.path.insert(0, str(SRC))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); this run only counts on a TPU")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import qwen2_7b
    from repro.core.planner import BankingPlanner
    from repro.core.service import PlanService
    from repro.core.tracing import serve_tracer
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.steps import make_serve_step
    from repro.models import get_model
    from repro.runtime.server import Request, Server, page_ticket

    cache_dir = enable_compile_cache()
    print(f"device: {device['kind']} x{device['count']}; "
          f"compile cache: {cache_dir}", flush=True)

    # -- size check, before anything is allocated ------------------------
    cfg = qwen2_7b.one_chip_stage()
    slots, max_len = qwen2_7b.STAGE_SLOTS, qwen2_7b.STAGE_MAX_LEN
    print(f"model: qwen2-7b d_model={cfg.d_model} heads={cfg.n_heads} "
          f"kv_heads={cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab}, {cfg.n_layers} of "
          f"{qwen2_7b.CONFIG.n_layers} layers", flush=True)
    print(f"cut: depth {qwen2_7b.CONFIG.n_layers} -> {cfg.n_layers} layers "
          f"(one stage of a two-stage pipeline): all 28 layers are about "
          f"15.2 GB of bf16 weights, more than one 16 GB chip holds beside "
          f"an {slots} x {max_len}-token KV cache; widths are as published",
          flush=True)
    model = get_model(cfg)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(SEED))
    cache_shapes = jax.eval_shape(lambda: model.init_cache(slots, max_len))

    def nbytes(tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    param_bytes, cache_bytes = nbytes(param_shapes), nbytes(cache_shapes)
    step = jax.jit(make_serve_step(model)).lower(
        param_shapes, cache_shapes,
        jax.ShapeDtypeStruct((slots, 1), jnp.int32)).compile()
    mem = step.memory_analysis()
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    limit = dev.memory_stats()["bytes_limit"]
    print(f"size: params {param_bytes} B, kv cache {cache_bytes} B, "
          f"decode step {step_bytes} B (memory_analysis) of {limit} B on "
          f"the chip", flush=True)
    check(step_bytes < limit, "the decode step does not fit the chip")

    # -- serve ---------------------------------------------------------------
    # the server's own counters: compiles inside its serve.* spans
    counters = serve_tracer().metrics
    release = threading.Event()

    class HeldPlanner(BankingPlanner):
        """Holds every cold solve until ``release`` is set, so the first
        tick is served from the fallback layout."""

        def build_space(self, prep):
            release.wait(SOLVE_TIMEOUT_S)
            return super().build_space(prep)

    service = PlanService(planner=HeldPlanner(), workers=2)
    ticket = page_ticket(cfg, max_len, page=PAGE, readers=slots,
                         service=service)
    t0 = time.perf_counter()
    server = Server(model, max_batch=slots, max_len=max_len, kv_plan=ticket)
    t_init = time.perf_counter() - t0
    fallback = server.pager.artifact
    check(fallback.n_banks == 1, f"server did not start on the fallback "
          f"layout: {fallback.describe()}")

    # Server keeps no logits; wrap its jitted step to check every step's
    finite = []
    decode = server._decode

    def checked_decode(params, cache, tokens):
        nxt, logits, cache = decode(params, cache, tokens)
        finite.append(jnp.isfinite(logits).all())
        return nxt, logits, cache

    server._decode = checked_decode

    rng = np.random.default_rng(SEED)
    requests = []
    for uid in range(REQUESTS):
        n = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        prompt = rng.integers(2, cfg.vocab - 1, size=n).astype(np.int32)
        requests.append(Request(uid=uid, prompt=prompt, max_new=NEW_TOKENS))
        server.submit(requests[-1])
    print(f"serving {REQUESTS} requests, prompts of "
          f"{sorted(len(r.prompt) for r in requests)} tokens, "
          f"{NEW_TOKENS} new tokens each, max_batch={slots}, "
          f"from {fallback.describe()} (server built in {t_init:.3f} s)",
          flush=True)

    compiles0 = counters.counter("compiles")
    reads0 = counters.counter("compile_cache_reads")
    t0 = time.perf_counter()
    server.tick()                 # admission, then tick 1 on the fallback
    check(server.ticks == 1 and server.pager.artifact is fallback,
          "the first tick did not serve from the fallback layout")
    release.set()
    check(ticket.wait(SOLVE_TIMEOUT_S), "the KV plan's solve never landed")
    server.tick()                 # adopts the solved layout, decodes on it
    check(server.pager.artifact is not fallback,
          "the second tick did not adopt the solved layout")
    compiles1 = counters.counter("compiles")
    ticks1 = server.ticks
    server.run(max_ticks=8 * NEW_TOKENS)
    wall = time.perf_counter() - t0
    compiles = counters.counter("compiles") - compiles0
    reads = counters.counter("compile_cache_reads") - reads0
    later = counters.counter("compiles") - compiles1

    solved = server.pager.artifact
    tokens = sum(len(r.out) for r in requests)
    print(f"served: {sum(r.done for r in requests)}/{REQUESTS} requests, "
          f"{tokens} tokens, {server.ticks} ticks, {wall:.3f} s wall "
          f"(bring-up timing, compilation included; not a benchmark)",
          flush=True)
    print(f"layouts: swaps={server.swaps} promotions={server.promotions}; "
          f"now serving {solved.describe()}", flush=True)
    print(f"record checks per layout: {server.record_checks}; "
          f"mismatches={server.record_mismatches}", flush=True)
    print(f"compilations while serving: {compiles:g} "
          f"({server.ticks} ticks), {later:g} of them in the "
          f"{server.ticks - ticks1} ticks after the first on the solved "
          f"layout; persistent cache reads while serving: {reads:g}",
          flush=True)
    for name, art in (("fallback", fallback), ("solved", solved)):
        print(f"banked kernels on the {name} layout: builds "
              f"{art.kernel_builds}, calls {art.kernel_calls}", flush=True)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}", flush=True)

    check(all(r.done and len(r.out) == NEW_TOKENS for r in requests),
          "not every request finished with its new tokens")
    check(all(0 <= t < cfg.vocab for r in requests for t in r.out),
          "a generated token is outside the vocabulary")
    check(server.swaps + server.promotions >= 1 and solved.n_banks > 1,
          f"the solved multi-bank layout never landed "
          f"(serving {solved.describe()})")
    check(server.record_checks.get(fallback.describe(), 0) > 0
          and server.record_checks.get(solved.describe(), 0) > 0,
          "the banked gather was not checked on both layouts")
    check(later == 0, f"{later:g} compilations after the first tick on "
          f"the solved layout: a kernel was built again")
    check(server.record_mismatches == 0,
          f"{server.record_mismatches} gathered tokens differ from the "
          f"tokens the host recorded")
    check(len(finite) > 0 and bool(np.asarray(jnp.stack(finite)).all()),
          "a decode step produced non-finite logits")
    service.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
