"""Drive the program's serving path for one cell and record what happened.

The path is the program's own: ``page_ticket`` -> ``Server`` ->
``Server.submit`` -> ``Server.tick``.  The benchmark hands the server its
seeded weights (as the model's ``init``), wraps the server's jitted step
only to keep a reference to what each call was fed and returned, and
gives each request an output list that notes the step call, the tick
and the slot of every token appended to it.  None of this changes what
the step computes or when it runs.

Set-up serves the mix's warm requests until the solved KV layout is
adopted and the warm group has reached the state the mix asks for; the
measured window then drives ``Server.tick`` in a loop, submitting each
load request once it is due.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .modelspec import ModelSpec
from .timeline import ReqTimes, Tick
from .traffic import Planned

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PLAN_TIMEOUT_S = 120.0
MAX_WARM_TICKS = 400


class Recorder:
    """What the window's calls were fed and returned, and where the
    serving loop is: the tick under way and the server that owns the
    slots."""

    def __init__(self):
        self.fed: List[Any] = []       # (slots, 1) int32 device arrays
        self.out: List[Any] = []       # (slots, 1) int32 device arrays
        self.server = None
        self.tick = -1
        self.tokens_this_tick = 0
        self.compiles: List[Tuple[float, float]] = []   # (end, seconds)
        self.cache_reads = 0       # compiles answered by the persistent cache
        self.counting = False

    def wrap(self, step):
        def recorded(params, cache, tokens):
            out = step(params, cache, tokens)
            self.fed.append(tokens)
            self.out.append(out[0])
            return out
        return recorded

    def slot_of(self, req) -> int:
        for slot, r in self.server.active.items():
            if r is req:
                return slot
        return -1

    def on_duration(self, event, duration, **kw):
        if self.counting and event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), float(duration)))

    def on_event(self, event, **kw):
        if self.counting and event == CACHE_HIT_EVENT:
            self.cache_reads += 1


class _Out(list):
    """A request's output list that notes where each token came from."""

    def __init__(self, req, rec: Recorder):
        super().__init__()
        self._req, self._rec = req, rec

    def append(self, tok):
        rec, req = self._rec, self._req
        req.token_calls.append(len(rec.fed) - 1)
        req.token_ticks.append(rec.tick)
        req.token_slots.append(rec.slot_of(req))
        rec.tokens_this_tick += 1
        super().append(tok)


def tracked_class(Request):
    """A subclass of the program's ``Request`` whose first next-token
    assignment (the prefill's last call) and whose tokens are noted."""

    class Tracked(Request):
        def __init__(self, rec: Recorder, plan: Planned):
            super().__init__(uid=plan.uid, prompt=plan.prompt,
                             max_new=plan.max_new)
            self.out = _Out(self, rec)
            self.plan = plan
            self.rec = rec
            self.token_calls: List[int] = []
            self.token_ticks: List[int] = []
            self.token_slots: List[int] = []
            self.first_call: Optional[int] = None
            self.first_tok: Optional[int] = None
            self.due: Optional[float] = None
            self.submitted: Optional[float] = None

        @property
        def _next(self):
            try:
                return self.__dict__["next_tok"]
            except KeyError:
                raise AttributeError("_next") from None

        @_next.setter
        def _next(self, tok):
            if self.first_call is None:
                self.first_call = len(self.rec.fed) - 1
                self.first_tok = int(tok)
            self.__dict__["next_tok"] = tok

    return Tracked


def arch_config(spec: ModelSpec):
    """The program's configuration type, filled from a configuration
    file, with the file's ``"program"`` options applied: each key a field
    of ``ArchConfig``, each value what the program is handed.  A key the
    program does not have is an error, so a model that needs an option
    the program lacks fails before it serves another model."""
    import dataclasses

    from repro.configs.base import ArchConfig

    options = dict(spec.raw.get("program", {}))
    unknown = sorted(set(options) - {f.name for f in
                                     dataclasses.fields(ArchConfig)})
    if unknown:
        raise ValueError(f"configuration {spec.name!r} asks the program "
                         f"for options it does not have: {unknown} are "
                         f"not fields of ArchConfig")
    return dataclasses.replace(ArchConfig(
        name=spec.name, family="moe" if spec.moe else "dense",
        n_layers=spec.layers, d_model=spec.d_model, n_heads=spec.heads,
        n_kv_heads=spec.kv_heads,
        d_ff=spec.expert_ff if spec.moe else spec.d_ff, vocab=spec.vocab,
        head_dim=spec.head_dim, qkv_bias=spec.qkv_bias,
        rope_theta=spec.rope_theta, tie_embeddings=False,
        norm_eps=spec.norm_eps, n_experts=spec.experts, top_k=spec.top_k,
        moe_d_ff=spec.expert_ff, shared_expert=False), **options)


@dataclass
class Run:
    """Everything a run recorded, for the metrics and the comparison."""
    spec: ModelSpec
    mix: Dict[str, Any]
    seconds: float
    ticks: List[Tick] = field(default_factory=list)
    reqs: List[Any] = field(default_factory=list)
    t_start: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    plan_ready_s: Optional[float] = None
    first_window_call: int = 0
    window_calls_end: int = 0
    span_log: List[Tuple[str, float]] = field(default_factory=list)
    compiles_in_window: int = 0
    cache_reads: int = 0
    slow_compiles: int = 0        # window compiles JAX's cache would keep
    cache_threshold_s: float = 0.0
    longest_compile_s: float = 0.0
    compile_intervals: List[Tuple[float, float]] = field(
        default_factory=list)
    gather_window: int = 4
    memory_peak_bytes: int = 0
    rows: Optional[np.ndarray] = None      # (slots, calls) fed tokens
    outs: Optional[np.ndarray] = None      # (slots, calls) step outputs
    layouts: Dict[str, int] = field(default_factory=dict)
    routing: Optional[np.ndarray] = None
    trace: Optional[Dict[str, Any]] = None
    peaks: Any = None
    attempted: int = 0
    failed: int = 0

    def req_times(self) -> List[ReqTimes]:
        return [ReqTimes(r.uid, r.due, list(r.token_ticks))
                for r in self.reqs]


def build(spec: ModelSpec, params):
    """The program's server for ``spec`` serving ``params``, with its KV
    plan submitted; returns (server, ticket, submit time)."""
    import dataclasses

    from repro.core.service import PlanService
    from repro.models import get_model
    from repro.runtime.server import Server, page_ticket

    cfg = arch_config(spec)
    model = get_model(cfg)
    model = dataclasses.replace(model, init=lambda key: params)
    service = PlanService(workers=2)
    t_submit = time.perf_counter()
    ticket = page_ticket(cfg, spec.max_len, page=spec.page,
                         readers=spec.slots, service=service)
    server = Server(model, max_batch=spec.slots, max_len=spec.max_len,
                    kv_plan=ticket)
    return server, ticket, service, t_submit


def expected_tree(spec: ModelSpec):
    import jax

    from repro.models import get_model
    return jax.eval_shape(get_model(arch_config(spec)).init,
                          jax.random.PRNGKey(0))


def _solved(server, ticket) -> bool:
    if not ticket.done():
        return False
    try:
        return server.pager.artifact.layout == ticket.artifact().layout
    except Exception:                      # a failed solve never lands
        return False


def serve(run: Run, server, ticket, t_submit: float, plans: List[Planned],
          Tracked, rec: Recorder, trace_dir: Optional[str]) -> None:
    """Set-up, then the measured window, then (where the mix asks) a
    drain that waits for every due request's first token."""
    import jax

    rec.server = server
    server._decode = rec.wrap(server._decode)
    run.gather_window = int(getattr(server, "_gather_window", 4))
    jax.monitoring.register_event_duration_secs_listener(rec.on_duration)
    jax.monitoring.register_event_listener(rec.on_event)
    try:
        _serve(run, server, ticket, t_submit, plans, Tracked, rec, trace_dir)
    finally:
        jax.monitoring.unregister_event_duration_listener(rec.on_duration)
        jax.monitoring.unregister_event_listener(rec.on_event)


def _serve(run: Run, server, ticket, t_submit: float, plans: List[Planned],
           Tracked, rec: Recorder, trace_dir: Optional[str]) -> None:
    import jax

    warm = [Tracked(rec, p) for p in plans if p.group == "warm"]
    load = [Tracked(rec, p) for p in plans if p.group == "load"]
    run.reqs = warm + load
    run.attempted = len(run.reqs)
    for r in warm:
        server.submit(r)
    until = run.mix["warm"]["until"]
    extra = int(run.mix["warm"].get("ticks", 0))

    def tick(record: bool):
        rec.tick = len(run.ticks)
        rec.tokens_this_tick = 0
        calls0 = len(rec.fed)
        t0 = time.perf_counter()
        if trace_dir is not None and record:
            with jax.profiler.TraceAnnotation("bench.tick"):
                server.tick()
        else:
            server.tick()
        t1 = time.perf_counter()
        calls = len(rec.fed) - calls0
        decoded = rec.tokens_this_tick
        run.ticks.append(Tick(
            start=t0, end=t1, calls=calls, tokens=decoded,
            prefill_tokens=calls - (1 if decoded else 0),
            layout=server.pager.artifact.describe()))
        if record:
            run.span_log.append(("bench.tick", t0))

    # -- set-up -----------------------------------------------------------
    def warm_done():
        if until == "admitted":
            return all(r.first_call is not None for r in warm)
        return all(r.done for r in warm)

    n = 0
    while not (_solved(server, ticket) and warm_done()):
        if server.queue or server.active:
            tick(False)
        else:
            ticket.wait(PLAN_TIMEOUT_S)
            server.tick()            # adopts the solved layout, if idle
        if run.plan_ready_s is None and _solved(server, ticket):
            run.plan_ready_s = time.perf_counter() - t_submit
        n += 1
        if n > MAX_WARM_TICKS:
            raise RuntimeError("set-up did not reach the solved layout "
                               "with the warm requests served")
    for _ in range(extra):
        tick(False)
    jax.block_until_ready(server.cache)

    # -- window -----------------------------------------------------------
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    pending = deque(sorted(load, key=lambda r: r.plan.due_s))
    rec.counting = True
    run.first_window_call = len(rec.fed)
    first_tick = len(run.ticks)
    run.t_open = t_open = time.perf_counter()
    run.setup_s = t_open - run.t_start
    run.t_close = t_close = t_open + run.seconds
    for r in pending:
        r.due = t_open + r.plan.due_s
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        while pending and pending[0].due <= now:
            r = pending.popleft()
            if trace_dir is not None:
                run.span_log.append(("bench.submit", now))
                with jax.profiler.TraceAnnotation("bench.submit"):
                    server.submit(r)
            else:
                server.submit(r)
            r.submitted = now
        if server.queue or server.active:
            tick(True)
        else:
            wake = pending[0].due if pending else t_close
            time.sleep(max(0.0, min(wake, t_close) - now))
    rec.counting = False
    run.window_calls_end = len(rec.fed)
    in_window = [d for end, d in rec.compiles if t_open <= end <= t_close]
    run.compiles_in_window = len(in_window)
    run.cache_threshold_s = float(
        jax.config.jax_persistent_cache_min_compile_time_secs)
    run.slow_compiles = sum(1 for d in in_window
                            if d >= run.cache_threshold_s)
    run.longest_compile_s = max(in_window, default=0.0)
    run.compile_intervals = [(end - d, end) for end, d in rec.compiles]
    run.cache_reads = rec.cache_reads
    if trace_dir is not None:
        jax.block_until_ready(server.cache)
        jax.profiler.stop_trace()
    run.layouts = {}
    for t in run.ticks[first_tick:]:
        run.layouts[t.layout] = run.layouts.get(t.layout, 0) + t.tokens

    # -- drain: the latency of a request that waits counts the wait --------
    drain_end = time.perf_counter() + float(run.mix.get("drain_s", 0))
    due_in_window = [r for r in load if r.submitted is not None]
    while any(not r.token_ticks for r in due_in_window) \
            and time.perf_counter() < drain_end and \
            (server.queue or server.active):
        tick(False)
    run.failed = sum(1 for r in run.reqs if r.done and not r.out)


def collect(run: Run, rec: Recorder) -> None:
    """Pull what the calls were fed and returned to the host."""
    import jax

    fed = jax.device_get(rec.fed)
    outs = jax.device_get(rec.out)
    run.rows = np.concatenate([np.asarray(f) for f in fed], axis=1) \
        if fed else np.zeros((run.spec.slots, 0), np.int32)
    run.outs = np.concatenate([np.asarray(o) for o in outs], axis=1) \
        if outs else np.zeros((run.spec.slots, 0), np.int32)


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def release(server, service, rec: Recorder) -> None:
    """Drop every array the program holds, so the reference has the
    chip's memory."""
    server.cache = None
    server._params = None
    server.kv_records = None
    server._decode = None
    rec.fed.clear()
    rec.out.clear()
    rec.server = None
    service.shutdown()
    gc.collect()


@dataclass
class Served:
    """Where each served token came from, for the comparison."""
    calls: np.ndarray      # step call (= row position) of each token
    slots: np.ndarray
    tokens: np.ndarray
    feed_mismatches: int   # prompt tokens not fed as sent
    record_mismatches: int  # decode inputs that are not the last token
    step_mismatches: int   # served tokens that are not the step's output
    record_checks: int


def served(run: Run) -> Served:
    """Every token the server handed back (each request's first token,
    from its prefill's last call, and each decoded token), with the
    checks of the record path: the token fed to each decode call must be
    the request's previous token, and each prompt must be fed as sent."""
    calls, slots, tokens = [], [], []
    feed = record = step = checks = 0
    rows, outs = run.rows, run.outs
    for r in run.reqs:
        if r.first_call is None or not r.out:
            continue
        slot = r.token_slots[0]
        # the prefill: len(prompt) calls ending at first_call, in `slot`
        p0 = r.first_call - len(r.prompt) + 1
        if slot < 0 or p0 < 0:
            feed += len(r.prompt)
            continue
        feed += int(np.sum(rows[slot, p0:r.first_call + 1]
                           != np.asarray(r.prompt)))
        prev = r.first_tok                          # the first token
        if int(outs[slot, r.first_call]) != prev:
            step += 1
        calls.append(r.first_call)
        slots.append(slot)
        tokens.append(prev)
        for c, s, tok in zip(r.token_calls, r.token_slots, r.out):
            checks += 1
            if s != slot or int(rows[slot, c]) != prev:
                record += 1
                continue
            if int(outs[s, c]) != int(tok):
                step += 1
            calls.append(c)
            slots.append(s)
            tokens.append(int(tok))
            prev = int(tok)
    return Served(np.asarray(calls, np.int64), np.asarray(slots, np.int64),
                  np.asarray(tokens, np.int64), feed, record, step, checks)
