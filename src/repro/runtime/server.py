"""Batched serving loop: continuous-batching decode with a paged KV cache.

Serving structure (vLLM-style, TPU-native):

* requests queue in; the scheduler packs up to ``max_batch`` active
  sequences into the fixed decode batch (slots);
* prefill runs per request (chunked attention), its KV written into the
  slot's region of the cache;
* one fused ``serve_step`` decodes a token for every active slot per tick;
* finished sequences (EOS or max_len) free their slot for the next queued
  request -- continuous batching.

The cache pages are banks from the banking planner (pages = banks, page
size = bank volume).  Since the service redesign the server never blocks
on the solver: ``page_ticket()`` submits the KV-pool problem to the
:class:`~repro.core.service.PlanService` and the :class:`Server` starts
serving immediately from the ticket's **fallback artifact** (trivial
single-bank scheme), then atomically hot-swaps the page pool -- and the
bank-major token-record table -- to the solved artifact between decode
ticks once the background solve lands.

Each decode tick reads its per-slot token records through **one batched
banked gather** (a single ``pallas_call`` over a stacked
``(max_batch, W)`` index matrix) instead of one kernel launch per
row-set -- the compiled resolution arithmetic addresses the kernel's row
DMAs from the prefetched indices either way, so the scheduler and the
gather agree on the layout by construction.  Writes go the same way:
token records queue per tick and flush through **one batched banked
scatter** (``artifact.scatter`` with per-slot column indices), so the
resolution circuit -- not host-side index math -- places the rows on
both paths.

Every tick runs in live ``serve.*`` spans (:mod:`repro.core.tracing`),
recorded through the plan service's tracer when it has one and the
process's :func:`~repro.core.tracing.serve_tracer` otherwise, and shown
on a profiler trace's host plane: ``serve.tick`` holds ``serve.swap``
(a layout adopted), ``serve.admit`` (one request's prefill),
``serve.gather`` (with ``serve.gather.wait``, the blocking readback),
``serve.inputs``, ``serve.step`` (with ``serve.step.wait``),
``serve.emit`` and ``serve.scatter`` (every record flush).  Compiles
land on the innermost span that ran them; ``serve.queue_wait`` records
each request's time from ``submit`` to admission.  Once a tick the
served artifact's kernel builds and calls are published as the gauges
``banked_kernel_builds`` and ``banked_kernel_calls`` (label ``op``).
A mixture of experts' step also leaves its expert traffic in the cache
it returns (``MoECache.routes``); the server reads it with the step's
tokens, in the same blocking read, and puts it, summed over layers, on
``serve.step`` (and on ``serve.admit``, summed over the prefill calls) as
the attributes ``moe_experts_routed``, ``moe_experts_read`` and
``moe_dropped``, and on counters of the same names.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.artifact import CompiledBankingPlan
from ..core.controller import AccessDecl, Counter, Ctrl, Program, Sched
from ..core.jointplan import ResourceBudget
from ..core.service import (JointTicket, PlanService, PlanTicket,
                            default_service)
from ..core.polytope import Affine, MemorySpec
from ..core.tracing import new_trace_id, serve_tracer
from ..models import Model
from ..models.moe import ROUTE_COUNTS, MoECache
from ..launch import steps as steps_mod


@dataclass
class Request:
    uid: int
    prompt: np.ndarray          # (S,) int32
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    done: bool = False
    queued_at: Optional[float] = None   # perf_counter at submit


def _page_program(max_len: int, page: int, readers: int) -> Program:
    mem = MemorySpec("kv_pool", dims=(max_len,), word_bits=16, ports=1)
    return Program(
        root=Ctrl("decode", Sched.INNER,
                  counters=[Counter("r", 0, 1, readers, par=readers),
                            Counter("j", 0, 1, page)],
                  accesses=[AccessDecl("kv_pool", (Affine.of(r=page, j=1),))]),
        memories={"kv_pool": mem},
    )


def model_memory_program(cfg: ArchConfig, max_len: int, page: int = 128,
                         readers: int = 8) -> Program:
    """One whole-model ``Program``: every banked memory the serving loop
    touches for this architecture, as children of one root controller.

    * ``kv_pool`` -- the paged KV cache every family reads per decode
      tick (``readers`` parallel lanes, ``page``-token pages);
    * ``moe_dispatch`` (MoE families) -- the per-expert token staging
      buffer the router scatters into, ``top_k`` experts in parallel;
    * ``ssm_state`` (SSM families) -- the chunked state the scan
      updates, four head lanes in parallel.

    This is what turns each config in ``configs/`` into a distinct
    joint-planning workload: one ``submit_joint`` co-selects schemes
    for all of a model's pools under a shared budget.
    """
    mems: Dict[str, MemorySpec] = {
        "kv_pool": MemorySpec("kv_pool", dims=(max_len,), word_bits=16,
                              ports=1)}
    kids = [Ctrl("decode", Sched.INNER,
                 counters=[Counter("r", 0, 1, readers, par=readers),
                           Counter("j", 0, 1, page)],
                 accesses=[AccessDecl("kv_pool",
                                      (Affine.of(r=page, j=1),))])]
    if cfg.n_experts > 0:
        slot = max(4, page // 4)
        mems["moe_dispatch"] = MemorySpec(
            "moe_dispatch", dims=(cfg.n_experts * slot,), word_bits=16,
            ports=1)
        par = max(1, cfg.top_k)
        kids.append(Ctrl(
            "route", Sched.INNER,
            counters=[Counter("e", 0, 1, par, par=par),
                      Counter("j", 0, 1, slot)],
            accesses=[AccessDecl("moe_dispatch",
                                 (Affine.of(e=slot, j=1),))]))
    if cfg.ssm_state > 0:
        lanes = 4
        mems["ssm_state"] = MemorySpec(
            "ssm_state", dims=(lanes * cfg.ssm_state,), word_bits=16,
            ports=1)
        kids.append(Ctrl(
            "scan", Sched.INNER,
            counters=[Counter("h", 0, 1, lanes, par=lanes),
                      Counter("j", 0, 1, cfg.ssm_state)],
            accesses=[AccessDecl("ssm_state",
                                 (Affine.of(h=cfg.ssm_state, j=1),))]))
    if len(kids) == 1:
        return Program(root=kids[0], memories=mems)
    return Program(root=Ctrl("model", Sched.FORKJOIN, children=kids),
                   memories=mems)


def joint_ticket(cfg: ArchConfig, max_len: int, page: int = 128,
                 readers: int = 8, *,
                 service: Optional[PlanService] = None,
                 budget: Optional[ResourceBudget] = None,
                 scorer=None, tenant: Optional[str] = None) -> JointTicket:
    """Submit the whole model's banking problems as ONE joint request;
    returns the :class:`~repro.core.service.JointTicket` immediately.

    The server starts on ``ticket.fallback()`` for every pool and
    promotes all of them to the jointly co-selected layouts atomically
    between decode ticks -- never a mixed generation.  ``budget`` caps
    the summed draw (banks / volume / LUT / FF / BRAM / DSP) across all
    of the model's memories.
    """
    from ..core.solver import SolverOptions
    svc = service if service is not None else default_service()
    return svc.submit_joint(
        model_memory_program(cfg, max_len, page=page, readers=readers),
        budget=budget,
        opts=SolverOptions(b_candidates=(page, 1), allow_multidim=False),
        scorer=scorer, tenant=tenant)


def page_ticket(cfg: ArchConfig, max_len: int, page: int = 128,
                readers: int = 8, *,
                service: Optional[PlanService] = None,
                scorer=None, tenant: Optional[str] = None) -> PlanTicket:
    """Submit the KV-pool banking problem (pages = banks); returns the
    :class:`PlanTicket` immediately.

    ``readers`` concurrent decode lanes must never contend on a page.
    The server starts on ``ticket.fallback()`` (one bank = one page, no
    solver work) and hot-swaps to ``ticket.artifact()`` between ticks
    when the solve lands; a warm plan store answers before the ticket is
    even returned.  ``scorer="measured"`` ranks candidates on the
    service's telemetry log (see ``PlanService.enable_telemetry``).
    ``tenant`` names this server on a shared multi-tenant service
    (QoS band, quotas, per-tenant stats -- see
    :mod:`repro.runtime.tenancy`).
    """
    from ..core.solver import SolverOptions
    svc = service if service is not None else default_service()
    return svc.submit(
        _page_program(max_len, page, readers), "kv_pool",
        opts=SolverOptions(b_candidates=(page, 1), allow_multidim=False),
        scorer=scorer, tenant=tenant)


def page_solution(cfg: ArchConfig, max_len: int, page: int = 128,
                  readers: int = 8) -> CompiledBankingPlan:
    """Blocking convenience: the *solved* compiled KV-pool artifact.

    ``page_ticket(...).artifact()`` -- tools and tests that want the final
    layout synchronously; the serving path itself uses the ticket.
    """
    return page_ticket(cfg, max_len, page=page, readers=readers).artifact()


class KVPagePool:
    """Page accounting over a compiled KV banking artifact's layout.

    Pages *are* the artifact's banks and the page size is its bank volume,
    read straight off ``artifact.layout`` -- no local page math.  The
    banking problem is posed per sequence (``dims = (max_len,)``), and the
    decode cache is a dense per-slot region, so every slot owns its own
    ``n_banks`` pages: admission succeeds iff the request's token budget
    fits one slot's pages.  Pages release when the sequence finishes.

    ``swap(artifact)`` re-derives the page geometry -- and every live
    slot's page count -- from a new artifact's layout, which is how the
    server promotes the fallback layout to the solved one mid-flight.
    """

    def __init__(self, artifact: CompiledBankingPlan, slots: int = 1):
        self.slots = slots
        self.owned: Dict[int, int] = {}    # slot -> allocated pages
        self.tokens: Dict[int, int] = {}   # slot -> admitted token budget
        self.swap(artifact)

    def swap(self, artifact: CompiledBankingPlan) -> None:
        """Adopt a new artifact's layout; re-page live allocations."""
        self.artifact = artifact
        self.layout = artifact.layout
        self.page_size = int(self.layout.bank_volume)
        self.pages_per_slot = int(self.layout.n_banks)
        self.owned = {slot: min(self.pages_for(tok), self.pages_per_slot)
                      for slot, tok in self.tokens.items()}

    @property
    def total_pages(self) -> int:
        return self.pages_per_slot * self.slots

    @property
    def used_pages(self) -> int:
        return sum(self.owned.values())

    def pages_for(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.page_size))

    def fits(self, n_tokens: int) -> bool:
        """Can this token budget ever be admitted (into one slot)?"""
        return self.pages_for(n_tokens) <= self.pages_per_slot

    def try_alloc(self, slot: int, n_tokens: int) -> bool:
        need = self.pages_for(n_tokens)
        if need > self.pages_per_slot or slot in self.owned:
            return False
        self.owned[slot] = need
        self.tokens[slot] = int(n_tokens)
        return True

    def release(self, slot: int) -> None:
        self.owned.pop(slot, None)
        self.tokens.pop(slot, None)


class Server:
    """Continuous-batching decode server.

    ``kv_plan`` may be a solved ``CompiledBankingPlan`` (legacy), a
    ``PlanTicket``, or a ``JointTicket``: with a ticket the server
    builds its page pool and token-record table from the ticket's
    fallback -- serving its first tick without waiting on the solver --
    and atomically swaps in the solved artifact between ticks once the
    ticket resolves.  A joint ticket brings the whole model's pools
    (``kv_pool`` plus e.g. ``moe_dispatch`` / ``ssm_state``): ALL of
    them promote to the jointly co-selected layouts in one coherent
    generation between decode ticks, never a mixed one
    (``server.generations`` stays uniform by construction; asserted by
    ``coherent``).
    """

    def __init__(self, model: Model, max_batch: int = 4, max_len: int = 128,
                 kv_plan: Optional[Union[CompiledBankingPlan,
                                         PlanTicket, JointTicket]] = None):
        self.model = model
        self.cfg = model.cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.queue: Deque[Request] = deque()
        self.active: Dict[int, Request] = {}   # slot -> request
        self._decode = jax.jit(steps_mod.make_serve_step(model))
        self._params = model.init(jax.random.PRNGKey(0))
        self.cache = model.init_cache(max_batch, max_len)
        self._kv_ticket: Optional[PlanTicket] = None
        self._kv_art: Optional[CompiledBankingPlan] = None
        # the joint ticket graph and its satellite pools (every model
        # memory except kv_pool, which owns the record table below)
        self._joint: Optional[JointTicket] = None
        self.pools: Dict[str, KVPagePool] = {}
        self.generations: Dict[str, int] = {}
        self._joint_version = 0
        self._joint_adopted_final = False
        # demotion hot-swap: remember which service answered the KV plan
        # (and under which key) so _maybe_swap_kv can poll its telemetry
        # hub for a replacement ticket after the served plan is demoted
        self._kv_service = (kv_plan._service
                            if isinstance(kv_plan, (PlanTicket, JointTicket))
                            else None)
        self._kv_key = ((kv_plan.signature, kv_plan.scorer_name)
                        if isinstance(kv_plan, PlanTicket) else None)
        art: Optional[CompiledBankingPlan] = None
        if isinstance(kv_plan, JointTicket):
            self._joint = kv_plan
            arts = (kv_plan.artifacts() if kv_plan.done()
                    else kv_plan.fallback())
            if "kv_pool" not in arts:
                raise ValueError(
                    "joint ticket has no 'kv_pool' member; build the "
                    "program with model_memory_program()")
            art = arts["kv_pool"]
            for name, a in arts.items():
                if name != "kv_pool":
                    self.pools[name] = KVPagePool(a, slots=max_batch)
            self.generations = {name: 0 for name in arts}
            self._joint_adopted_final = kv_plan.done()
        elif isinstance(kv_plan, PlanTicket):
            # serve NOW: solved artifact when already done, else fallback.
            # Only drop the ticket once its solved artifact was actually
            # adopted -- a solve landing (or failing) between these calls
            # must still hot-swap (or keep serving the fallback) later.
            self._kv_ticket = kv_plan
            if kv_plan.done():
                try:
                    art = kv_plan.artifact()
                    self._kv_ticket = None
                except Exception:
                    art = None   # solve failed: fall back, like mid-serve
            if art is None:
                art = kv_plan.fallback()
        elif kv_plan is not None:
            art = kv_plan
        self.pager = (KVPagePool(art, slots=max_batch)
                      if art is not None else None)
        self.kv_records = None    # bank-major (banks, vol, max_batch) int32
        self._pending_records: List[tuple] = []   # (pos, slot, tok) queue
        self._gather_window = min(4, max_len)
        # every gathered decode input is checked against the token the
        # host recorded: checks per serving layout, and the mismatches
        self.record_checks: Dict[str, int] = {}
        self.record_mismatches = 0
        if art is not None:
            self._adopt_kv_artifact(art, records=None)
        self.swaps = 0
        self.promotions = 0       # best-so-far adoptions before the solve
        self.joint_swaps = 0      # coherent all-pool swaps (final plan)
        self.joint_promotions = 0  # coherent all-pool best-so-far adoptions
        self._kv_best_version = 0
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.positions = np.zeros(max_batch, np.int64)  # next record slot
        self.ticks = 0
        # rolling serve trace: (tracer, trace_id) the serve.* spans
        # accumulate under, finished and restarted every
        # _SERVE_TRACE_TICKS ticks so completed windows reach the
        # flight recorder instead of growing forever
        self._trace: Optional[tuple] = None
        self._trace_ticks = 0

    # -- banked token records ----------------------------------------------------
    def _adopt_kv_artifact(self, art: CompiledBankingPlan,
                           records) -> None:
        """(Re)build the bank-major record table for a (new) artifact;
        ``records`` carries logical rows across a swap."""
        self._kv_art = art
        if records is None:
            records = jnp.zeros((self.max_len, self.max_batch), jnp.int32)
        self.kv_records = art.pack(records)

    def _record(self, slot: int, tok: int) -> None:
        """Queue one token record at the slot's next position.  Records
        land in the bank-major table at the next flush, placed by the
        artifact's scatter kernel (same resolution circuit the gather
        reads through)."""
        pos = int(self.positions[slot])
        if self.kv_records is not None and pos < self.max_len:
            self._pending_records.append((pos, slot, int(tok)))
        self.positions[slot] = pos + 1

    def _flush_records(self) -> None:
        """Drain queued token records through ONE batched banked scatter
        -- the write-path twin of the tick's batched gather.  The
        artifact's BA/BO circuit places every row in the kernel's index
        map; no host-side bank arithmetic.

        The writes are padded to the next power of two by repeating the
        last one, so the artifact builds one record-write executable per
        power of two and not per prompt length; the kernel writes in
        order, last write wins, so the repeats leave the table as the
        real writes do."""
        if not self._pending_records:
            return
        with self._span("serve.scatter"):
            pend, self._pending_records = self._pending_records, []
            pend += pend[-1:] * ((1 << (len(pend) - 1).bit_length())
                                 - len(pend))
            rows = np.asarray([p for p, _, _ in pend], np.int64)
            cols = np.asarray([s for _, s, _ in pend], np.int64)
            vals = np.asarray([t for _, _, t in pend], np.int32)
            self.kv_records = self._kv_art.scatter(self.kv_records, rows,
                                                   vals, col=cols)

    def _gather_next_tokens(self) -> Dict[int, int]:
        """Each active slot's decode input, via ONE batched banked gather.

        Stacks every active slot's trailing ``W`` record positions into a
        ``(max_batch, W)`` index matrix -- a single ``pallas_call``
        resolves all of them through the compiled BA/BO circuit; rows past
        the active slots read address 0 and are dropped, so one gather
        executable serves every batch size.  The last column is the most
        recent record: the next decode input.
        """
        self._flush_records()     # queued writes land before any read
        slots = sorted(self.active)
        W = self._gather_window
        rows = np.zeros((self.max_batch, W), np.int32)
        for i, s in enumerate(slots):
            pos = min(int(self.positions[s]), self.max_len)
            rows[i] = np.clip(np.arange(pos - W, pos), 0, self.max_len - 1)
        got = self._kv_art.gather(self.kv_records, rows)
        with self._span("serve.gather.wait"):
            got = np.asarray(got)               # (max_batch, W, max_batch)
        layout = self._kv_art.describe()
        out = {}
        for i, s in enumerate(slots):
            if int(self.positions[s]) <= self.max_len:
                out[s] = int(got[i, -1, s])
                self.record_checks[layout] = \
                    self.record_checks.get(layout, 0) + 1
                if out[s] != self.active[s]._next:
                    self.record_mismatches += 1
            else:  # records past max_len aren't stored; fall back
                out[s] = getattr(self.active[s], "_next", 1)
        return out

    # -- hot swap -----------------------------------------------------------------
    def _swap_to(self, art: CompiledBankingPlan) -> None:
        """Adopt a new layout atomically from the decode loop's point of
        view: the record table is unpacked from the old layout and
        repacked into the new one, the pager re-pages live slots, and
        the next tick's gather runs the new resolution circuit over
        identical logical records."""
        self._flush_records()     # pending writes belong to the old layout
        flat = self._kv_art.unpack(self.kv_records)   # logical rows survive
        self._adopt_kv_artifact(art, records=flat)
        self.pager.swap(art)

    def _maybe_swap_kv(self) -> None:
        """Between ticks: promote the page layout toward the solver.

        While the sharded search streams, the ticket's **best-so-far**
        scheme is adopted whenever it improves (the search never
        regresses, so each promotion strictly improves the layout); once
        the ticket resolves, the final solved artifact is swapped in --
        same winner the monolithic solver would have produced.

        With telemetry enabled on the answering service, a served layout
        the measurements demoted leaves a *replacement* re-solve ticket
        on the hub; adopting it here closes the self-correction loop --
        measure, demote, re-solve, hot-swap -- without the server ever
        blocking.
        """
        t = self._kv_ticket
        if t is None and self._kv_service is not None \
                and self._kv_key is not None:
            hub = getattr(self._kv_service, "telemetry", None)
            if hub is not None:
                t = hub.replacement(self._kv_key)
                if t is not None:
                    self._kv_ticket = t
                    self._kv_best_version = 0
        if t is None:
            return
        final = t.done()
        if final:
            self._kv_ticket = None
            try:
                art = t.artifact()
            except Exception:
                return  # solve failed: keep serving the current layout
        else:
            version = t.best_version()
            if version == self._kv_best_version:
                return
            self._kv_best_version = version
            art = t.best_so_far_artifact()
        # a promotion may already have landed the winning layout
        if art is None or art.layout == self._kv_art.layout:
            return
        with self._span("serve.swap", layout=art.describe()):
            self._swap_to(art)
        if final:
            self.swaps += 1
        else:
            self.promotions += 1

    # -- coherent multi-pool swap ---------------------------------------------
    @property
    def coherent(self) -> bool:
        """True iff every pool serves the same joint generation -- the
        invariant the atomic all-pool swap maintains: a decode tick
        never sees a mixed generation."""
        return len(set(self.generations.values())) <= 1

    def _swap_all(self, arts: Dict[str, CompiledBankingPlan]) -> int:
        """Adopt a whole joint selection atomically between ticks: the
        KV record table repacks, every satellite pool re-pages, and ALL
        pool generations advance to one new value in the same swap --
        no tick ever reads pools from two generations.  Returns how
        many pools actually changed layout."""
        changed = 0
        with self._span("serve.swap") as span:
            kv = arts.get("kv_pool")
            if kv is not None and self._kv_art is not None \
                    and kv.layout != self._kv_art.layout:
                self._swap_to(kv)
                changed += 1
            for name, pool in self.pools.items():
                a = arts.get(name)
                if a is not None and a.layout != pool.artifact.layout:
                    pool.swap(a)
                    changed += 1
            gen = max(self.generations.values(), default=0) + 1
            for name in self.generations:
                self.generations[name] = gen
            span.attrs.update(generation=gen, changed=changed)
        return changed

    def _maybe_swap_joint(self) -> None:
        """Between ticks: promote ALL pools toward the joint selection.

        While member solves stream, the joint ticket re-co-selects
        progressively; whenever the *joint* selection changes (its
        ``best_version`` bumps) every pool adopts its newly selected
        layout in one coherent swap.  Once the ticket resolves, the
        final certified selection lands the same way -- never a mixed
        generation."""
        jt = self._joint
        if jt is None:
            return
        if jt.done():
            if self._joint_adopted_final:
                return
            self._joint_adopted_final = True
            try:
                arts = jt.artifacts()
            except Exception:
                return   # selection failed: keep serving current layouts
            if self._swap_all(arts):
                self.joint_swaps += 1
            return
        version = jt.best_version()
        if version == self._joint_version:
            return
        self._joint_version = version
        try:
            arts = jt.artifacts()
        except Exception:
            return
        if self._swap_all(arts):
            self.joint_promotions += 1

    # -- admission -------------------------------------------------------------
    def submit(self, req: Request):
        req.queued_at = time.perf_counter()
        self.queue.append(req)

    def _admit(self):
        for slot in range(self.max_batch):
            if slot in self.active or not self.queue:
                continue
            req = self.queue[0]
            if self.pager is not None:
                need_tokens = len(req.prompt) + req.max_new
                if not self.pager.fits(need_tokens):
                    # can never fit a slot: reject instead of deadlocking
                    self.queue.popleft()
                    req.done = True
                    continue
                self.pager.try_alloc(slot, need_tokens)
            self.queue.popleft()
            tr, tid = self._tracer()
            if req.queued_at is not None:
                tr.record(tid, "serve.queue_wait", req.queued_at,
                          time.perf_counter(), uid=req.uid)
            with tr.span(tid, "serve.admit", uid=req.uid, slot=slot,
                         prompt_tokens=len(req.prompt)) as admit_span:
                self.positions[slot] = 0
                # per-request prefill: run the prompt through decode one
                # token at a time into this slot (batch=1 prefill folded
                # into the shared cache; a production server runs a
                # separate prefill graph)
                routes = []
                for t in req.prompt:
                    self.tokens = self.tokens.at[slot, 0].set(int(t))
                    nxt, _, self.cache = self._decode(
                        self._params, self.cache, self.tokens)
                    if isinstance(self.cache, MoECache):
                        routes.append(self.cache.routes)
                    self._record(slot, int(t))
                if routes:
                    nxt, routes = jax.device_get((nxt, routes))
                    self._note_routes(tr, admit_span, np.sum(routes, axis=0))
                nxt_tok = int(np.asarray(nxt)[slot, 0])
                req._next = nxt_tok
                self._record(slot, nxt_tok)   # the next tick's decode input
            self.active[slot] = req

    # -- decode tick -------------------------------------------------------------
    _SERVE_TRACE_TICKS = 256   # ticks per rolling serve-trace window

    def _tracer(self):
        """(tracer, trace_id) of the rolling serve trace: the answering
        plan service's tracer when it has one, else the process's
        ``serve_tracer()``."""
        tr = getattr(self._kv_service, "tracer", None) or serve_tracer()
        cur = self._trace
        if cur is None or cur[0] is not tr:
            self._end_trace()
            cur = self._trace = (tr, new_trace_id())
            tr.label(cur[1], "serve loop")
        return cur

    def _span(self, name: str, **attrs):
        tr, tid = self._tracer()
        return tr.span(tid, name, **attrs)

    def _end_trace(self) -> None:
        """Finish the rolling serve trace: it reaches the flight
        recorder, and the next span starts a fresh one."""
        if self._trace is not None:
            tr, tid = self._trace
            self._trace = None
            self._trace_ticks = 0
            tr.finish(tid, status="ok")

    def _maybe_swap(self) -> None:
        if self._joint is not None:
            self._maybe_swap_joint()
        else:
            self._maybe_swap_kv()

    def tick(self):
        """One decode tick: adopt a layout that landed, admit queued
        requests into free slots, then decode one token for every active
        slot, all inside one ``serve.tick`` span.  With nothing queued
        or active a call only adopts."""
        if not (self.queue or self.active):
            self._maybe_swap()
            return
        tr, tid = self._tracer()
        with tr.span(tid, "serve.tick", tick=self.ticks) as tick_span:
            self._maybe_swap()
            self._admit()
            if self.active:
                self._decode_active(tr, tid, tick_span)
        if tr.metrics is not None:
            tr.metrics.set_gauge("serve_active_slots", len(self.active))
            tr.metrics.set_gauge("serve_queue_depth", len(self.queue))
            if self._kv_art is not None:    # of the layout being served
                for op, n in self._kv_art.kernel_builds.items():
                    tr.metrics.set_gauge("banked_kernel_builds", n, op=op)
                for op, n in self._kv_art.kernel_calls.items():
                    tr.metrics.set_gauge("banked_kernel_calls", n, op=op)
        self._trace_ticks += 1
        if self._trace_ticks >= self._SERVE_TRACE_TICKS:
            self._end_trace()

    def _decode_active(self, tr, tid, tick_span) -> None:
        if self.kv_records is not None:
            with tr.span(tid, "serve.gather"):
                nxt_in = self._gather_next_tokens()  # one batched gather
        else:
            nxt_in = {s: getattr(r, "_next", 1)
                      for s, r in self.active.items()}
        with tr.span(tid, "serve.inputs"):
            for slot in self.active:
                self.tokens = self.tokens.at[slot, 0].set(nxt_in[slot])
        with tr.span(tid, "serve.step") as step_span:
            nxt, _, self.cache = self._decode(self._params, self.cache,
                                              self.tokens)
            routes = (self.cache.routes if isinstance(self.cache, MoECache)
                      else None)
            with tr.span(tid, "serve.step.wait"):
                if routes is None:
                    nxt = np.asarray(nxt)
                else:
                    nxt, routes = jax.device_get((nxt, routes))
            if routes is not None:
                self._note_routes(tr, step_span, routes)
        slots = len(self.active)
        with tr.span(tid, "serve.emit"):
            finished = []
            for slot, req in self.active.items():
                tok = int(nxt[slot, 0])
                req.out.append(tok)
                req._next = tok
                self._record(slot, tok)
                if len(req.out) >= req.max_new:
                    req.done = True
                    finished.append(slot)
            for slot in finished:
                del self.active[slot]
                if self.pager is not None:
                    self.pager.release(slot)
        if self.kv_records is not None:
            self._flush_records()   # this tick's records land this tick
        self.ticks += 1
        tick_span.attrs.update(slots=slots, tokens=slots)

    @staticmethod
    def _note_routes(tr, span, routes) -> None:
        """A mixture of experts' step traffic, per layer (L, 3) in the
        order of ``moe.ROUTE_COUNTS``: summed over layers onto ``span``
        under those names, and added to the counters of those names."""
        for name, n in zip(ROUTE_COUNTS, np.sum(routes, axis=0).tolist()):
            span.attrs[name] = n
            if tr.metrics is not None:
                tr.metrics.inc(name, n)

    def run(self, max_ticks: int = 1000):
        while (self.queue or self.active) and self.ticks < max_ticks:
            self.tick()
        # a partial serve-trace window still lands in the recorder
        self._end_trace()
