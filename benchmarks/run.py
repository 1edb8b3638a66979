"""Benchmark harness: one function per paper table/figure + kernel micros.

``PYTHONPATH=src python -m benchmarks.run [--fast]``

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
human-readable tables.  Roofline numbers live in launch/roofline.py (they
need the 512-device dry-run) -- see EXPERIMENTS.md.
"""

from __future__ import annotations

import argparse
import json
import time


def _bench_callable(fn, *args, iters=3, warmup=1, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / iters
    return out, dt * 1e6


def bench_tables(fast: bool) -> None:
    from benchmarks import tables

    t2 = tables.table2()
    print("\n=== Table 2 (Virtex-7 proxy): app x system ===")
    print(f"{'app':12s} {'system':9s} {'LUT':>8s} {'FF':>8s} {'BRAM':>5s} {'DSP':>4s} {'t(s)':>6s}")
    for app, rows in t2.items():
        for sysname, r in rows.items():
            print(f"{app:12s} {sysname:9s} {r['lut']:8.0f} {r['ff']:8.0f} "
                  f"{r['bram']:5d} {r['dsp']:4d} {r['seconds']:6.2f} "
                  f"{r['scheme'] if r['banks'] == 0 else ''}")
    ch2 = tables.avg_change(t2)
    for sysname, d in ch2.items():
        print(f"Avg change vs {sysname}: "
              + " ".join(f"{k}={v:+.1f}%" for k, v in d.items()))
        print(f"table2_vs_{sysname},0,"
              + ";".join(f"{k}{v:+.1f}%" for k, v in d.items()))

    t3 = tables.table3()
    print("\n=== Table 3 (AWS F1 proxy): app x system ===")
    for app, rows in t3.items():
        for sysname, r in rows.items():
            print(f"{app:12s} {sysname:9s} {r['lut']:8.0f} {r['ff']:8.0f} "
                  f"{r['bram']:5d} {r['dsp']:4d} {r['seconds']:6.2f} "
                  f"{r['scheme'] if r['banks'] == 0 else ''}")
    ch3 = tables.avg_change(t3)
    for sysname, d in ch3.items():
        print(f"Avg change vs {sysname}: "
              + " ".join(f"{k}={v:+.1f}%" for k, v in d.items()))
        print(f"table3_vs_{sysname},0,"
              + ";".join(f"{k}{v:+.1f}%" for k, v in d.items()))

    st = tables.search_time()
    print("\n=== Search-time (Sec 6 claim) ===")
    for app, r in st.items():
        print(f"{app:8s} multidim={r['with_multidim_s']:.2f}s "
              f"flat-only={r['flat_only_s']:.2f}s speedup={r['speedup']:.2f}x")
        print(f"search_time_{app},{r['with_multidim_s']*1e6:.0f},"
              f"speedup={r['speedup']:.2f}x")

    import os
    cached = "results/fig11.json"
    if fast and os.path.exists(cached):
        f11 = json.load(open(cached))
        tag = " (cached: estimator CV is independent of ranking weights)"
    elif not fast:
        f11 = tables.fig11(n_splits=3)
        with open(cached, "w") as f:
            json.dump(f11, f, indent=1)
        tag = ""
    else:
        return
    print(f"\n=== Fig 11 (cost-model CV, 3 splits){tag} ===")
    for m in ("gbt", "mlp"):
        for tgt, s in f11[m].items():
            print(f"{m:4s} {tgt:5s} R2 = {s['mean']:.3f} +- {s['std']:.3f}")
            print(f"fig11_{m}_{tgt},0,R2={s['mean']:.3f}")


def bench_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    print("\n=== Kernel micro-benches (interpret on CPU; structural) ===")
    B, S, H, Hkv, Dh = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    _, us = _bench_callable(
        lambda: ops.mha(q, k, v).block_until_ready(), iters=2)
    print(f"flash_attention_{S},{us:.0f},interpret")

    Bs, Hs, Q, P, N = 1, 4, 64, 32, 16
    x = jnp.asarray(rng.normal(size=(Bs, Hs, Q, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.2, (Bs, Hs, Q)), jnp.float32)
    cum = jnp.cumsum(-dt, -1)
    bm = jnp.asarray(rng.normal(size=(Bs, Q, N)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(Bs, Q, N)), jnp.float32)
    s0 = jnp.zeros((Bs, Hs, P, N), jnp.float32)
    _, us = _bench_callable(
        lambda: ops.ssd(x, dt, bm, cm, cum, s0)[0].block_until_ready(),
        iters=2)
    print(f"ssd_chunk_{Q},{us:.0f},interpret")


def bench_solver() -> None:
    from repro.core import problems
    from repro.core.planner import BankingPlanner

    planner = BankingPlanner()
    print("\n=== Solver latency per benchmark problem ===")
    for app in list(problems.STENCILS) + ["sw", "spmv", "sgd", "md_grid"]:
        prog = problems.build(app)
        memname = list(prog.memories)[0]
        t0 = time.perf_counter()
        plan = planner.plan(prog, memname, use_cache=False)
        us = (time.perf_counter() - t0) * 1e6
        print(f"solver_{app},{us:.0f},candidates={plan.num_candidates}")


def bench_planner_cache() -> None:
    """Cold plan vs warm signature-cache hit (the serving-hot-path win)."""
    from repro.core import problems
    from repro.core.planner import BankingPlanner

    planner = BankingPlanner()
    prog = problems.build("sobel")
    memname = list(prog.memories)[0]
    t0 = time.perf_counter()
    planner.plan(prog, memname)
    cold_us = (time.perf_counter() - t0) * 1e6
    _, warm_us = _bench_callable(
        lambda: planner.plan(prog, memname), iters=20, warmup=2)
    print("\n=== Planner cache (cold solve vs warm hit) ===")
    print(f"planner_cache,{warm_us:.0f},"
          f"cold={cold_us:.0f}us;speedup={cold_us / max(warm_us, 1e-9):.0f}x")


def bench_compile_cache() -> None:
    """Cold artifact lowering vs warm planner compile-cache hit.

    The cold path lowers the resolution graphs to jit-ready callables and
    builds the pack/unpack address tables; warm calls are dict hits on the
    planner's (signature, backend)-keyed compile cache -- the lowering
    happens once per scheme per process (or once ever, with cache_dir=)."""
    from repro.core import problems
    from repro.core.planner import BankingPlanner

    planner = BankingPlanner()
    prog = problems.build("sobel")
    memname = list(prog.memories)[0]
    plan = planner.plan(prog, memname)
    t0 = time.perf_counter()
    planner.compile(plan)
    cold_us = (time.perf_counter() - t0) * 1e6
    _, warm_us = _bench_callable(
        lambda: planner.compile(plan), iters=50, warmup=2)
    print("\n=== Compile cache (cold lower vs warm artifact hit) ===")
    print(f"compile_cache,{warm_us:.0f},"
          f"cold={cold_us:.0f}us;speedup={cold_us / max(warm_us, 1e-9):.0f}x")


def bench_plan_service() -> None:
    """The async front door: submit latency, time-to-first-fallback
    artifact, time-to-solved-swap, and the warm-store hit -- the four
    numbers that decide whether serving ever blocks on the solver.
    Emitted both as a CSV row and as results/BENCH_plan_service.json."""
    import tempfile

    from repro.core import PlanService, problems
    from repro.core.store import DirectoryStore

    # warm the jax import + trivial-lowering path so the fallback number
    # measures the artifact machinery, not a first-time jax import
    from repro.core import MemorySpec
    from repro.core.artifact import compile_trivial
    compile_trivial(MemorySpec("warm", dims=(8,), word_bits=16, ports=1))

    prog = problems.build("sobel")
    memname = list(prog.memories)[0]
    with tempfile.TemporaryDirectory() as d:
        svc = PlanService(store=DirectoryStore(d), workers=2)
        t0 = time.perf_counter()
        ticket = svc.submit(prog, memname)
        submit_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        fb = ticket.fallback()
        fallback_us = (time.perf_counter() - t0) * 1e6
        ticket.result(timeout=120)
        t0 = time.perf_counter()
        ticket.artifact()
        solved_swap_us = (time.perf_counter() - t0) * 1e6
        time_to_solved_s = time.time() - ticket.submitted_at
        # a second service over the same store: the cross-process warm hit
        warm_svc = PlanService(store=DirectoryStore(d), workers=2)
        t0 = time.perf_counter()
        warm_ticket = warm_svc.submit(prog, memname)
        warm_us = (time.perf_counter() - t0) * 1e6
        assert warm_ticket.done(), "warm store must answer inside submit"
        out = {
            "submit_us": submit_us,
            "fallback_artifact_us": fallback_us,
            "fallback_banks": fb.n_banks,
            "solved_swap_us": solved_swap_us,
            "time_to_solved_s": time_to_solved_s,
            "warm_store_hit_us": warm_us,
            "warm_ticket_done": warm_ticket.done(),
        }
    with open("results/BENCH_plan_service.json", "w") as f:
        json.dump(out, f, indent=1)
    print("\n=== Plan service (submit / fallback / solved swap / warm) ===")
    print(f"plan_service,{submit_us:.0f},"
          f"fallback={fallback_us:.0f}us;"
          f"solved_swap={solved_swap_us:.0f}us;"
          f"time_to_solved={time_to_solved_s*1e3:.0f}ms;"
          f"warm_hit={warm_us:.0f}us")


def bench_solver_shards(fast: bool = False) -> None:
    """Sharded candidate-space solve: 1/2/4-shard cold-solve wall-clock
    plus time-to-first-best, per benchmark problem.

    1-shard runs the in-thread pipeline (work-equivalent to the old
    monolithic search); multi-shard fans contiguous work units across a
    process pool with the reducer's section cuts pruning dispatch
    (``core.candidates.evaluate_parallel``).  Every configuration must
    agree on the chosen scheme -- the shard-equivalence property.
    Writes results/BENCH_solver_shards.json.
    """
    from repro.core import problems, unroll, build_groups
    from repro.core.candidates import CandidateSpace, evaluate_parallel
    from repro.core.planner import rank_solutions
    from repro.core.solver import SolverOptions

    apps = ["sobel"] if fast else ["sobel", "sw", "spmv"]
    shard_counts = (1, 2) if fast else (1, 2, 4)
    out = {}
    print("\n=== Sharded solver (cold solve, k shards) ===")
    for app in apps:
        prog = problems.build(app)
        memname = list(prog.memories)[0]
        up = unroll(prog)
        groups = build_groups(up, memname)
        mem = prog.memories[memname]
        rows = {}
        winners = set()
        for k in shard_counts:
            space = CandidateSpace(mem, groups, up.iterators,
                                   SolverOptions())
            t0 = time.perf_counter()
            red = evaluate_parallel(space, k)
            sols = red.finalize()
            wall_s = time.perf_counter() - t0
            best = rank_solutions(list(sols))[0]
            winners.add((best.kind, str(best.geometry), best.duplicates))
            rows[str(k)] = {
                "wall_s": wall_s,
                "time_to_first_best_s": red.first_best_seconds,
                "candidates_evaluated": red.evaluated,
                "space_size": len(space),
                "solutions": len(sols),
            }
            print(f"solver_shards_{app}_k{k},{wall_s*1e6:.0f},"
                  f"ttfb={red.first_best_seconds*1e6:.0f}us;"
                  f"evaluated={red.evaluated}/{len(space)}")
        assert len(winners) == 1, f"shard-equivalence broken for {app}"
        rows["same_winner_all_k"] = True
        rows["winner"] = next(iter(winners))[1]
        out[app] = rows
    with open("results/BENCH_solver_shards.json", "w") as f:
        json.dump(out, f, indent=1)


def bench_solve_fabric(fast: bool = False) -> None:
    """Distributed solve fabric: cold-solve wall-clock + time-to-first-
    best for 1/2/4 remote worker subprocesses vs the in-process fork
    pool, same problem, same-winner assert (shard equivalence over the
    wire).  Writes results/BENCH_solve_fabric.json.
    """
    from repro.core import (CandidateSpace, SolutionReducer, SolveFabric,
                            build_groups, problems, spawn_local_workers,
                            unroll)
    from repro.core.candidates import evaluate_parallel
    from repro.core.planner import rank_solutions
    from repro.core.solver import SolverOptions

    apps = ["sobel"] if fast else ["sobel", "sw"]
    counts = (1, 2) if fast else (1, 2, 4)
    out = {}
    print("\n=== Solve fabric (remote workers vs in-process pool) ===")
    for app in apps:
        prog = problems.build(app)
        memname = list(prog.memories)[0]
        up = unroll(prog)
        groups = build_groups(up, memname)
        mem = prog.memories[memname]
        rows = {}
        winners = set()

        def record(name, red, wall_s, extra=None):
            sols = red.finalize()
            best = rank_solutions(list(sols))[0]
            winners.add((best.kind, str(best.geometry), best.duplicates))
            rows[name] = dict(
                wall_s=wall_s,
                time_to_first_best_s=red.first_best_seconds,
                solutions=len(sols), **(extra or {}))
            ttfb = (red.first_best_seconds or 0.0) * 1e6
            print(f"solve_fabric_{app}_{name},{wall_s*1e6:.0f},"
                  f"ttfb={ttfb:.0f}us")

        # in-process pool baseline (the PR-4 scaling primitive)
        space = CandidateSpace(mem, groups, up.iterators, SolverOptions())
        t0 = time.perf_counter()
        red = evaluate_parallel(space, 2)
        record("pool_k2", red, time.perf_counter() - t0)

        for w in counts:
            fabric = SolveFabric(chunk=24)
            procs = spawn_local_workers(fabric.address, w)
            try:
                assert fabric.wait_for_workers(w, timeout=60)
                space = CandidateSpace(mem, groups, up.iterators,
                                       SolverOptions())
                red = SolutionReducer(space)
                t0 = time.perf_counter()
                report = fabric.solve(space, reducer=red)
                record(f"fabric_w{w}", red, time.perf_counter() - t0,
                       extra=dict(leases=report.leases,
                                  evaluated=report.evaluated,
                                  cut_broadcasts=report.cut_broadcasts))
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    p.wait()
                fabric.shutdown()
        assert len(winners) == 1, f"fabric equivalence broken for {app}"
        rows["same_winner_all_configs"] = True
        rows["winner"] = next(iter(winners))[1]
        out[app] = rows
    # worker counts beyond the host's cores oversubscribe CPU-bound
    # evaluators (the real win needs N hosts); record the context
    import os as _os
    out["host_cpus"] = _os.cpu_count()
    with open("results/BENCH_solve_fabric.json", "w") as f:
        json.dump(out, f, indent=1)


def bench_feedback_scorer(fast: bool = False) -> None:
    """Measured-cost feedback loop: cold ml/static ranking vs the rank
    after measurements contradict it, wall-clock from first observation
    to the demotion re-solve, and the per-call gather cost with timing
    hooks off (must be ~ the raw call) vs on.
    Writes results/BENCH_feedback_scorer.json.
    """
    import numpy as np

    from repro.core import (AccessDecl, Counter, Ctrl, FlatGeometry,
                            MemorySpec, MemoryStore, PlanService, Program,
                            Sched, compile_geometry)
    from repro.core.polytope import Affine
    from repro.core.solver import SolverOptions
    from repro.core.telemetry import (MeasuredScorer, TelemetryConfig,
                                      TelemetryLog, roofline_prior_seconds,
                                      scheme_hash)

    mem = MemorySpec("table", dims=(256,), word_bits=32, ports=1)
    prog = Program(
        root=Ctrl("reader", Sched.INNER,
                  counters=[Counter("i", 0, 1, 32, par=8)],
                  accesses=[AccessDecl("table", (Affine.of(i=1),))]),
        memories={"table": mem},
    )
    out = {}
    print("\n=== Feedback scorer (measure -> re-rank -> demote) ===")

    # -- cold rank vs measured-refreshed rank ---------------------------
    svc = PlanService(store=MemoryStore(), workers=1)
    hub = svc.enable_telemetry(TelemetryConfig(min_observations=4,
                                               flush_every=0))
    plan = svc.submit(prog, "table",
                      opts=SolverOptions(n_budget=8)).result(timeout=120)
    sols = plan.solutions[:2]
    assert len(sols) == 2, "need two candidate schemes"
    log = TelemetryLog()
    static = {scheme_hash(sols[0]): 1.0, scheme_hash(sols[1]): 2.0}
    scorer = MeasuredScorer(log=log,
                            static=lambda s: static[scheme_hash(s)])
    cold = sorted(sols, key=scorer)
    for _ in range(8):   # hardware says the cold winner is 10x slower
        log.observe(plan.signature, scheme_hash(sols[0]), "numpy",
                    "gather", (8,), 1e-3,
                    prior=roofline_prior_seconds(sols[0]))
        log.observe(plan.signature, scheme_hash(sols[1]), "numpy",
                    "gather", (8,), 1e-4,
                    prior=roofline_prior_seconds(sols[1]))
    measured = sorted(sols, key=scorer)
    out["cold_rank"] = [scheme_hash(s) for s in cold]
    out["measured_rank"] = [scheme_hash(s) for s in measured]
    out["rank_flipped"] = cold[0] is not measured[0]

    # -- demotion latency: first observation -> speculative re-solve ----
    art = svc.planner.compile(plan, backend="numpy")
    hub.log.observe(plan.signature, "rival-scheme", "numpy", "gather",
                    (8,), 1e-5)
    t0 = time.perf_counter()
    while svc.stats.demotions == 0:
        hub.observe(art, "gather", (8,), 1e-3)
    demote_us = (time.perf_counter() - t0) * 1e6
    svc.drain(timeout=120)
    resolve_s = time.perf_counter() - t0
    out["demotion_latency_us"] = demote_us
    out["demotion_resolve_s"] = resolve_s
    out["observations_to_demote"] = svc.stats.observations

    # -- per-call gather: hooks off must cost ~ the raw inner call ------
    geo = FlatGeometry(N=4, B=16, alpha=(1,), P=(16,))
    bare = compile_geometry(mem, geo, backend="numpy")
    table = np.arange(256 * 2, dtype=np.int32).reshape(256, 2)
    packed = np.asarray(bare.pack(table))
    rows = np.arange(8)
    iters = 50 if fast else 300
    _, raw_us = _bench_callable(lambda: bare._gather(packed, rows),
                                iters=iters, warmup=5)
    _, off_us = _bench_callable(lambda: bare.gather(packed, rows),
                                iters=iters, warmup=5)

    class _Sink:
        def observe(self, *a):
            pass

    bare.enable_telemetry(_Sink())
    _, on_us = _bench_callable(lambda: bare.gather(packed, rows),
                               iters=iters, warmup=5)
    bare.disable_telemetry()
    out["gather_raw_us"] = raw_us
    out["gather_hooks_off_us"] = off_us
    out["gather_hooks_on_us"] = on_us
    out["hooks_off_overhead_us"] = off_us - raw_us

    with open("results/BENCH_feedback_scorer.json", "w") as f:
        json.dump(out, f, indent=1)
    print(f"feedback_scorer,{demote_us:.0f},"
          f"rank_flipped={out['rank_flipped']};"
          f"resolve={resolve_s*1e3:.0f}ms;"
          f"hooks_off_overhead={off_us - raw_us:.2f}us;"
          f"hooks_on={on_us:.1f}us")


def bench_certify(fast: bool = False) -> None:
    """Independent conflict-freedom certification over the Sec-4 suite:
    per-plan certify latency (solver output re-decided pair-by-pair via
    the lattice/residue path) plus the certificate re-check latency, and
    the negative control -- a deliberately corrupted scheme MUST come
    back with a concrete two-point counterexample, never a pass.
    Writes results/BENCH_certify.json.
    """
    import dataclasses

    from repro.analysis.certify import certify_plan, certify_solution, \
        check_certificate
    from repro.core import problems, unroll
    from repro.core.planner import BankingPlanner

    apps = (["denoise", "sobel", "sgd"] if fast
            else list(problems.STENCILS) + ["sw", "spmv", "sgd"])
    planner = BankingPlanner()
    out = {}
    print("\n=== Certifier (independent conflict-freedom re-decision) ===")
    for app in apps:
        prog = problems.build(app)
        memname = list(prog.memories)[0]
        plan = planner.plan(prog, memname, use_cache=False)
        iters = unroll(prog).iterators
        t0 = time.perf_counter()
        res = certify_plan(plan, iters)
        certify_us = (time.perf_counter() - t0) * 1e6
        assert res.ok, f"{app}: solver/certifier disagreement: {res.reason}"
        t0 = time.perf_counter()
        ok, why = check_certificate(res.certificate)
        recheck_us = (time.perf_counter() - t0) * 1e6
        assert ok, f"{app}: certificate failed re-check: {why}"
        out[app] = {
            "certify_us": certify_us,
            "recheck_us": recheck_us,
            "pairs_checked": res.pairs_checked,
            "scheme": plan.best.describe(),
        }
        print(f"certify_{app},{certify_us:.0f},"
              f"pairs={res.pairs_checked};recheck={recheck_us:.0f}us")

    # negative control: forge sobel's winner down to one bank -- every
    # access now collides, and the certifier must SAY so concretely
    prog = problems.build("sobel")
    memname = list(prog.memories)[0]
    plan = planner.plan(prog, memname, use_cache=False)
    iters = unroll(prog).iterators
    forged = dataclasses.replace(
        plan.best, geometry=dataclasses.replace(plan.best.geometry,
                                                N=1, B=1))
    t0 = time.perf_counter()
    res = certify_solution(forged, plan.groups, iters)
    detect_us = (time.perf_counter() - t0) * 1e6
    assert not res.ok and res.counterexample is not None, \
        "corrupted scheme certified as conflict-free!"
    out["corrupted_control"] = {
        "detect_us": detect_us,
        "counterexample": res.counterexample.describe(),
    }
    print(f"certify_corrupted_control,{detect_us:.0f},detected=True")
    with open("results/BENCH_certify.json", "w") as f:
        json.dump(out, f, indent=1)


def bench_multi_tenant(fast: bool = False) -> None:
    """Multi-tenant QoS under solver saturation: three tenants (one
    deliberately noisy batch flooder) share ONE PlanService; per-tenant
    p50/p95 ticket latency is measured with QoS classes on vs off (off =
    every submit untagged: one band, plain FIFO).  The interactive
    tenant's p95 must stay bounded with QoS on, over-quota submits must
    defer -- never silently drop -- and the per-tenant stats slices must
    reconcile exactly with the global counters.
    Writes results/BENCH_multi_tenant.json.
    """
    from repro.core import (AccessDecl, Counter, Ctrl, MemorySpec,
                            PlanService, Program, Sched)
    from repro.core.polytope import Affine
    from repro.runtime.tenancy import TenantRegistry

    def program(tag: str, i: int):
        name = f"{tag}{i}"
        mem = MemorySpec(name, dims=(4096,), word_bits=32, ports=1)
        return Program(
            root=Ctrl("reader", Sched.INNER,
                      counters=[Counter("i", 0, 1, 24 + i, par=8)],
                      accesses=[AccessDecl(name, (Affine.of(i=1),))]),
            memories={name: mem},
        ), name

    n_batch, n_best, n_inter = (8, 3, 4) if fast else (16, 4, 6)

    def scenario(qos: bool) -> dict:
        registry = None
        if qos:
            registry = TenantRegistry()
            registry.register("interactive", "interactive")
            registry.register("batch", "batch")
            registry.register("best_effort", "best_effort")
        svc = PlanService(workers=2, tenants=registry)
        tickets = []
        # the flood lands FIRST: by the time interactive submits, the
        # queue is saturated with batch/best_effort work
        for i in range(n_batch):
            tickets.append(("batch", svc.submit(
                *program("b", i), use_cache=False,
                tenant="batch" if qos else None)))
        for i in range(n_best):
            tickets.append(("best_effort", svc.submit(
                *program("e", i), use_cache=False,
                tenant="best_effort" if qos else None)))
        for i in range(n_inter):
            tickets.append(("interactive", svc.submit(
                *program("q", i), use_cache=False,
                tenant="interactive" if qos else None)))
        for _, t in tickets:
            assert t.wait(timeout=300), "ticket never resolved"
        svc.drain(timeout=300)
        per = {}
        for tenant, t in tickets:
            if t.status == "shed":
                per.setdefault(tenant, []).append(None)
                continue
            per.setdefault(tenant, []).append(
                t.resolved_at - t.submitted_at)
        row = {}
        for tenant, lats in per.items():
            shed = sum(1 for x in lats if x is None)
            lats = sorted(x for x in lats if x is not None)
            row[tenant] = {
                "n": len(lats),
                "shed": shed,
                "p50_s": round(lats[len(lats) // 2], 4),
                "p95_s": round(lats[min(len(lats) - 1,
                                        int(len(lats) * 0.95))], 4),
            }
        row["deferred"] = svc.stats.deferred
        row["shed"] = svc.stats.shed
        # exact reconciliation: every global counter == sum of slices
        g = svc.stats.as_dict()
        slices = g.pop("tenants", {})
        mismatch = [k for k, v in g.items()
                    if v != sum(s.get(k, 0) for s in slices.values())]
        assert not mismatch, f"stats slices drifted: {mismatch}"
        svc.shutdown()
        return row

    print("\n=== Multi-tenant QoS (saturated solver, on vs off) ===")
    on = scenario(qos=True)
    off = scenario(qos=False)
    gap = (off["interactive"]["p95_s"]
           / max(on["interactive"]["p95_s"], 1e-9))
    out = {
        "qos_on": on, "qos_off": off,
        "interactive_p95_gap": round(gap, 2),
        "flood": {"batch": n_batch, "best_effort": n_best,
                  "interactive": n_inter},
    }
    # the headline property: QoS keeps the interactive tenant's p95 at
    # or under the unprioritized run's (equal is possible on an idle
    # host -- the flood may drain before interactive even queues)
    assert (on["interactive"]["p95_s"]
            <= off["interactive"]["p95_s"] * 1.5 + 0.05), \
        f"QoS made interactive latency WORSE: {out}"
    for name, row in (("on", on), ("off", off)):
        for tenant in ("interactive", "batch", "best_effort"):
            print(f"multi_tenant_{tenant}_qos_{name},"
                  f"{row[tenant]['p95_s']*1e6:.0f},"
                  f"p50={row[tenant]['p50_s']*1e3:.0f}ms;"
                  f"shed={row[tenant]['shed']}")
    print(f"multi_tenant_gap,0,interactive_p95_off/on={gap:.2f}x;"
          f"deferred_on={on['deferred']};shed_on={on['shed']}")
    with open("results/BENCH_multi_tenant.json", "w") as f:
        json.dump(out, f, indent=1)


def bench_joint_plan(fast: bool = False) -> None:
    """Whole-model joint planning under a shared resource budget, on two
    real model configs (dense qwen2_7b: one KV pool; MoE olmoe_1b_7b: KV
    pool + expert dispatch table).  The independent baseline lets every
    memory take its own argmin; the joint run co-selects under a BRAM
    budget set to 60% of the baseline's draw -- the argmins can NOT fit,
    the joint selection must.  Every non-trivial selected scheme must
    come back certified conflict-free (verify="store" is armed), and a
    slack-budget joint run must reproduce the baseline exactly.
    Writes results/BENCH_joint_plan.json.
    """
    del fast
    from repro.core import PlanService, ResourceBudget, SolverOptions
    from repro.core.jointplan import independent_use
    from repro.configs import get_arch
    from repro.runtime.server import model_memory_program

    out = {}
    print("\n=== Joint whole-model planning (budget vs independent) ===")
    for arch in ("qwen2-7b", "olmoe-1b-7b"):
        cfg = get_arch(arch).reduced()
        program = model_memory_program(cfg, max_len=64, page=16, readers=4)
        opts = SolverOptions(b_candidates=(16, 1), allow_multidim=False)
        svc = PlanService(workers=2, verify="store")
        # independent baseline: every memory argmins on its own
        t0 = time.perf_counter()
        plans = svc.planner.plan_all(program, opts=opts)
        indep_s = time.perf_counter() - t0
        indep = independent_use(plans)
        # slack-budget joint == independent, exactly
        slack = svc.submit_joint(program, opts=opts).result(timeout=300)
        assert slack.total_use.as_tuple() == indep.as_tuple(), \
            f"{arch}: slack joint drifted from independent planning"
        # 60% of the baseline BRAM: argmins cannot fit, joint must
        cap = ResourceBudget(bram=max(2, int(indep.bram * 0.6)))
        assert not cap.admits(indep), \
            f"{arch}: baseline unexpectedly fits the cap"
        t0 = time.perf_counter()
        ticket = svc.submit_joint(program, budget=cap, opts=opts,
                                  use_cache=False)
        jplan = ticket.result(timeout=300)
        joint_s = time.perf_counter() - t0
        assert jplan.feasible and jplan.fits(), \
            f"{arch}: joint selection failed to fit the budget"
        for name, m in jplan.members.items():
            assert m.trivial or m.certified, \
                f"{arch}:{name} selected scheme is uncertified"
        traded = sorted(
            name for name, m in jplan.members.items()
            if m.chosen.describe() != plans[name].best.describe())
        joint = jplan.total_use
        out[arch] = {
            "memories": sorted(jplan.members),
            "independent": indep.as_dict(),
            "budget": cap.as_dict(),
            "joint": joint.as_dict(),
            "independent_fits": cap.admits(indep),
            "joint_fits": jplan.fits(),
            "traded_down": traded,
            "members": jplan.as_dict()["members"],
            "independent_s": round(indep_s, 4),
            "joint_s": round(joint_s, 4),
            "stats": {k: getattr(svc.stats, k) for k in
                      ("joint_submits", "joint_solved", "joint_reselects",
                       "joint_infeasible", "joint_cert_evictions",
                       "certified")},
        }
        print(f"joint_plan_{arch.replace('-', '_')},{joint_s*1e6:.0f},"
              f"bram={indep.bram}->{joint.bram}(cap {cap.bram});"
              f"traded={'+'.join(traded) or 'none'};"
              f"certified={svc.stats.certified}")
        svc.shutdown()
    # headline: on every config the budgeted joint plan fits where the
    # independent argmins do not
    assert all(r["joint_fits"] and not r["independent_fits"]
               for r in out.values())
    with open("results/BENCH_joint_plan.json", "w") as f:
        json.dump(out, f, indent=1)


def bench_trace_overhead(fast: bool = False) -> None:
    """Observability-plane cost + the first measured w1 fabric-vs-pool
    breakdown.

    Part 1 proves tracing is effectively free: a disabled hook is one
    attribute load + None check (microbenchmarked per site, then scaled
    by the hook sites a cold solve crosses -- "hooks-off ~ raw"), and
    the hooks-ON cold-solve median stays within 3% of hooks-off.
    Part 2 answers the ROADMAP's standing question ("w1 fabric slower
    than pool: dispatch overhead is the next bottleneck") from the
    stitched trace itself: the lease wall time splits into worker eval,
    worker->driver result wire time, and dispatch gap (serialize +
    lease round-trips + driver-side frame handling).

    Writes results/BENCH_trace_overhead.json.
    """
    import statistics

    from repro.core import (PlanService, SolveFabric, problems,
                            spawn_local_workers)

    reps = 3 if fast else 7
    prog = problems.build("sobel")
    memname = list(prog.memories)[0]
    print("\n=== Trace overhead (hooks off/on) + w1 attribution ===")

    def cold_solve_ms(svc):
        t0 = time.perf_counter()
        assert svc.submit(prog, memname,
                          use_cache=False).result(timeout=120) is not None
        return (time.perf_counter() - t0) * 1e3

    def series(svc):
        cold_solve_ms(svc)                                 # warmup
        return [cold_solve_ms(svc) for _ in range(reps)]

    # -- part 1: hooks off vs on, same service path -------------------
    svc_off = PlanService(workers=2)
    off = series(svc_off)
    # the disabled hook, microbenchmarked: ONE attribute load + None
    # check (exactly what every instrumentation site compiles to when
    # enable_tracing was never called)
    n_iters = 200_000
    t0 = time.perf_counter()
    for _ in range(n_iters):
        if svc_off.tracer is not None:
            pass
    hook_ns = (time.perf_counter() - t0) / n_iters * 1e9
    svc_off.shutdown()

    svc_on = PlanService(workers=2)
    svc_on.enable_tracing()
    on = series(svc_on)
    trace = svc_on.recorder.traces()[-1]
    svc_on.shutdown()

    off_ms = statistics.median(off)
    on_ms = statistics.median(on)
    # ~40 guarded sites fire per cold solve; scaling the measured
    # per-site cost gives the hooks-off overhead vs raw (pre-tracing)
    hooks_off_pct = hook_ns * 40 / (off_ms * 1e6) * 100
    hooks_on_pct = (on_ms - off_ms) / off_ms * 100
    print(f"trace_overhead_hooks_off,{off_ms*1e3:.0f},"
          f"hook={hook_ns:.0f}ns;overhead={hooks_off_pct:.4f}%")
    print(f"trace_overhead_hooks_on,{on_ms*1e3:.0f},"
          f"overhead={hooks_on_pct:+.2f}%")

    def _stage(name):
        return round(sum(s.duration_ms for s in trace.spans
                         if s.name == name), 3)

    out = {
        "cold_solve": {
            "reps": reps,
            "hooks_off_ms": [round(v, 3) for v in off],
            "hooks_on_ms": [round(v, 3) for v in on],
            "hooks_off_median_ms": round(off_ms, 3),
            "hooks_on_median_ms": round(on_ms, 3),
            "disabled_hook_ns": round(hook_ns, 1),
            "hooks_off_overhead_pct": round(hooks_off_pct, 5),
            "hooks_on_overhead_pct": round(hooks_on_pct, 3),
            "traced_stage_ms": {n: _stage(n) for n in
                                ("prepare", "queue-wait", "enumerate",
                                 "shard-eval", "reduce")},
        },
    }

    # -- part 2: w1 fabric vs pool, attributed stage by stage ---------
    svc = PlanService(workers=2)
    svc.enable_tracing()
    pool_ms = statistics.median(series(svc))
    pool_trace = svc.recorder.traces()[-1]
    svc.shutdown()

    fabric = SolveFabric(chunk=24)
    procs = spawn_local_workers(fabric.address, 1)
    try:
        assert fabric.wait_for_workers(1, timeout=60)
        svc = PlanService(executor="fabric", fabric=fabric)
        svc.enable_tracing()
        fab_ms = statistics.median(series(svc))
        fab_trace = svc.recorder.traces()[-1]
        svc.shutdown()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        fabric.shutdown()

    spans = fab_trace.spans
    lease_wall = sum(s.duration_ms for s in spans if s.name == "lease")
    worker_eval = sum(s.duration_ms for s in spans if s.name == "w-eval")
    worker_wire = sum(s.attrs.get("wire_ms", 0.0) for s in spans
                     if s.name == "w-lease")
    serialize = sum(s.duration_ms for s in spans if s.name == "serialize")
    fab_solve = sum(s.duration_ms for s in spans
                    if s.name == "fabric-solve")
    dispatch_gap = max(0.0, lease_wall - worker_eval - worker_wire)
    pool_eval = sum(s.duration_ms for s in pool_trace.spans
                    if s.name == "shard-eval")
    attribution = {
        "pool_total_ms": round(pool_ms, 3),
        "fabric_w1_total_ms": round(fab_ms, 3),
        "gap_ms": round(fab_ms - pool_ms, 3),
        "pool_shard_eval_ms": round(pool_eval, 3),
        "fabric_solve_ms": round(fab_solve, 3),
        "serialize_ms": round(serialize, 3),
        "lease_wall_ms": round(lease_wall, 3),
        "worker_eval_ms": round(worker_eval, 3),
        "worker_result_wire_ms": round(worker_wire, 3),
        "dispatch_gap_ms": round(dispatch_gap, 3),
        "leases": sum(1 for s in spans if s.name == "lease"),
    }
    out["w1_attribution"] = attribution
    print(f"trace_overhead_w1_vs_pool,{fab_ms*1e3:.0f},"
          f"pool={pool_ms:.1f}ms;serialize={serialize:.1f}ms;"
          f"eval={worker_eval:.1f}ms;wire={worker_wire:.1f}ms;"
          f"dispatch_gap={dispatch_gap:.1f}ms")

    # the acceptance gates: disabled hooks are noise, enabled < 3%
    assert hooks_off_pct < 0.5, hooks_off_pct
    assert hooks_on_pct < 3.0, hooks_on_pct
    with open("results/BENCH_trace_overhead.json", "w") as f:
        json.dump(out, f, indent=1)


BENCHES = {
    "joint_plan": bench_joint_plan,
    "trace_overhead": bench_trace_overhead,
    "multi_tenant": bench_multi_tenant,
    "solver": lambda fast: bench_solver(),
    "planner_cache": lambda fast: bench_planner_cache(),
    "compile_cache": lambda fast: bench_compile_cache(),
    "plan_service": lambda fast: bench_plan_service(),
    "solver_shards": bench_solver_shards,
    "solve_fabric": bench_solve_fabric,
    "feedback_scorer": bench_feedback_scorer,
    "certify": bench_certify,
    "kernels": lambda fast: bench_kernels(),
    "tables": bench_tables,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the cost-model CV (slowest part)")
    ap.add_argument("--only", choices=sorted(BENCHES), default=None,
                    help="run a single benchmark (CI smoke)")
    args = ap.parse_args()
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs("results", exist_ok=True)
    print("name,us_per_call,derived")
    if args.only is not None:
        BENCHES[args.only](args.fast)
        return
    bench_solver()
    bench_planner_cache()
    bench_compile_cache()
    bench_plan_service()
    bench_solver_shards(args.fast)
    bench_solve_fabric(args.fast)
    bench_multi_tenant(args.fast)
    bench_joint_plan(args.fast)
    bench_feedback_scorer(args.fast)
    bench_certify(args.fast)
    bench_trace_overhead(args.fast)
    bench_kernels()
    bench_tables(args.fast)


if __name__ == "__main__":
    main()
