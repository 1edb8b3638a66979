"""Shardable candidate-space pipeline: shard equivalence against the
monolithic search, reducer truncation/dedup/monotonicity, shard
self-containment (pickling), and the parallel drivers."""

import pickle
import threading

import pytest

from repro.core import (
    CandidateSpace,
    SolutionReducer,
    SolverOptions,
    build_groups,
    evaluate,
    evaluate_parallel,
    unroll
)
from repro.core import problems
from repro.core.candidates import EvaluatedCandidate
from repro.core.planner import rank_solutions
from repro.core.solver import solve, solve_monolithic


def _problem(app):
    prog = problems.build(app)
    memname = list(prog.memories)[0]
    up = unroll(prog)
    return (prog.memories[memname], build_groups(up, memname),
            up.iterators)


def _key(s):
    return (s.kind, s.geometry, s.duplicates)


def _dedup(keys):
    seen = set()
    return [k for k in keys if not (k in seen or seen.add(k))]


# ---------------------------------------------------------------------------
# Shard equivalence (the ISSUE acceptance property)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("app", ["sobel", "motion-lh", "sgd", "md_grid"])
def test_shard_equivalence_matrix(app):
    """Merging evaluate() over space.shards(k) for k in {1, 2, 4} yields
    the identical solution list -- and the identical ranked winner -- as
    the pre-redesign monolithic solve."""
    mem, groups, iters = _problem(app)
    mono = solve_monolithic(mem, groups, iters)
    mono_keys = _dedup([_key(s) for s in mono])
    mono_winner = _key(rank_solutions(list(mono))[0])
    for k in (1, 2, 4):
        space = CandidateSpace(mem, groups, iters, SolverOptions())
        red = SolutionReducer(space)
        for shard in space.shards(k):
            for ev in evaluate(shard, gate=red):
                red.add(ev)
        sols = red.finalize()
        assert [_key(s) for s in sols] == mono_keys, (app, k)
        assert _key(rank_solutions(list(sols))[0]) == mono_winner, (app, k)


def test_solve_is_the_single_shard_pipeline():
    mem, groups, iters = _problem("sobel")
    pipe = [_key(s) for s in solve(mem, groups, iters)]
    mono = _dedup([_key(s) for s in solve_monolithic(mem, groups, iters)])
    assert pipe == mono


def test_shard_equivalence_under_merged_thread_streams():
    """Interleaved arrival order (concurrent shard threads sharing one
    reducer + gate) must not change the final list."""
    mem, groups, iters = _problem("sobel")
    want = [_key(s) for s in solve(mem, groups, iters)]
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    red = SolutionReducer(space)

    def run(shard):
        for ev in evaluate(shard, gate=red):
            red.add(ev)

    threads = [threading.Thread(target=run, args=(sh,))
               for sh in space.shards(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert [_key(s) for s in red.finalize()] == want


# ---------------------------------------------------------------------------
# Enumeration / partitioning
# ---------------------------------------------------------------------------


def test_shards_partition_the_space_exactly():
    mem, groups, iters = _problem("sobel")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    for k in (1, 3, 8):
        for interleave in (True, False):
            shards = space.shards(k, interleave=interleave)
            idxs = sorted(c.index for sh in shards for c in sh.candidates)
            assert idxs == list(range(len(space)))


def test_sections_cover_candidates_and_encode_budgets():
    mem, groups, iters = _problem("sgd")      # has duplication sections
    opts = SolverOptions()
    space = CandidateSpace(mem, groups, iters, opts)
    assert [s.name for s in space.sections][:1] == ["flat"]
    assert any(s.name.startswith("dup x") for s in space.sections)
    covered = []
    for s in space.sections:
        assert s.cap > 0
        covered.extend(range(s.start, s.stop))
        if s.name.startswith("dup"):
            assert s.keep == 2 and s.D > 1
        else:
            assert s.cap == opts.max_solutions
    assert covered == list(range(len(space)))
    # candidates point back at their section
    for c in space.candidates:
        sec = space.sections[c.section]
        assert sec.start <= c.index < sec.stop


def test_local_stop_prunes_beyond_the_cut():
    """A single shard stops each section once its own emissions reach
    the cap -- far fewer evaluations than the whole space."""
    mem, groups, iters = _problem("sobel")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    shard = space.shards(1)[0]
    n_evaluated = sum(1 for _ in evaluate(shard))
    assert n_evaluated < len(space)


# ---------------------------------------------------------------------------
# Shard self-containment
# ---------------------------------------------------------------------------


def test_pickled_shard_evaluates_identically():
    """Shards are self-contained: a pickled shard (fresh conflict cache
    on the far side) yields byte-identical evaluation results."""
    mem, groups, iters = _problem("motion-lh")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    shard = space.shards(4)[1]
    local = [( e.index, [_key(s) for s in e.solutions], e.valid_mask)
             for e in evaluate(shard)]
    far = pickle.loads(pickle.dumps(shard))
    assert far.space is not shard.space
    remote = [(e.index, [_key(s) for s in e.solutions], e.valid_mask)
              for e in evaluate(far)]
    assert remote == local


def test_evaluate_parallel_matches_single_shard():
    """The process-pool driver (cut-filtered dispatch) returns the same
    ranked winner and solution list as the in-thread pipeline."""
    mem, groups, iters = _problem("sobel")
    want = [_key(s) for s in solve(mem, groups, iters)]
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    red = evaluate_parallel(space, 2)
    assert [_key(s) for s in red.finalize()] == want


def test_evaluate_parallel_stays_in_process_on_an_accelerator(monkeypatch):
    """A process that holds a chip never forks the pool: the solve runs
    in-process and still returns the monolithic answer."""
    import concurrent.futures

    from repro.core import candidates as cand_mod

    def no_fork(*a, **k):
        raise AssertionError("forked a pool while holding a chip")

    monkeypatch.setattr(cand_mod, "_holds_accelerator", lambda: True)
    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", no_fork)
    mem, groups, iters = _problem("sobel")
    want = [_key(s) for s in solve(mem, groups, iters)]
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    red = evaluate_parallel(space, 2)
    assert [_key(s) for s in red.finalize()] == want


def test_cpu_backend_does_not_count_as_holding_a_chip():
    import jax
    import jax.numpy as jnp

    from repro.core.candidates import _holds_accelerator

    jnp.zeros(1).block_until_ready()          # bring a backend up
    assert jax.default_backend() == "cpu"
    assert not _holds_accelerator()


# ---------------------------------------------------------------------------
# Reducer semantics
# ---------------------------------------------------------------------------


def test_reducer_best_never_regresses_and_matches_final():
    mem, groups, iters = _problem("sobel")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    red = SolutionReducer(space)
    scores = []
    for ev in evaluate(space.shards(1)[0], gate=red):
        red.add(ev)
        best = red.best()
        if best is not None:
            scores.append(best.score)
    assert scores, "search admitted no solutions"
    assert all(a >= b for a, b in zip(scores, scores[1:]))
    sols = red.finalize()
    assert red.best().score == min(s.score for s in sols) == scores[-1]
    assert red.version == red.promotions > 0
    assert red.first_best_seconds is not None


def test_reducer_out_of_order_arrival_equals_in_order():
    mem, groups, iters = _problem("motion-lh")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    evs = list(evaluate(space.shards(1)[0]))
    fwd = SolutionReducer(space)
    for e in evs:
        fwd.add(e)
    rev = SolutionReducer(space)
    for e in reversed(evs):
        rev.add(e)
    assert ([_key(s) for s in fwd.finalize()]
            == [_key(s) for s in rev.finalize()])


def test_reducer_dedupes_identical_schemes():
    """Identical geometries reaching the reducer twice are dropped
    before scoring; the duplicate still counts toward the section cap
    (monolithic accounting)."""
    mem, groups, iters = _problem("sobel")
    space = CandidateSpace(mem, groups, iters, SolverOptions())
    shard = space.shards(1)[0]
    it = evaluate(shard)
    first_valid = None
    for ev in it:
        if ev.solutions:
            first_valid = ev
            break
    assert first_valid is not None
    red = SolutionReducer(space)
    doubled = EvaluatedCandidate(
        index=first_valid.index,
        solutions=list(first_valid.solutions) * 2,
        valid_mask=first_valid.valid_mask * 2)
    red.add(doubled)
    admitted = red.finalize()
    assert red.dedup_hits == len(first_valid.solutions)
    assert len(admitted) == len(first_valid.solutions)
