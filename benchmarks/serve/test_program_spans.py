"""CPU tests of the per-layer metrics that read the program's own
serve-loop spans: a tiny dense server driven through the benchmark's
harness, its window ticks split into kernel builds, device waits and
host work."""

import time

import pytest

from servebench import harness, spec as specmod
from servebench.modelspec import spec_from_dict
from servebench.spans import tick_parts
from servebench.traffic import generate
from servebench.weights import make_weights

from test_servebench import TINY, TINY_MIX

SPLIT = ("tick_build_ms", "tick_wait_ms", "tick_host_ms")


@pytest.fixture(scope="module")
def served_run():
    from repro.runtime.server import Request

    spec = spec_from_dict(TINY)
    seed = 2**31 + 17
    run = harness.Run(spec, TINY_MIX, 2.0, t_start=time.perf_counter())
    params = make_weights(spec, seed)
    rec = harness.Recorder()
    server, ticket, service, t_submit = harness.build(spec, params)
    harness.serve(run, server, ticket, t_submit,
                  generate(TINY_MIX, seed, run.seconds, spec.vocab),
                  harness.tracked_class(Request), rec, None)
    harness.release(server, service, rec)
    return run


def test_the_split_adds_up_to_the_harness_tick(served_run):
    run = served_run
    read = {m: specmod.load_reader(m)(run) for m in SPLIT}
    assert all(v is not None and v >= 0 for v in read.values()), read
    whole = [t.end - t.start for t in run.ticks
             if run.t_open <= t.start and t.end <= run.t_close]
    assert whole
    mean_ms = 1e3 * sum(whole) / len(whole)
    assert sum(read.values()) == pytest.approx(mean_ms, rel=0.05), read


def test_the_first_tick_on_each_layout_builds_its_kernels(served_run):
    firsts = {}
    for t in served_run.ticks:
        firsts.setdefault(t.layout, t)
    assert firsts
    for layout, t in firsts.items():
        (parts,) = tick_parts(t.start, t.end)
        assert parts[1] > 0, layout


def test_readers_give_nothing_for_a_window_without_ticks(served_run):
    from types import SimpleNamespace

    now = time.perf_counter()
    empty = SimpleNamespace(t_open=now, t_close=now + 3600.0)
    for m in SPLIT:
        assert specmod.load_reader(m)(empty) is None
