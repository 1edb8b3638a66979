"""CPU tests of the serving benchmark: its reductions, its traffic, its
index of cells, and its correctness comparison against a control and
planted faults, at a size a test run can hold."""

import json
import re

import numpy as np
import pytest

from servebench import spec as specmod
from servebench import timeline
from servebench import trace as tr
from servebench.costs import active_params, step_cost
from servebench.modelspec import load_spec, spec_from_dict
from servebench.peaks import peaks_for, roofline_seconds
from servebench.traffic import generate, load_count, load_mix

# -- trace reduction ------------------------------------------------------------


def _ops():
    # two programs: a step (module jit_serve_step) and a kernel
    return [tr.DeviceOp("fusion.1", "jit_serve_step", 1.0, 1.2),
            tr.DeviceOp("fusion.2", "jit_serve_step", 1.2, 1.3),
            tr.DeviceOp("tpu_custom_call.1", "jit_wrapped", 2.0, 2.1),
            tr.DeviceOp("fusion.1", "jit_serve_step", 3.5, 3.6)]


def test_busy_is_the_union_of_device_intervals():
    assert tr.busy(_ops(), 0.0, 4.0) == pytest.approx(0.3 + 0.1 + 0.1)
    assert tr.busy(_ops(), 1.15, 3.55) == pytest.approx(0.15 + 0.1 + 0.05)


def test_idle_gaps_are_attributed_to_compiles_then_innermost_spans():
    gaps = tr.idle_gaps(_ops(), 0.5, 4.0)
    assert gaps == [pytest.approx(g) for g in
                    [(0.5, 1.0), (1.3, 2.0), (2.1, 3.5), (3.6, 4.0)]]
    spans = [tr.Span("bench.tick", 0.8, 3.0), tr.Span("bench.submit", 2.5,
                                                      2.6)]
    by = tr.attribute(gaps, spans, compiles=[(1.5, 1.8)])
    assert by[tr.COMPILE] == pytest.approx(0.3)
    assert by["bench.submit"] == pytest.approx(0.1)
    # tick: 0.8-1.0, 1.3-1.5, 1.8-2.0, 2.1-2.5, 2.6-3.0
    assert by["bench.tick"] == pytest.approx(0.2 + 0.2 + 0.2 + 0.4 + 0.4)
    assert by[tr.NO_SPAN] == pytest.approx(0.3 + 0.5 + 0.4)
    assert sum(by.values()) == pytest.approx(sum(b - a for a, b in gaps))


def test_device_time_by_program_and_by_kernel_name():
    ops = _ops()
    assert tr.device_time(ops, 0, 4, module_prefix="jit_serve_step") == \
        pytest.approx(0.4)
    assert tr.device_time(ops, 0, 4, name_prefix="tpu_custom_call",
                          exclude_module="jit_serve_step") == \
        pytest.approx(0.1)
    top = tr.top_ops(ops, 0, 4, 3)
    assert top[0] == ["jit_serve_step:fusion.1", pytest.approx(0.3)]


def test_self_time_leaves_out_nested_operations():
    loop = [tr.DeviceOp("while.2", "jit_serve_step", 0.0, 1.0),
            tr.DeviceOp("fusion.7", "jit_serve_step", 0.1, 0.4),
            tr.DeviceOp("fusion.8", "jit_serve_step", 0.5, 0.6),
            tr.DeviceOp("copy.1", "jit_serve_step", 1.0, 1.2)]
    self_t = tr.self_times(loop, 0.0, 2.0)
    assert self_t["jit_serve_step:while.2"] == pytest.approx(0.6)
    assert self_t["jit_serve_step:fusion.7"] == pytest.approx(0.3)
    assert self_t["jit_serve_step:copy.1"] == pytest.approx(0.2)
    assert tr.device_time(loop, 0.0, 2.0) == pytest.approx(1.2)


def test_clock_offset_matches_spans_by_name_and_order():
    spans = [tr.Span("bench.tick", 10.0, 11.0), tr.Span("bench.tick", 12.0,
                                                        13.0)]
    mine = [("bench.tick", 2.0), ("bench.tick", 4.0)]
    assert tr.clock_offset(mine, spans) == pytest.approx(8.0)
    assert tr.clock_offset([], spans) is None


# -- whole-tick rate, percentiles, TTFT -------------------------------------------


def _ticks(shift):
    # 40 ticks of 0.25 s, 8 tokens each, the timeline shifted by `shift`
    return [timeline.Tick(shift + 0.25 * i, shift + 0.25 * (i + 1), 1, 8, 0)
            for i in range(40)]


@pytest.mark.parametrize("shift", [0.0, 0.05, 0.125, 0.2, 0.249])
def test_whole_tick_rate_does_not_depend_on_where_the_edge_falls(shift):
    rate = timeline.whole_tick_rate(_ticks(shift), 0.1, 8.1)
    assert rate == pytest.approx(32.0)


def test_whole_tick_rate_leaves_out_cut_ticks():
    ticks = _ticks(0.0)
    ticks[10] = timeline.Tick(2.5, 2.75, 1, 800, 0)   # a burst tick inside
    assert timeline.whole_tick_rate(ticks, 0.0, 10.0) == pytest.approx(
        (39 * 8 + 800) / 10.0)
    assert timeline.whole_tick_rate(ticks, 2.6, 2.7) is None


def test_percentiles_are_over_all_samples():
    samples = list(range(1, 101))
    assert timeline.percentile(samples, 50) == pytest.approx(50.5)
    assert timeline.percentile(samples, 95) == pytest.approx(95.05)
    assert timeline.beyond(samples, 95.05) == 5
    assert timeline.percentile([], 50) is None


def test_ttft_is_from_due_time_to_the_end_of_the_first_token_tick():
    ticks = _ticks(0.0)
    reqs = [timeline.ReqTimes(0, due=1.0, token_ticks=[7, 8]),
            timeline.ReqTimes(1, due=2.0, token_ticks=[]),
            timeline.ReqTimes(2, due=None, token_ticks=[1]),
            timeline.ReqTimes(3, due=20.0, token_ticks=[])]
    firsts = timeline.ttft(reqs, ticks, 0.0, 10.0)
    assert firsts[0] == pytest.approx(2.0 - 1.0)
    assert firsts[1] == float("inf")
    assert len(firsts) == 2


def test_itl_gaps_and_stalls():
    ticks = _ticks(0.0)
    ticks[5] = timeline.Tick(1.25, 1.5, 30, 1, 29)        # admitted a prompt
    reqs = [timeline.ReqTimes(0, None, token_ticks=[3, 4, 5, 6])]
    gaps, stalled = timeline.itl(reqs, ticks, 0.0, 10.0)
    assert gaps == [pytest.approx(0.25)] * 3
    assert stalled == [False, True, False]


# -- traffic ----------------------------------------------------------------------

MIXES = sorted(p.stem for p in (specmod.HERE / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_two_seeds_offer_the_same_work_in_another_order(mix):
    m = load_mix(specmod.mix_path(mix))
    a = generate(m, 7, 30.0, 50_000)
    b = generate(m, 2**31 + 11, 30.0, 50_000)
    assert len(a) == len(b) == int(m["warm"]["requests"]) + load_count(m, 30)
    for group in ("warm", "load"):
        ga = [r for r in a if r.group == group]
        gb = [r for r in b if r.group == group]
        assert sorted(len(r.prompt) for r in ga) == \
            sorted(len(r.prompt) for r in gb)
        assert sorted(r.max_new for r in ga) == sorted(r.max_new for r in gb)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    if any(len(set(len(r.prompt) for r in a if r.group == g)) > 1
           for g in ("warm", "load")):
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    again = generate(m, 7, 30.0, 50_000)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in again]
    for r in a:
        assert 0.0 <= r.due_s <= 30.0
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50_000


# -- the index of cells ----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_resolves_to_its_files_and_names_are_plain():
    bench = specmod.load_benchmark()
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + [c["name"] for c in
                                           bench["configs"]] + \
        [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
        spec = load_spec(specmod.ROOT / c["file"])
        assert spec.name == c["name"]
        assert set(c["reduced"]) == set(json.loads(
            (specmod.ROOT / c["file"]).read_text())["reduced"])
    moves = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"])
        cell = specmod.resolve(bench, w["name"])
        assert cell.config_file.is_file() and cell.mix_file.is_file()
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert specmod.reader_path(m["name"]).is_file()
            assert callable(specmod.load_reader(m["name"]))
            assert m["moves"] in moves and m["moves"] in reported, m
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


# -- operation and byte counts ------------------------------------------------


# an MoE at OLMoE-1B-7B's widths, 8 of its 16 layers
MOE_WIDE = {"name": "moe-wide", "hidden_size": 2048, "intermediate_size": 1024,
            "num_attention_heads": 16, "num_key_value_heads": 16,
            "num_hidden_layers": 8, "num_experts": 64,
            "num_experts_per_tok": 8, "norm_topk_prob": True,
            "vocab_size": 50304, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
            "qkv_bias": False,
            "serve": {"slots": 8, "max_len": 4096, "page": 128},
            "reference": "benchmarks/serve/servebench/reference.py"}


# a leaf the program does not have, added by a reference copy's `leaves`
EXTRA_LEAF = """
from .weights import leaves as _base_leaves


def leaves(s):
    tree = _base_leaves(s)
    tree["layers"]["q_norm"] = ((s.layers, s.d_model), "bfloat16", 0.1)
    return tree
"""

# a reference copy that notes each comparison it makes and defines the
# benchmark's own tree
NOTED = """
from .weights import leaves as _base_leaves

NOTED = []
_compare = compare


def compare(*args, **kw):
    NOTED.append(len(args[-1]))
    return _compare(*args, **kw)


def leaves(s):
    return _base_leaves(s)
"""


def _reference_copy(tmp_path, name, extra):
    """A copy of the benchmark's reference outside its package, with
    ``extra`` appended."""
    path = tmp_path / f"{name}_reference.py"
    path.write_text((specmod.PACKAGE / "reference.py").read_text() + extra)
    return path


def test_counts_grow_with_context_and_stay_under_the_weights_for_moe():
    bench = specmod.load_benchmark()
    specs = {c["name"]: load_spec(specmod.ROOT / c["file"])
             for c in bench["configs"]}
    dense = specs["qwen2-7b-14L"]
    f1, b1 = step_cost(dense, [100] * 8)
    f2, b2 = step_cost(dense, [1000] * 8)
    assert f2 > f1 and b2 > b1
    assert active_params(dense) == pytest.approx(3.81e9, rel=0.02)
    moe = spec_from_dict(MOE_WIDE)
    few = step_cost(moe, [10] * 8, [8] * moe.layers)[1]
    all_ = step_cost(moe, [10] * 8, [64] * moe.layers)[1]
    assert few < all_ < 7.2e9
    with pytest.raises(ValueError):
        step_cost(moe, [10], None)
    v5e = peaks_for("TPU v5 lite")
    assert roofline_seconds(0, 819e9, v5e) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 giant")


def test_counts_follow_the_leaves_and_keep_the_formulas_numbers():
    # the counts of the formulas that counted each kind of layer by hand,
    # before the counts followed the leaf tree
    dense = load_spec(specmod.ROOT / "benchmarks/serve/configs/"
                      "qwen2-7b-14L.json")
    assert active_params(dense) == 3_807_810_048
    assert step_cost(dense, [100] * 8) == (61_085_523_968, 7_638_615_040)
    moe = spec_from_dict(MOE_WIDE)
    assert active_params(moe) == 640_976_896
    assert step_cost(moe, [100] * 8, [42] * 8) == (10_308_059_136,
                                                   4_759_064_576)


def test_a_leaf_that_a_reference_adds_is_counted(tmp_path):
    moe = spec_from_dict(MOE_WIDE)
    more = spec_from_dict(dict(MOE_WIDE, reference=str(_reference_copy(
        tmp_path, "qk", EXTRA_LEAF))))
    L, D = moe.layers, moe.d_model
    assert active_params(more) - active_params(moe) == L * D
    f0, b0 = step_cost(moe, [100] * 8, [42] * 8)
    f1, b1 = step_cost(more, [100] * 8, [42] * 8)
    assert (f1 - f0, b1 - b0) == (2 * L * D * 8, 2 * L * D)


# -- the comparison: control and planted faults, at a test size -------------------

TINY = {"name": "tiny", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "qkv_bias": True,
        "serve": {"slots": 4, "max_len": 512, "page": 16},
        "reference": "benchmarks/serve/servebench/reference.py",
        # limits for this size, between the program's readings on CPU
        # (widest gap about 0.01, mean about 1e-4) and the float8
        # control's (widest 0.17, mean 3.5e-3)
        "limits": {"logit_gap": 0.05, "logit_gap_mean": 0.001}}
TINY_MIX = {
    "warm": {"requests": 3, "prompt": {"dist": "uniform", "min": 4, "max": 9},
             "output": {"dist": "uniform", "min": 30, "max": 40},
             "until": "admitted", "ticks": 1},
    "load": {"arrival": "even", "rate_per_s": 3.0, "jitter": 0.5,
             "prompt": {"dist": "uniform", "min": 3, "max": 7},
             "output": {"dist": "uniform", "min": 4, "max": 8}},
    "drain_s": 0}


def _run(config, fault=None, control=False, seconds=1.0, seed=2**31 + 5):
    import time

    from servebench.runcell import run_cell

    bench = specmod.load_benchmark()
    cell = specmod.Cell("tiny", 1, None, None, bench["end_to_end"],
                        bench["per_layer"])
    return run_cell(cell, spec_from_dict(config), TINY_MIX, seed, seconds,
                    trace=False, control=control,
                    peaks=peaks_for("TPU v5 lite"),
                    t_start=time.perf_counter(),
                    device={"platform": "cpu", "kind": "cpu", "count": 1},
                    fault=fault)


def _moe():
    # An MoE configuration.  Its widest gap leaves out near-tied tokens:
    # route_tie is twice the largest change of a router probability that
    # rounding the router's input to bfloat16 makes (0.00217 over 28 CPU
    # runs of the 8-s window, 276 served tokens each).  Over those runs
    # the program's widest gap read at most 0.0286, the float8 control's
    # at least 0.176, and at most 9.4% of the tokens were near-tied (mean
    # 6.2%).  The mean gap is not compared here: one token on a flipped
    # route (margin 1e-5 to 0.0019) lifts the program's to 0.0044, and
    # the control's reads 0.0076 at least, under three times that.
    return dict(TINY, intermediate_size=32, qkv_bias=False, num_experts=4,
                num_experts_per_tok=2, norm_topk_prob=True,
                limits={"logit_gap": 0.08, "near_tie_share": 0.15,
                        "route_tie": 0.0044})


def _numbers(line):
    return {k: float(v) for k, v in re.findall(r"(\w+) ([0-9.e-]+)",
                                                line.split(": ", 1)[1])}


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
@pytest.mark.parametrize("config", [TINY, _moe()], ids=["dense", "moe"])
def test_program_is_correct_and_the_float8_control_is_not(config, control):
    result, info = _run(config, control=control, seconds=8.0)
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert set(config["limits"]) - {"route_tie"} <= set(checks)
    program = _numbers(next(x for x in info if x.startswith("program")))
    compared = {k: lim for k, lim in config["limits"].items()
                if k != "route_tie"}
    assert all(program[k] <= lim for k, lim in compared.items()), \
        (program, config["limits"])
    if control:
        # the control in the program's place fails a compared number
        assert result["correct"] is False, checks
        assert any(checks[k]["value"] > lim
                   for k, lim in compared.items()), checks
    else:
        assert result["correct"] is True, checks
    assert checks["positions"]["value"] <= config["serve"]["max_len"]
    assert set(result["metrics"]) >= {"itl_p50_ms", "setup_s"}


# gaps the reference gave TINY's seeded weights on the rows of
# `_fixed_rows`, before a configuration named its own reference
PARENT_GAP = [1.7149102687835693, 2.816540241241455, 2.3991665840148926,
              2.1973037719726562, 2.5881218910217285, 1.4710813760757446,
              3.1419312953948975, 3.253419876098633, 2.813732385635376,
              3.3828494548797607]
PARENT_CONTROL_GAP = [0.0, 0.0, 0.032691001892089844, 0.0, 0.0, 0.0, 0.0,
                      0.09197092056274414, 0.0, 0.0]


def _fixed_rows():
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 1024, (4, 40)).astype(np.int32)
    calls = np.array([0, 3, 9, 17, 25, 33, 39, 39, 12, 30])
    slots = np.array([0, 1, 2, 3, 0, 1, 2, 3, 3, 2])
    return rows, calls, slots, rng.integers(0, 1024, len(calls))


def test_a_dense_comparison_gives_the_gaps_it_gave_before():
    from servebench.spec import load_reference
    from servebench.weights import make_weights

    spec = spec_from_dict(TINY)
    out = load_reference(TINY).compare(
        spec, make_weights(spec, 2**31 + 5), *_fixed_rows(), control=True)
    np.testing.assert_allclose(out["gap"], PARENT_GAP, rtol=1e-6, atol=0)
    np.testing.assert_allclose(out["control_gap"], PARENT_CONTROL_GAP,
                               rtol=1e-6, atol=1e-7)
    assert out["margin"] is None and out["route_shift"] is None


def test_the_widest_gap_leaves_out_near_tied_tokens_only():
    from servebench.runcell import gap_numbers

    gaps = np.array([0.0, 0.5, 0.01, 0.0])
    assert gap_numbers(gaps) == {"logit_gap": 0.5, "logit_gap_mean": 0.1275,
                                 "argmax_miss_share": 0.5}
    margin = np.array([0.2, 0.001, 0.05, 0.3])
    tied = gap_numbers(gaps, margin, 0.004)
    assert tied == {"logit_gap": 0.01, "logit_gap_mean": 0.1275,
                    "argmax_miss_share": 0.5, "near_tie_share": 0.25}
    assert gap_numbers(gaps, margin, 1.0)["logit_gap"] is None


def test_every_configuration_names_its_reference(tmp_path):
    from servebench.spec import load_reference, reference_path

    bench = specmod.load_benchmark()
    for c in bench["configs"]:
        raw = json.loads((specmod.ROOT / c["file"]).read_text())
        assert reference_path(raw).is_file()
        assert callable(load_reference(raw).compare)
    qwen = json.loads((specmod.HERE / "configs/qwen2-7b-14L.json")
                      .read_text())
    assert reference_path(qwen) == specmod.PACKAGE / "reference.py"
    from servebench import reference
    assert load_reference(qwen) is reference
    with pytest.raises(KeyError, match="'reference'"):
        load_reference({"name": "x"})
    with pytest.raises(FileNotFoundError, match="'reference'.*nowhere.py"):
        load_reference({"name": "x", "reference": str(tmp_path /
                                                      "nowhere.py")})


def test_a_configuration_runs_through_a_reference_of_its_own(tmp_path):
    from servebench.spec import load_reference

    config = dict(_moe(), reference=str(_reference_copy(tmp_path, "noted",
                                                        NOTED)))
    result, info = _run(config, seconds=2.0)
    noted = load_reference(config).NOTED
    assert len(noted) == 1 and noted[0] > 0
    assert result["correct"] is True, result["checks"]
    assert {"logit_gap", "near_tie_share"} <= set(result["checks"])


def test_a_leaf_the_program_lacks_fails_before_serving(tmp_path):
    config = dict(_moe(), reference=str(_reference_copy(tmp_path, "qk",
                                                        EXTRA_LEAF)))
    with pytest.raises(ValueError, match="q_norm"):
        _run(config)


def test_program_options_are_passed_through_by_name():
    import dataclasses

    from repro.configs.base import ArchConfig
    from servebench.harness import arch_config

    qwen = load_spec(specmod.HERE / "configs/qwen2-7b-14L.json")
    assert arch_config(qwen) == ArchConfig(
        name="qwen2-7b-14L", family="dense", n_layers=14, d_model=3584,
        n_heads=28, n_kv_heads=4, d_ff=18944, vocab=152064, head_dim=128,
        qkv_bias=True, rope_theta=1e6, tie_embeddings=False, norm_eps=1e-6,
        n_experts=0, top_k=0, moe_d_ff=0, shared_expert=False)
    moe = spec_from_dict(dict(_moe(), program={"capacity_factor": 2.0}))
    assert arch_config(moe) == dataclasses.replace(
        arch_config(spec_from_dict(_moe())), capacity_factor=2.0)
    with pytest.raises(ValueError, match="qk_norm"):
        arch_config(spec_from_dict(dict(_moe(), program={
            "qk_norm": True, "capacity_factor": 2.0})))


def _step_fault(kind):
    def plant(server):
        step = server._decode
        calls = [0]

        def broken(params, cache, tokens):
            calls[0] += 1
            if kind == "state_unchanged":
                nxt, logits, _ = step(params, cache, tokens)
                return nxt, logits, cache
            if kind == "half_batch":
                half = tokens.shape[0] // 2
                nxt, logits, new = step(params, cache,
                                        tokens.at[half:].set(0))
                return nxt, logits, new
            nxt, logits, new = step(params, cache, tokens)
            if calls[0] % 5 == 0:                # token_altered
                nxt = nxt.at[0, 0].set((nxt[0, 0] + 1) % logits.shape[-1])
            return nxt, logits, new
        server._decode = broken
    return plant


def _record_fault(server):
    gather = server._gather_next_tokens

    def broken():
        out = gather()
        slot = min(out)
        out[slot] = (out[slot] + 1) % server.cfg.vocab
        return out
    server._gather_next_tokens = broken


@pytest.mark.parametrize("config", [TINY, _moe()], ids=["dense", "moe"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "record_altered"])
def test_a_broken_timed_path_is_not_correct(fault, config):
    plant = _record_fault if fault == "record_altered" else _step_fault(
        fault)
    result, _ = _run(config, fault=plant)
    assert not result["correct"], result["checks"]


# -- per-layer readers on a synthetic run -----------------------------------------


def _synthetic_run():
    from types import SimpleNamespace

    from servebench.peaks import roofline_seconds
    from servebench.work import call_work

    bench = specmod.load_benchmark()
    spec = load_spec(specmod.ROOT / bench["configs"][0]["file"])
    req = SimpleNamespace(prompt=np.zeros(4, np.int32), first_call=3,
                          token_slots=[0] * 6, token_calls=list(range(4, 10)))
    run = SimpleNamespace(
        spec=spec, peaks=peaks_for("TPU v5 lite"), reqs=[req], routing=None,
        first_window_call=0, window_calls_end=10, t_open=0.0, t_close=1.0,
        ticks=[timeline.Tick(0.1 * i, 0.1 * i + 0.1, 1, 1, 0)
               for i in range(10)],
        compiles_in_window=20, plan_ready_s=0.5, gather_window=4)
    # each step call takes exactly the least time it needs, one after another
    ops, t = [], 0.0
    for c, items in sorted(call_work(run).items()):
        need = roofline_seconds(*step_cost(spec, [n for _, n in items]),
                                run.peaks)
        ops.append(tr.DeviceOp("while.2", "jit_serve_step", t, t + need))
        t += need
    ops.append(tr.DeviceOp("tpu_custom_call.1", "jit_wrapped", t, t + 1e-6))
    run.trace = {"ops": ops, "t0": 0.0, "t1": 1.0, "offset": 0.0,
                 "busy_s": tr.busy(ops, 0.0, 1.0), "window_s": 1.0,
                 "devices": 1}
    return run, t


def test_readers_on_a_synthetic_run():
    run, device_s = _synthetic_run()
    read = {m: specmod.load_reader(m)(run) for m in (
        "model_step_roofline", "serve_mfu", "banked_roofline",
        "compiles_per_tick", "device_idle_share", "plan_ready_s")}
    assert read["model_step_roofline"] == pytest.approx(100.0)
    assert read["compiles_per_tick"] == pytest.approx(2.0)
    assert read["device_idle_share"] == pytest.approx(
        100.0 * (1 - device_s - 1e-6))
    assert 0 < read["serve_mfu"] < 100
    assert 0 < read["banked_roofline"] < 100
    assert read["plan_ready_s"] == 0.5
    run.trace = None
    assert specmod.load_reader("model_step_roofline")(run) is None
