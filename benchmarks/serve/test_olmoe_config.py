"""CPU tests of the OLMoE configuration: its file against the program's
published preset, a tiny model of its shape through ``run_cell`` with its
own reference, and the ``expert_weight_use`` reader."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from servebench import harness, spec as specmod
from servebench.modelspec import load_spec, spec_from_dict
from servebench.weights import check_tree, model_leaves

from test_servebench import TINY, _numbers, _run

OLMOE_FILE = specmod.HERE / "configs/olmoe-1b-7b-8L.json"
REFERENCE = "benchmarks/serve/servebench/olmoe_reference.py"


def _olmoe():
    # OLMoE's shape at a test size: 4 experts, top-2, the q/k norm over the
    # whole projection width, and gates not renormalised.  Limits from 24
    # CPU runs of the 8-s window (276 served tokens each), with the float8
    # control computed on each: the largest change of a router probability
    # that rounding the router's input to bfloat16 makes is 0.00196, so
    # route_tie is 0.004, under which at most 8.7% of the tokens are
    # near-tied (mean 5.5%); every token the program serves on a flipped
    # route has a margin of 0.0006 or less.  The widest gap over tokens
    # that are not near-tied reads 0.0088-0.0420 for the program and
    # 0.113-0.689 for the control.  The mean gap (program 0.00005-0.00145,
    # control 0.0042-0.0126) and the share of tokens off the reference's
    # first choice (0.007-0.036 against 0.051-0.141) separate less than
    # threefold, and are not compared.  64 more CPU runs, four at a time
    # (32 of each case, each reading the program): program 0-0.0170,
    # control 0.140-0.529, near-tied at most 10.9%; none failed.
    # Flake risk: over all 88 program and 56 control readings the
    # control's smallest widest gap is only 2.7 times the program's
    # largest, and the limit stands 1.7 times above the one and 1.6 times
    # below the other, so a rare tail on either side would fail this test.
    # A longer window does not widen the room: at 16 s the rows pass the
    # 512 positions of `max_len` and nothing is compared.
    return dict(TINY, intermediate_size=32, qkv_bias=False, num_experts=4,
                num_experts_per_tok=2, norm_topk_prob=False,
                rms_norm_eps=1e-5, reference=REFERENCE,
                program={"qk_proj_norm": True, "norm_topk_prob": False},
                limits={"logit_gap": 0.07, "near_tie_share": 0.15,
                        "route_tie": 0.004})


def test_the_file_serves_the_programs_published_preset():
    from repro.configs import olmoe_1b_7b
    from repro.models import get_model

    spec = load_spec(OLMOE_FILE)
    assert harness.arch_config(spec) == olmoe_1b_7b.one_chip_stage()
    assert (spec.d_model, spec.heads, spec.kv_heads, spec.head_dim,
            spec.experts, spec.top_k, spec.expert_ff, spec.vocab) == \
        (2048, 16, 16, 128, 64, 8, 1024, 50304)
    assert not spec.norm_topk and spec.layers == 8
    assert specmod.reference_path(spec.raw) == specmod.ROOT / REFERENCE
    tree = model_leaves(spec)
    assert tree["layers"]["q_norm"][0] == (8, 2048)
    assert tree["layers"]["k_norm"][0] == (8, 2048)
    shapes = {k: jax.ShapeDtypeStruct(v[0], jnp.dtype(v[1]))
              for k, v in tree["top"].items()}
    shapes["layers"] = {k: jax.ShapeDtypeStruct(v[0], jnp.dtype(v[1]))
                        for k, v in tree["layers"].items()}
    check_tree(harness.expected_tree(spec), shapes)
    # without the q/k norm the program's tree lacks the reference's gains
    bare = dict(spec.raw, program={"norm_topk_prob": False})
    bare_tree = jax.eval_shape(
        get_model(harness.arch_config(spec_from_dict(bare))).init,
        jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="q_norm"):
        check_tree(bare_tree, shapes)


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_tiny_olmoe_is_correct_and_the_float8_control_is_not(control):
    config = _olmoe()
    result, info = _run(config, control=control, seconds=8.0)
    checks = result["checks"]
    compared = {k: lim for k, lim in config["limits"].items()
                if k != "route_tie"}
    assert set(compared) <= set(checks)
    program = _numbers(next(x for x in info if x.startswith("program")))
    assert all(program[k] <= lim for k, lim in compared.items()), \
        (program, compared)
    if control:
        assert result["correct"] is False, checks
        assert any(checks[k]["value"] > lim
                   for k, lim in compared.items()), checks
    else:
        assert result["correct"] is True, checks


# -- expert_weight_use on spans recorded for a synthetic run ------------------


def _spans(t0, steps):
    """A rolled serve trace far from any real one: one ``serve.tick`` a
    second from ``t0``, each holding a ``serve.step`` with ``steps[i]``
    as its attributes."""
    from repro.core.tracing import new_trace_id, serve_tracer

    tr = serve_tracer()
    tid = new_trace_id()
    for i, attrs in enumerate(steps):
        tick = tr.record(tid, "serve.tick", t0 + i, t0 + i + 0.5, tick=i)
        tr.record(tid, "serve.step", t0 + i + 0.1, t0 + i + 0.4,
                  parent=tick, **attrs)
    tr.finish(tid)


def test_expert_weight_use_reads_the_window_steps():
    read = specmod.load_reader("expert_weight_use")
    t0 = time.perf_counter() + 1e7
    step = {"moe_experts_routed": 320, "moe_experts_read": 512,
            "moe_dropped": 0}
    _spans(t0, [step, step, dict(step, moe_experts_routed=352)])
    # the window holds the first two ticks whole and cuts the third
    run = SimpleNamespace(t_open=t0 - 0.5, t_close=t0 + 2.2)
    assert read(run) == pytest.approx(62.5)
    run.t_close = t0 + 3.0
    assert read(run) == pytest.approx(100 * (320 + 320 + 352) / (3 * 512))
    # a program whose steps carry no route counts gives nothing
    t1 = t0 + 1e3
    _spans(t1, [{}, {}])
    assert read(SimpleNamespace(t_open=t1, t_close=t1 + 3.0)) is None
