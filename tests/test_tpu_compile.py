"""Compile the serving path for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what Mosaic or the
device's memory would refuse (misaligned tiles, programs that do not fit).
These tests keep the banked kernels and the model step that ``Server``
runs compilable for v5e at the shapes it serves qwen2-7b and OLMoE with.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import olmoe_1b_7b, qwen2_7b
from repro.core import compile_trivial
from repro.core.planner import BankingPlanner
from repro.runtime.server import _page_program

HBM_BYTES = 16 * 10**9          # one v5e chip (Google Cloud, "TPU v5e")
PAGE = 128
GATHER_WINDOW = 4               # Server._gather_window at max_len >= 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, which would only warn on the next one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def kv_artifacts():
    """The server's two KV layouts: the trivial fallback and the solved
    multi-bank scheme."""
    program = _page_program(qwen2_7b.STAGE_MAX_LEN, PAGE,
                            qwen2_7b.STAGE_SLOTS)
    solved = BankingPlanner().plan(program, "kv_pool").compile()
    assert solved.n_banks > 1
    trivial = compile_trivial(program.memories["kv_pool"])
    return {"trivial": trivial, "solved": solved}


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("layout", ["trivial", "solved"])
def test_banked_kernels_compile_for_v5e(one_chip, kv_artifacts, layout):
    """The gather, the per-slot record write and the row scatter compile
    through Mosaic at the record table's serving shapes."""
    art = kv_artifacts[layout]
    slots = qwen2_7b.STAGE_SLOTS
    table = (art.layout.table_shape(slots), jnp.int32)
    vec = ((slots,), jnp.int32)
    gather = _compile(lambda t, r: art.gather(t, r, interpret=False),
                      one_chip, table, ((slots, GATHER_WINDOW), jnp.int32))
    record = _compile(
        lambda t, r, c, v: art.scatter(t, r, v, col=c, interpret=False),
        one_chip, table, vec, vec, vec)
    rows = _compile(lambda t, r, v: art.scatter(t, r, v, interpret=False),
                    one_chip, table, vec, ((slots, slots), jnp.int32))
    for compiled in (gather, record, rows):
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["trivial", "solved"])
def test_cached_banked_executables_compile_for_v5e(one_chip, kv_artifacts,
                                                   layout):
    """The executables the artifact builds once and keeps -- the gather
    and the per-slot record write, each with the record table's padding
    to whole tiles inside -- compile through Mosaic as they are, and the
    Mosaic call keeps the ``tpu_custom_call`` name a device trace shows
    for the banked kernels."""
    art = kv_artifacts[layout]
    slots = qwen2_7b.STAGE_SLOTS
    table = jax.ShapeDtypeStruct(art.layout.table_shape(slots), jnp.int32,
                                 sharding=one_chip)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((slots, GATHER_WINDOW), jnp.int32,
                                sharding=one_chip)
    for op, args in (("gather", (table, rows)),
                     ("scatter_elems", (table, vec, vec, vec))):
        text = art._kernel(op, False, *args).lower(*args).compile().as_text()
        assert re.search(r"%tpu_custom_call\S* = \S+ custom-call\(", text), op
        assert " pad(" in text, op          # 8 lanes -> one (1, 128) tile


@pytest.mark.parametrize("width,dtype", [(128, jnp.float32),
                                         (512, jnp.bfloat16)])
def test_banked_gather_compiles_for_wide_rows(one_chip, kv_artifacts,
                                              width, dtype):
    """Rows wider than one lane tile, and packed 16-bit rows, DMA as
    whole tile stacks."""
    art = kv_artifacts["solved"]
    compiled = _compile(lambda t, r: art.gather(t, r, interpret=False),
                        one_chip, (art.layout.table_shape(width), dtype),
                        ((32,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _assert_serve_step_fits(one_chip, arch):
    """``arch``'s one-chip stage decode step, as ``Server`` jits it, at
    its served batch and cache, compiles for v5e and fits its HBM."""
    from repro.launch.steps import make_serve_step
    from repro.models import get_model

    model = get_model(arch.one_chip_stage())
    slots = arch.STAGE_SLOTS

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda: model.init_cache(slots, arch.STAGE_MAX_LEN)))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(model)).lower(
        params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    param_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                      for p in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= param_bytes
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < HBM_BYTES, peak


def test_qwen2_serve_step_fits_one_v5e(one_chip):
    """The 14-layer qwen2-7b decode step at published widths, with the
    8 x 4096-token bf16 cache, compiles for v5e and fits its HBM."""
    _assert_serve_step_fits(one_chip, qwen2_7b)


def test_olmoe_serve_step_fits_one_v5e(one_chip):
    """The 8-layer OLMoE-1B-7B decode step at published widths (64
    experts, top-8, the q/k norm), with the 8 x 4096-token bf16 cache,
    compiles for v5e and fits its HBM."""
    _assert_serve_step_fits(one_chip, olmoe_1b_7b)
