"""CompiledBankingPlan: the executable artifact between planner and
consumers -- resolution correctness, layout round-trips, compile cache,
serialization, and the downstream bridges (pager, PartitionSpec)."""


import numpy as np
import pytest

from repro.core import (AccessDecl, BankingPlanner, CompiledBankingPlan,
                        Counter, Ctrl, FlatGeometry, MemorySpec,
                        MultiDimGeometry, Program, Sched, compile_geometry,
                        compile_plan)
from repro.core.geometry import propose_P
from repro.core.polytope import Affine


def _reader_program(dims=(256,), par=8, count=32, name="table"):
    mem = MemorySpec(name, dims=dims, word_bits=32, ports=1)
    return Program(
        root=Ctrl("reader", Sched.INNER,
                  counters=[Counter("i", 0, 1, count, par=par)],
                  accesses=[AccessDecl(name, (Affine.of(i=1),))]),
        memories={name: mem},
    )


def _coords(addr, dims):
    out, rem = [], addr
    for d in reversed(dims):
        out.append(rem % d)
        rem //= d
    return tuple(reversed(out))


# ---------------------------------------------------------------------------
# Resolution circuit == brute-force Eq. 1-2 (deterministic sweep; the
# hypothesis generalization lives in test_artifact_properties.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dims,N,B,unit_dim", [
    ((24,), 3, 1, 0),
    ((60,), 8, 1, 0),          # pad = 4
    ((32,), 4, 2, 0),
    ((21,), 5, 3, 0),
    ((8, 12), 4, 1, 1),
    ((8, 12), 3, 2, 0),
    ((6, 10), 4, 1, None),     # diagonal alpha = (1, 1)
])
def test_flat_resolution_matches_bruteforce(dims, N, B, unit_dim):
    n = len(dims)
    alpha = ((1,) * n if unit_dim is None else
             tuple(1 if i == unit_dim else 0 for i in range(n)))
    mem = MemorySpec("m", dims=dims, word_bits=16, ports=1)
    geo = FlatGeometry(N=N, B=B, alpha=alpha, P=propose_P(mem, N, B, alpha)[0])
    art = compile_geometry(mem, geo, backend="numpy")
    A = art.layout.logical_size
    ba, bo = art.resolve(np.arange(A, dtype=np.int64))
    ba = np.broadcast_to(np.asarray(ba), (A,))
    bo = np.broadcast_to(np.asarray(bo), (A,))
    for a in range(A):
        x = _coords(a, dims)
        assert ba[a] == geo.bank_address(x), (a, x)
        assert bo[a] == geo.bank_offset(x, dims), (a, x)
        assert 0 <= bo[a] < art.bank_volume


@pytest.mark.parametrize("dims,Ns,Bs", [
    ((8, 12), (2, 3), (1, 1)),
    ((8, 12), (4, 1), (2, 1)),
    ((6, 6), (3, 2), (1, 1)),
])
def test_multidim_resolution_matches_bruteforce(dims, Ns, Bs):
    mem = MemorySpec("m", dims=dims, word_bits=16, ports=1)
    geo = MultiDimGeometry(Ns=Ns, Bs=Bs, alphas=(1,) * len(dims))
    art = compile_geometry(mem, geo, backend="numpy")
    A = art.layout.logical_size
    ba, bo = art.resolve(np.arange(A, dtype=np.int64))
    for a in range(A):
        x = _coords(a, dims)
        bat = geo.bank_address(x)
        folded = 0
        for b, n in zip(bat, Ns):
            folded = folded * n + b
        assert ba[a] == folded, (a, x)
        assert bo[a] == geo.bank_offset(x, dims), (a, x)


def test_unpack_inverts_pack_with_padding():
    import jax.numpy as jnp

    mem = MemorySpec("m", dims=(60,), word_bits=32, ports=1)
    geo = FlatGeometry(N=8, B=1, alpha=(1,), P=propose_P(mem, 8, 1, (1,))[0])
    art = compile_geometry(mem, geo)
    assert art.layout.pad == (4,)                      # 60 -> 64
    assert art.n_banks * art.bank_volume > 60          # padded slots exist
    x = jnp.asarray(np.random.default_rng(0).normal(size=(60, 3)),
                    jnp.float32)
    assert (np.asarray(art.unpack(art.pack(x))) == np.asarray(x)).all()


def test_batched_gather_matches_per_rowset_gathers():
    """A stacked (T, R) index matrix -- one kernel launch -- returns
    exactly what T separate per-row-set gathers return, on both
    backends."""
    import jax.numpy as jnp

    plan = BankingPlanner().plan(_reader_program(), "table")
    rng = np.random.default_rng(0)
    flat = jnp.asarray(rng.normal(size=(256, 8)), jnp.float32)
    idx = rng.integers(0, 256, size=(5, 7)).astype(np.int32)
    for backend in ("jax", "numpy"):
        art = plan.compile(backend=backend)
        table = art.pack(flat) if backend == "jax" else \
            np.asarray(plan.compile(backend="jax").pack(flat))
        got = np.asarray(art.gather(table, idx))
        assert got.shape == (5, 7, 8)
        for t in range(idx.shape[0]):
            row = np.asarray(art.gather(table, idx[t]))
            np.testing.assert_array_equal(got[t], row)


def test_scatter_writes_rows_through_the_resolution_circuit():
    """scatter(table, rows, values) is the write-path analogue of the
    batched gather: rows land exactly where pack's reference layout
    places them, untouched slots carry over, and duplicates resolve
    last-write-wins -- on both backends."""
    import jax.numpy as jnp

    plan = BankingPlanner().plan(_reader_program(), "table")
    rng = np.random.default_rng(1)
    flat = rng.normal(size=(256, 8)).astype(np.float32)
    rows = np.asarray([3, 77, 3, 200, 41], np.int64)   # 3 duplicated
    vals = rng.normal(size=(5, 8)).astype(np.float32)
    want = flat.copy()
    for r, v in zip(rows, vals):                       # last write wins
        want[r] = v
    for backend in ("jax", "numpy"):
        art = plan.compile(backend=backend)
        table = (art.pack(jnp.asarray(flat)) if backend == "jax" else
                 np.asarray(plan.compile(backend="jax").pack(flat)))
        out = art.scatter(table, rows, vals)
        np.testing.assert_array_equal(np.asarray(art.unpack(out)), want,
                                      err_msg=backend)


def test_scatter_single_column_element_writes():
    """scatter(..., col=...) writes one element per row -- the serving
    runtime's batched per-slot token-record write -- without touching
    the rest of the row."""
    import jax.numpy as jnp

    plan = BankingPlanner().plan(_reader_program(), "table")
    flat = np.zeros((256, 4), np.int32)
    rows = np.asarray([0, 17, 99, 17], np.int64)
    cols = np.asarray([1, 3, 0, 2], np.int64)
    vals = np.asarray([11, 22, 33, 44], np.int32)
    want = flat.copy()
    for r, c, v in zip(rows, cols, vals):
        want[r, c] = v
    for backend in ("jax", "numpy"):
        art = plan.compile(backend=backend)
        table = (art.pack(jnp.asarray(flat)) if backend == "jax" else
                 np.asarray(plan.compile(backend="jax").pack(flat)))
        out = art.scatter(table, rows, vals, col=cols)
        np.testing.assert_array_equal(np.asarray(art.unpack(out)), want,
                                      err_msg=backend)


def test_ops_scatter_banked_gather_round_trip():
    """ops.scatter_banked then ops.gather_banked round-trips rows
    through the same compiled artifact (kernel-to-kernel agreement)."""
    import jax.numpy as jnp

    from repro.kernels import ops

    plan = BankingPlanner().plan(_reader_program(), "table")
    art = plan.compile()
    rng = np.random.default_rng(2)
    table = art.pack(jnp.asarray(rng.normal(size=(256, 8)), jnp.float32))
    rows = jnp.asarray([5, 120, 250], jnp.int32)
    vals = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    table = ops.scatter_banked(table, rows, vals, art)
    got = ops.gather_banked(table, rows, art)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals))


def test_one_cached_gather_serves_every_index_matrix_of_its_shape():
    """The jitted gather is built once for an index shape; index
    matrices of that shape with other values reuse it and still match
    the numpy backend."""
    import jax.numpy as jnp

    plan = BankingPlanner().plan(_reader_program(), "table")
    art, ref_art = plan.compile(), plan.compile(backend="numpy")
    rng = np.random.default_rng(3)
    flat = rng.integers(0, 1 << 20, size=(256, 8)).astype(np.int32)
    table = art.pack(jnp.asarray(flat))
    for _ in range(4):
        idx = rng.integers(0, 256, size=(8, 4)).astype(np.int32)
        np.testing.assert_array_equal(
            np.asarray(art.gather(table, idx)),
            ref_art.gather(np.asarray(table), idx))
    assert art.kernel_builds["gather"] == 1
    assert art.kernel_calls["gather"] == 4
    art.gather(table, idx[:, :2])                     # a new shape builds
    assert art.kernel_builds["gather"] == 2


def _tiny_server(art, max_len):
    import dataclasses

    from repro.configs import get_arch
    from repro.models import get_model
    from repro.runtime.server import Server

    cfg = dataclasses.replace(
        get_arch("qwen2_7b").reduced(), n_layers=1, d_model=32, d_ff=64,
        vocab=64, n_heads=2, n_kv_heads=2, head_dim=16)
    return Server(get_model(cfg), max_batch=2, max_len=max_len,
                  kv_plan=art)


def _kv_layouts(max_len):
    from repro.core import compile_trivial
    from repro.runtime.server import _page_program

    program = _page_program(max_len, 8, 2)
    solved = BankingPlanner().plan(program, "kv_pool").compile()
    assert solved.n_banks > 1
    return compile_trivial(program.memories["kv_pool"]), solved


def test_padded_record_flush_equals_the_unpadded_write():
    """Server pads a record flush to a power of two by repeating its last
    write: the table ends up as the unpadded writes leave it, duplicate
    addresses last-write-wins, through one 8-long executable."""
    _, solved = _kv_layouts(32)
    server = _tiny_server(solved, 32)
    writes = [(0, 0, 5), (3, 1, 7), (3, 1, 9), (31, 0, 11), (0, 0, 13)]
    server._pending_records = list(writes)
    server._flush_records()
    want = np.zeros((32, 2), np.int32)
    for pos, slot, tok in writes:
        want[pos, slot] = tok
    np.testing.assert_array_equal(
        np.asarray(solved.unpack(server.kv_records)), want)
    assert solved.kernel_builds["scatter_elems"] == 1
    # key: op, interpret, then (shape, dtype) of table, rows, cols, values
    assert [k[3][0] for k in solved._kernels
            if k[0] == "scatter_elems"] == [(8,)]


def test_a_swapped_layout_gathers_through_its_own_executable():
    """After ``Server._swap_to`` the gather reads the same logical
    records through the new artifact's executable, built once; the old
    artifact's counts stay as they were."""
    trivial, solved = _kv_layouts(32)
    server = _tiny_server(trivial, 32)
    server._pending_records = [(p, p % 2, 100 + p) for p in range(32)]
    server._flush_records()
    rows = np.arange(8, dtype=np.int32).reshape(2, 4) * 3
    before = np.asarray(server._kv_art.gather(server.kv_records, rows))
    old = dict(trivial.kernel_builds), dict(trivial.kernel_calls)
    server._swap_to(solved)
    assert server._kv_art is solved
    for _ in range(3):
        after = np.asarray(server._kv_art.gather(server.kv_records, rows))
        np.testing.assert_array_equal(after, before)
    assert solved.kernel_builds["gather"] == 1
    assert solved.kernel_calls["gather"] == 3
    assert (dict(trivial.kernel_builds), dict(trivial.kernel_calls)) == old


def test_trivial_fallback_artifact_is_single_bank_rowmajor():
    from repro.core import compile_trivial

    mem = MemorySpec("m", dims=(60,), word_bits=32, ports=1)
    art = compile_trivial(mem, backend="numpy")
    assert art.n_banks == 1 and art.bank_volume == 60
    A = art.layout.logical_size
    ba, bo = art.resolve(np.arange(A, dtype=np.int64))
    assert (np.broadcast_to(np.asarray(ba), (A,)) == 0).all()
    np.testing.assert_array_equal(np.broadcast_to(np.asarray(bo), (A,)),
                                  np.arange(A))
    # 2-D memories flatten row-major
    mem2 = MemorySpec("m", dims=(6, 10), word_bits=32, ports=1)
    art2 = compile_trivial(mem2, backend="numpy")
    assert art2.n_banks == 1 and art2.layout.pad == (0, 0)
    _, bo2 = art2.resolve(np.arange(60, dtype=np.int64))
    np.testing.assert_array_equal(np.broadcast_to(np.asarray(bo2), (60,)),
                                  np.arange(60))


def test_jax_and_numpy_backends_agree():
    plan = BankingPlanner().plan(_reader_program(), "table")
    aj = plan.compile(backend="jax")
    an = plan.compile(backend="numpy")
    addr = np.arange(256, dtype=np.int64)
    np.testing.assert_array_equal(np.asarray(aj.resolve(addr)[0]),
                                  an.resolve(addr)[0])
    np.testing.assert_array_equal(np.asarray(aj.resolve(addr)[1]),
                                  an.resolve(addr)[1])


# ---------------------------------------------------------------------------
# Compile cache + durability
# ---------------------------------------------------------------------------


@pytest.mark.compile
def test_artifact_roundtrip_compile_save_load_gather(tmp_path):
    """compile -> save -> load -> gather: the serialization path CI gates."""
    import jax.numpy as jnp
    from repro.kernels import ref

    planner = BankingPlanner(cache_dir=tmp_path)
    plan = planner.plan(_reader_program(), "table")
    art = plan.compile()
    files = list(tmp_path.glob("*.compiled.json"))   # persisted next to plan
    assert len(files) == 1
    loaded = CompiledBankingPlan.load(files[0])
    assert loaded.signature == art.signature == plan.signature
    assert loaded.layout == art.layout
    assert loaded.kind == art.kind and loaded.geometry == art.geometry
    flat = jnp.asarray(np.random.default_rng(0).normal(size=(256, 4)),
                       jnp.float32)
    idx = jnp.asarray([3, 77, 130, 255], jnp.int32)
    got = loaded.gather(loaded.pack(flat), idx)
    assert (np.asarray(got) ==
            np.asarray(ref.banked_gather_reference(flat, idx))).all()


@pytest.mark.compile
def test_compile_cache_and_warm_start_skip_relowering(tmp_path):
    planner = BankingPlanner(cache_dir=tmp_path)
    plan = planner.plan(_reader_program(), "table")
    a1 = planner.compile(plan)
    a2 = plan.compile()                    # plan routes through its planner
    assert a2 is a1
    assert planner.stats.compiles == 1 and planner.stats.compile_hits == 1
    # a fresh planner warm-starts plans AND artifacts: no solve, no lower
    warm = BankingPlanner(cache_dir=tmp_path)
    assert warm.warm_start(tmp_path) == 2  # one plan + one artifact
    p = warm.plan(_reader_program(), "table")
    assert p.status == "cached"
    warm.compile(p)
    assert warm.stats.compiles == 0 and warm.stats.compile_hits == 1
    # and even without warm_start(), compile() consults the disk cache
    cold = BankingPlanner(cache_dir=tmp_path)
    cold.compile(cold.plan(_reader_program(), "table"))
    assert cold.stats.compiles == 0 and cold.stats.compile_disk_hits == 1


def test_detached_plan_compiles_standalone():
    plan = BankingPlanner().plan(_reader_program(), "table")
    art = compile_plan(plan)
    assert art.signature == plan.signature
    assert art.n_banks == plan.best.num_banks


def test_plan_without_solution_refuses_to_compile():
    from repro.core.planner import BankingPlan
    empty = BankingPlan(memory="m", signature="", best=None, status="timeout")
    with pytest.raises(ValueError, match="no solution"):
        empty.compile()


# ---------------------------------------------------------------------------
# Downstream bridges: PartitionSpec + KV page pool
# ---------------------------------------------------------------------------


def test_to_partition_spec_places_banked_dims():
    from jax.sharding import PartitionSpec as P

    mem = MemorySpec("m", dims=(64,), ports=1)
    geo = FlatGeometry(N=8, B=1, alpha=(1,), P=propose_P(mem, 8, 1, (1,))[0])
    assert compile_geometry(mem, geo).to_partition_spec("model") == P("model")

    mem2 = MemorySpec("m", dims=(8, 12), ports=1)
    md = MultiDimGeometry(Ns=(2, 3), Bs=(1, 1), alphas=(1, 1))
    assert compile_geometry(mem2, md).to_partition_spec(("x", "y")) == \
        P("x", "y")
    md1 = MultiDimGeometry(Ns=(1, 3), Bs=(1, 1), alphas=(1, 1))
    assert compile_geometry(mem2, md1).to_partition_spec("y") == P(None, "y")

    diag = FlatGeometry(N=4, B=1, alpha=(1, 1),
                        P=propose_P(mem2, 4, 1, (1, 1))[0])
    with pytest.raises(ValueError, match="diagonal"):
        compile_geometry(mem2, diag).to_partition_spec("model")


def test_kv_page_pool_reads_layout_off_artifact():
    from repro.runtime.server import KVPagePool, page_solution

    art = page_solution(None, max_len=64, page=16, readers=4)
    pool = KVPagePool(art, slots=4)
    assert pool.page_size == art.layout.bank_volume
    assert pool.pages_per_slot == art.layout.n_banks
    # each slot's pages cover the (padded) per-sequence pool
    assert pool.page_size * pool.pages_per_slot >= 64
    assert pool.total_pages == 4 * art.layout.n_banks
    assert pool.try_alloc(0, 17)
    assert pool.used_pages == pool.pages_for(17)
    assert not pool.try_alloc(0, 17)       # slot already owned
    # a request that can never fit one slot is rejected, not queued forever
    assert not pool.fits(pool.pages_per_slot * pool.page_size + 1)
    assert not pool.try_alloc(1, pool.pages_per_slot * pool.page_size + 1)
    pool.release(0)
    assert pool.used_pages == 0


def test_lane_artifact_bridge():
    from repro.parallel import sharding as shd

    art = shd.lane_artifact(64, 16)
    assert art is not None and art.n_banks % 16 == 0
    assert art.max_fan_out == 1
    assert art.to_partition_spec("model")[0] == "model"
    assert shd.lane_artifact(8, 16) is None
