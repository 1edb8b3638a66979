"""Banked gather/scatter: the paper's bank-resolution circuit as Pallas
kernels.

The memory is stored *bank-major* -- physical layout (N_banks, bank_volume,
row_width) owned by a ``CompiledBankingPlan`` -- and the kernel gathers
logical rows by evaluating the bank-address / bank-offset equations
(Eq. 1-2) with the Sec-3.4 strength-reduced arithmetic.

TPU adaptation of the circuit: the table stays in HBM
(``memory_space=ANY``) and the kernel reads each logical address from
scalar-prefetch memory, runs the BA/BO arithmetic on the scalar core, and
issues one row DMA from ``table[ba, bo]`` -- the resolution logic sits in
front of the memory, where an FPGA would put it.  Crandall/NAF rewrites
shorten that scalar path exactly as they eliminate DSPs on the FPGA (the
TPU scalar core has no integer divide either -- XLA emits long multiply
sequences for /C and %C).

Mosaic only DMAs whole (sublane, lane) tiles, so a row is moved as an
``(L, 128)`` tile stack: the wrappers view a ``(N, V, D)`` table as
``(N, V, L, 128)``, padding ``D`` up to ``L * 128`` lanes with ``L`` a
multiple of the dtype's sublane packing.  The view is free when ``D``
already fills whole tiles (e.g. 128 int32 lanes); narrower tables are
padded on the device, inside the same executable as the kernel when the
caller jits the wrapper (the artifact does, once per shape).

This module is the *raw kernel only*: it takes the already-compiled
``ba_fn`` / ``bo_fn`` resolution callables.  Lowering a banking scheme to
those callables (and to the pack/unpack layout converters) is the job of
``repro.core.artifact.CompiledBankingPlan`` -- use ``plan.compile()`` and
call ``artifact.gather(table, rows)`` instead of binding this directly.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_ROWS_PER_STEP = 8    # row DMAs in flight per grid step


def _to_tiles(x: jax.Array) -> jax.Array:
    """``(..., D)`` rows -> ``(..., L, LANES)`` DMA-able tile stacks."""
    D = x.shape[-1]
    pack = max(1, 4 // x.dtype.itemsize)          # sublanes per 32-bit word
    L = -(-max(1, -(-D // LANES)) // pack) * pack
    pad = L * LANES - D
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], L, LANES)


def _from_tiles(x: jax.Array, D: int) -> jax.Array:
    """Inverse of :func:`_to_tiles` for a row width of ``D``."""
    return x.reshape(*x.shape[:-2], -1)[..., :D]


def _rows_per_step(T: int) -> int:
    return math.gcd(T, MAX_ROWS_PER_STEP)


def _gather_kernel(ba_fn, bo_fn, rows, idx_ref, table_ref, out_ref, sem):
    base = pl.program_id(0) * rows
    copies = []
    for r in range(rows):                  # all row DMAs in flight at once
        a = idx_ref[base + r]
        cp = pltpu.make_async_copy(table_ref.at[ba_fn(a), bo_fn(a)],
                                   out_ref.at[base + r], sem)
        cp.start()
        copies.append(cp)
    for cp in copies:
        cp.wait()


def banked_gather(table: jax.Array, indices: jax.Array,
                  ba_fn: Callable, bo_fn: Callable, *,
                  interpret=False) -> jax.Array:
    """table: (N_banks, bank_volume, D) bank-major storage.
    indices: flat logical addresses, a (T,) vector or a stacked (T, R)
    matrix of row-sets, flattened into one grid.
    Returns ``indices.shape + (D,)`` gathered rows.

    The bank-resolution arithmetic (ba_fn/bo_fn, the compiled artifact's
    transformed op graphs) runs in the kernel on the prefetched index
    scalars and addresses one row DMA per index, up to
    ``MAX_ROWS_PER_STEP`` in flight per grid step.
    """
    lead = indices.shape
    indices = indices.reshape(-1)
    T = indices.shape[0]
    D = table.shape[-1]
    tiles = _to_tiles(table)
    rows = _rows_per_step(T)
    out = pl.pallas_call(
        lambda *refs: _gather_kernel(ba_fn, bo_fn, rows, *refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((T,) + tiles.shape[2:], table.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), tiles)
    return _from_tiles(out, D).reshape(*lead, D)


def _scatter_kernel(ba_fn, bo_fn, idx_ref, v_ref, t_in, t_ref, sem):
    # one row DMA into the resolved (bank, offset) slot; each finishes
    # before the next starts, so duplicate addresses keep the last write
    t = pl.program_id(0)
    a = idx_ref[t]
    cp = pltpu.make_async_copy(v_ref.at[t], t_ref.at[ba_fn(a), bo_fn(a)],
                               sem)
    cp.start()
    cp.wait()


def banked_scatter(table: jax.Array, indices: jax.Array, values: jax.Array,
                   ba_fn: Callable, bo_fn: Callable, *,
                   interpret=False) -> jax.Array:
    """Write logical rows into bank-major storage -- the write-path
    analogue of :func:`banked_gather`.

    table: (N_banks, bank_volume, D); indices: (T,) flat logical
    addresses; values: (T, D) replacement rows.  Returns the updated
    table; the table operand is aliased to the output
    (``input_output_aliases``), so untouched slots carry over and
    duplicate indices resolve last-write-wins (sequential grid order).
    The BA/BO resolution arithmetic addresses each row DMA -- in front of
    the memory, exactly like the gather.
    """
    T = indices.shape[0]
    D = table.shape[-1]
    tiles = _to_tiles(table)
    out = pl.pallas_call(
        lambda *refs: _scatter_kernel(ba_fn, bo_fn, *refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, table.dtype),
        input_output_aliases={2: 0},     # operand order: idx, values, table
        interpret=interpret,
    )(indices.astype(jnp.int32), _to_tiles(values.astype(table.dtype)),
      tiles)
    return _from_tiles(out, D)


def _scatter_elem_kernel(ba_fn, bo_fn, idx_ref, col_ref, v_ref, t_in, t_ref,
                         row, sem):
    # read the resolved row into VMEM, replace one element, write it back;
    # each write lands before the next read, so rows shared by several
    # elements keep every write and duplicates resolve last-write-wins
    t = pl.program_id(0)
    a = idx_ref[t]
    slot = t_ref.at[ba_fn(a), bo_fn(a)]
    cp = pltpu.make_async_copy(slot, row, sem)
    cp.start()
    cp.wait()
    L, C = row.shape
    pos = (jax.lax.broadcasted_iota(jnp.int32, (L, C), 0) * C
           + jax.lax.broadcasted_iota(jnp.int32, (L, C), 1))
    row[...] = jnp.where(pos == col_ref[t], v_ref[t].astype(row.dtype),
                         row[...])
    cp = pltpu.make_async_copy(row, slot, sem)
    cp.start()
    cp.wait()


def banked_scatter_elems(table: jax.Array, indices: jax.Array,
                         cols: jax.Array, values: jax.Array,
                         ba_fn: Callable, bo_fn: Callable, *,
                         interpret=False) -> jax.Array:
    """Scatter single elements: ``table[ba(i), bo(i), cols[t]] = values[t]``.

    The column index and the value are prefetched alongside the logical
    address, so a batch of per-slot token-record writes (the serving
    runtime's decode tick) lands in ONE kernel launch; each element
    moves its row through VMEM on the device, never through the host.
    Same aliasing / last-write-wins semantics as :func:`banked_scatter`.
    """
    T = indices.shape[0]
    D = table.shape[-1]
    tiles = _to_tiles(table)
    scalar = (jnp.int32 if jnp.issubdtype(table.dtype, jnp.integer)
              else jnp.float32)
    out = pl.pallas_call(
        lambda *refs: _scatter_elem_kernel(ba_fn, bo_fn, *refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(tiles.shape[2:], table.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, table.dtype),
        input_output_aliases={3: 0},     # idx, cols, values, table
        interpret=interpret,
    )(indices.astype(jnp.int32), cols.astype(jnp.int32),
      values.astype(scalar), tiles)
    return _from_tiles(out, D)
