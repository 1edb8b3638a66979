"""Pipeline parallelism (GPipe schedule) via shard_map + collective_permute.

An optional distribution mode: layers are split into S contiguous stages
along a 'stage' mesh axis; microbatches stream through with the classic
(M + S - 1)-tick schedule.  Activations hop stages with
``jax.lax.ppermute`` -- the TPU-native point-to-point.

This is deliberately generic: ``stage_fn(stage_params, x)`` applies one
stage's layer stack; the host model provides stacked per-stage params
(reshape of the scan-stacked (L, ...) arrays into (S, L/S, ...)).

Used by examples/pipeline_train.py and tests/test_pipeline.py; the main
dry-run meshes use DP x TP (the pod axis is pure DP), PP is the documented
alternative for slower inter-pod links (DESIGN.md Sec 5).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,       # leaves (S, ...) -- stage-major
                   x: jax.Array,            # (M, mb, ...) microbatched input
                   mesh: Mesh, axis: str = "stage") -> jax.Array:
    """Run a GPipe pipeline over mesh axis `axis`.  Returns (M, mb, ...)."""
    S = mesh.shape[axis]
    M = x.shape[0]
    n_ticks = M + S - 1

    def per_stage(params, xs):
        # params: (1, ...) this stage's slice; xs: (M, mb, ...) only stage 0
        # consumes real inputs, everything else starts from zeros.
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)           # activation in flight
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)   # stage S-1 collects

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if t < M), others use incoming
            feed = jnp.where(t < M, xs[jnp.minimum(t, M - 1)], 0.0)
            h_in = jnp.where(sid == 0, feed, buf)
            h_out = stage_fn(params, h_in)
            # pass to next stage
            perm = [(i, i + 1) for i in range(S - 1)]
            buf_next = jax.lax.ppermute(h_out, axis, perm)
            # last stage emits microbatch t - (S - 1)
            emit_idx = t - (S - 1)
            emit = jnp.logical_and(sid == S - 1, emit_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out.astype(o.dtype), jnp.maximum(emit_idx, 0), 0),
                lambda o: o,
                outs)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage ever writes outs; psum == broadcast to all
        outs = jax.lax.psum(outs, axis)
        return outs

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),     # params stage-sharded, x replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
