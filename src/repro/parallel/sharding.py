"""Banking-solver -> PartitionSpec bridge.

Device-level banking (DESIGN.md Sec 2): a tensor accessed by the unrolled
lanes of data/tensor/expert-parallel execution is an array accessed by a
concurrent access group; mesh axes are banks.  For each tensor *role* we
pose the corresponding 1-D banking problem to the solver -- lanes = mesh
axis size, array dim = the candidate partition dim -- and accept the
partition dimension whose hyperplane (N = axis size, B = 1, alpha = unit)
is conflict-free with fan-out 1 (each lane owns one shard: no crossbar =
no collective on the access path).  Dims that cannot bank conflict-free
(e.g. 8 kv heads across a 16-way axis) fall back to the next candidate dim
-- precisely the paper's 'many valid geometries, pick the cheap one'.

The result is memoized per (role, dims, axis size) and the underlying
banking problems are **submitted through the shared PlanService** (the
same submit -> ticket front door the serving runtime uses, so lane
problems share its plan store and in-flight dedup); the qualifying scheme
comes back as a **compiled artifact** (``core.artifact.lane_compile``)
whose ``to_partition_spec`` supplies the mesh-axis placement -- no
geometry reverse-engineering here.  The same compiled artifacts drive the
Pallas banked-gather kernel, so device-level and kernel-level banking
share one solver *and* one lowering.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeConfig
from ..core.artifact import CompiledBankingPlan, lane_compile
from ..core.controller import AccessDecl, Counter, Ctrl, Program, Sched
from ..core.polytope import Affine, MemorySpec
from ..core.service import default_service
from ..core.solver import SolverOptions


@functools.lru_cache(maxsize=None)
def lane_artifact(dim_size: int, lanes: int) -> Optional[CompiledBankingPlan]:
    """Compiled conflict-free FO=1 lane banking of `dim_size`, or None.

    Poses the canonical strided access problem to the banking planner:
    lanes read disjoint contiguous blocks.  Equivalent to lanes | dim
    (block scheme), but answered by the solver so the decision is the
    paper's -- and returned as the compiled artifact whose
    ``to_partition_spec`` places the banked dim on a mesh axis.
    """
    if lanes <= 1 or dim_size < lanes or dim_size % lanes:
        return None
    blk = dim_size // lanes
    mem = MemorySpec("t", dims=(dim_size,), ports=1)
    # lane l owns the contiguous block [l*blk, (l+1)*blk): outer counter
    # supplies the lane, the inner synchronized counter the offset.
    prog = Program(
        root=Ctrl("rd", Sched.INNER,
                  counters=[Counter("o", 0, 1, lanes, par=lanes),
                            Counter("j", 0, 1, blk)],
                  accesses=[AccessDecl("t", (Affine.of(o=blk, j=1),))]),
        memories={"t": mem},
    )
    opts = SolverOptions(max_solutions=4, n_budget=8,
                         b_candidates=(blk, 1) if blk > 1 else (1,),
                         allow_multidim=False, allow_duplication=False)
    # submit -> await through the shared service: lane problems share the
    # serving runtime's plan store, cache, and in-flight dedup
    plan = default_service().submit(prog, "t", opts=opts).result()
    return lane_compile(plan, lanes)


def bankable(dim_size: int, lanes: int) -> bool:
    """Can `dim_size` be banked conflict-free FO=1 across `lanes` lanes?"""
    return lanes <= 1 or lane_artifact(dim_size, lanes) is not None


def first_bankable(dims: Sequence[int], candidates: Sequence[int],
                   lanes: int) -> Optional[int]:
    for d in candidates:
        if d < len(dims) and bankable(dims[d], lanes):
            return d
    return None


# ---------------------------------------------------------------------------
# Mesh-axis vocabulary
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

def tp_axis(mesh: Mesh) -> str:
    return "model"


# ---------------------------------------------------------------------------
# Parameter sharding rules (driven by `bankable`)
# ---------------------------------------------------------------------------


def _param_spec(path: str, shape: Tuple[int, ...], tp_size: int,
                fsdp_size: int, fsdp: bool,
                fsdp_axes: Tuple[str, ...] = ("data",)) -> P:
    """Choose (tp_dim, fsdp_dim) for one parameter by role."""
    nd = len(shape)
    name = path.split("/")[-1]

    # role table: candidate tp dims (relative to trailing dims), then fsdp
    reversed_candidates = {
        # attention
        "wq": (0,), "wk": (0,), "wv": (0,), "wo": (1,),
        "bq": (0,), "bk": (0,), "bv": (0,),
        # mlps (gate/up shard F=last, down shards F=first-of-trailing-2)
        "w_gate": (0,), "w_up": (0,), "w_down": (1,),
        "b_up": (0,), "b_down": (),
        # embeddings / heads: shard vocab
        "embed": (1,), "lm_head": (1,),
        # moe: shard experts (dim -3 of (E, D, F))
        "we_gate": (2,), "we_up": (2,), "we_down": (2,),
        "router": (0,),
        # ssm
        "in_proj": (0,), "out_proj": (1,), "conv_w": (0,), "conv_b": (0,),
        "A_log": (0,), "D_skip": (0,), "dt_bias": (0,), "gate_ln": (0,),
    }
    cands_rev = reversed_candidates.get(name, ())
    spec = [None] * nd
    for c in cands_rev:
        d = nd - 1 - c
        if d < 0:
            continue
        if tp_size <= 1:
            spec[d] = "model"   # single-lane axis: placement is free
            break
        art = lane_artifact(shape[d], tp_size)
        if art is not None:
            # the artifact's own PartitionSpec bridge places the banked
            # (single) dim of its 1-D problem on the mesh axis
            spec[d] = art.to_partition_spec("model")[0]
            break
    if fsdp:
        # ZeRO-3 style: also cut the largest remaining dim across data
        # (and pod, for optimizer state -- fsdp_axes=("data","pod"))
        fsdp_entry = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
        order = sorted(range(nd), key=lambda d: -shape[d])
        for d in order:
            if spec[d] is not None or shape[d] < 2 * fsdp_size:
                continue
            if fsdp_size <= 1:
                spec[d] = fsdp_entry
                break
            art = lane_artifact(shape[d], fsdp_size)
            if art is not None:
                spec[d] = art.to_partition_spec(fsdp_entry)[0]
                break
    return P(*spec)


def _path_join(prefix, key) -> str:
    k = getattr(key, "key", getattr(key, "name", str(key)))
    return f"{prefix}/{k}" if prefix else str(k)


def param_specs(params_shape: Any, mesh: Mesh, fsdp: bool = False,
                fsdp_axes: Tuple[str, ...] = ("data",)) -> Any:
    """PartitionSpec pytree matching a params shape-pytree."""
    tp_size = mesh.shape["model"]
    fsdp_axes = tuple(a for a in fsdp_axes if a in mesh.axis_names)
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp_axes])) \
        if fsdp_axes else 1

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, _path_join(prefix, k)) for k, v in tree.items()}
        shape = tuple(tree.shape)
        return _param_spec(prefix, shape, tp_size, fsdp_size, fsdp,
                           fsdp_axes or ("data",))

    return walk(params_shape, "")


# ---------------------------------------------------------------------------
# Batch / cache / activation sharding per shape-kind
# ---------------------------------------------------------------------------


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh) -> Dict[str, P]:
    dp = dp_axes(mesh)
    bdim = dp if shape.global_batch >= int(np.prod([mesh.shape[a] for a in dp])) \
        else dp[:1] if shape.global_batch > 1 else ()
    b = bdim if bdim else None
    specs = {"tokens": P(b, None), "labels": P(b, None)}
    if cfg.family == "encdec":
        specs["frames"] = P(b, None, None)
    return specs


def _seq_or_heads(mesh: Mesh, heads: int, long: bool) -> Tuple[Any, Any]:
    """(head_axis_spec, seq_axis_spec) for KV caches."""
    tp = "model"
    if not long and bankable(heads, mesh.shape[tp]):
        return tp, None
    return None, tp


def cache_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh) -> Any:
    """PartitionSpec pytree for the family's decode cache."""
    dp = dp_axes(mesh)
    long = shape.kind == "long_decode"
    nb = None if shape.global_batch == 1 else dp
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        h_ax, s_ax = _seq_or_heads(mesh, cfg.n_kv_heads, long)
        if long and shape.global_batch == 1:
            # B=1: spread the huge cache over every axis we have
            kv = P(None, None, tuple(a for a in (*dp, "model")), None, None)
        else:
            kv = P(None, nb, s_ax, h_ax, None)
        if fam == "moe":
            from ..models.moe import MoECache
            return MoECache(k=kv, v=kv, pos=P(), routes=P())
        from ..models.transformer import KVCache
        return KVCache(k=kv, v=kv, pos=P())
    if fam == "ssm":
        from ..models.ssm import SSMCache
        return SSMCache(conv=P(None, nb, None, "model"),
                        state=P(None, nb, "model", None, None),
                        pos=P())
    if fam == "hybrid":
        from ..models.hybrid import HybridCache
        h_ax, s_ax = _seq_or_heads(mesh, cfg.n_kv_heads, long)
        if long and shape.global_batch == 1:
            kv = P(None, None, tuple(a for a in (*dp, "model")), None, None)
        else:
            kv = P(None, nb, s_ax, h_ax, None)
        return HybridCache(conv=P(None, None, nb, None, "model"),
                           state=P(None, None, nb, "model", None, None),
                           k=kv, v=kv, pos=P())
    if fam == "encdec":
        from ..models.encdec import EncDecCache
        h_ax, s_ax = _seq_or_heads(mesh, cfg.n_kv_heads, long)
        kv = P(None, nb, s_ax, h_ax, None)
        return EncDecCache(k_self=kv, v_self=kv, k_cross=kv, v_cross=kv,
                           pos=P())
    raise ValueError(fam)


def logits_spec(mesh: Mesh, batch_sharded: bool = True) -> P:
    dp = dp_axes(mesh)
    return P(dp if batch_sharded else None, "model")
