import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) so
the XLA_FLAGS line above executes before jax initializes -- the two lines
at the top of this file are load-bearing and must stay first.

For each cell we build ShapeDtypeStruct stand-ins (no allocation), attach
NamedShardings from the banking-solver bridge, ``jit(...).lower().compile()``
against the production mesh, and record ``memory_analysis()`` /
``cost_analysis()`` plus the collective-op byte census parsed from the
compiled HLO (for EXPERIMENTS.md Roofline).

    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod --out results/dryrun
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, get_arch, _ALIASES
from ..configs.base import ArchConfig, ShapeConfig
from ..models import get_model
from ..optim import adamw
from ..parallel import sharding as shd
from ..parallel.hints import sharding_policy
from . import steps
from .mesh import make_production_mesh


def make_policy(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh
                ) -> Dict[str, P]:
    """Activation-sharding policy per cell (see parallel/hints.py).

    Attention families run Megatron-SP: residual stream sequence-sharded
    over 'model', block inputs gathered.  SSM/hybrid shard the residual on
    channels instead (the SSD chunk scan cannot have a sharded leading
    axis).  Decode shapes leave activations to propagation (seq==1).
    """
    dp = shd.dp_axes(mesh)
    pol: Dict[str, P] = {"expert_buffer": P("model", None, None)}
    if shape.kind in ("train", "prefill"):
        if cfg.family in ("ssm", "hybrid"):
            pol["residual"] = P(dp, None, "model")
        else:
            pol["residual"] = P(dp, "model", None)
            pol["block_in"] = P(dp, None, None)
        pol["logits"] = P(dp, None, "model")
    return pol

SKIPS: Dict[tuple, str] = {}
for _a in ["deepseek_67b", "qwen2_7b", "internlm2_20b", "chameleon_34b",
           "llama4_maverick", "olmoe_1b_7b", "whisper_base"]:
    SKIPS[(_a, "long_500k")] = (
        "pure full attention (or unmodelled chunked variant): long_500k "
        "needs sub-quadratic attention -- skip per assignment, DESIGN.md "
        "Arch-applicability")


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(shape_tree, spec_tree, mesh):
    return jax.tree.map(
        lambda s, p: _sds(s.shape, s.dtype, mesh, p), shape_tree, spec_tree)


def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the data batch of one step."""
    b, s = shape.global_batch, shape.seq_len
    bs = shd.batch_specs(cfg, shape, mesh)
    out = {}
    if shape.kind in ("train", "prefill"):
        out["tokens"] = _sds((b, s), jnp.int32, mesh, bs["tokens"])
        if shape.kind == "train":
            out["labels"] = _sds((b, s), jnp.int32, mesh, bs["labels"])
        if cfg.family == "encdec":
            out["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16, mesh,
                                 bs["frames"])
    else:  # decode / long_decode: one new token against a seq_len cache
        out["tokens"] = _sds((b, 1), jnp.int32, mesh,
                             P(bs["tokens"][0], None))
    return out


def cache_structs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    model = get_model(cfg)
    if cfg.family == "encdec":
        from ..models.encdec import EncDecCache
        L, B, Hkv, Dh = cfg.n_layers, shape.global_batch, cfg.n_kv_heads, cfg.hd
        kvshape = (L, B, shape.seq_len, Hkv, Dh)
        shapes = EncDecCache(
            k_self=jax.ShapeDtypeStruct(kvshape, jnp.bfloat16),
            v_self=jax.ShapeDtypeStruct(kvshape, jnp.bfloat16),
            k_cross=jax.ShapeDtypeStruct(kvshape, jnp.bfloat16),
            v_cross=jax.ShapeDtypeStruct(kvshape, jnp.bfloat16),
            pos=jax.ShapeDtypeStruct((), jnp.int32),
        )
    else:
        shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
    specs = shd.cache_specs(cfg, shape, mesh)
    return _tree_sds(shapes, specs, mesh)


def params_structs(cfg: ArchConfig, mesh: Mesh, fsdp: bool):
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    specs = shd.param_specs(shapes, mesh, fsdp=fsdp)
    return _tree_sds(shapes, specs, mesh), specs


COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n=]*=\s*([a-z0-9](?:[^\s(]*))\(", re.I)

SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|u64)"
                      r"\[([\d,]*)\]")

DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "pred": 1}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-operand bytes of every collective op in compiled HLO."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(
            r".*=\s*((?:f32|bf16|f16|s32|u32|s8|u8|pred|s64|u64|f64|tuple|\()"
            r".*?)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)", stripped)
        if not m:
            continue
        kind = m.group(2).lower()
        nbytes = 0.0
        for dt, dims in SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + nbytes
    return out


def lower_cell(arch: str, shape_name: str, mesh: Mesh, *,
               fsdp: Optional[bool] = None, block_k: int = 1024,
               variant: str = "baseline",
               bf16_opt: bool = False) -> Dict[str, Any]:
    """variant: baseline | moe_a2a (shard_map expert dispatch) |
    ring_cache (windowed local-layer KV rings -- local:global archs)."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    model = get_model(cfg, moe_impl="a2a" if variant == "moe_a2a" else "sorted")
    if fsdp is None:
        # params: model-axis sharding only unless the bf16 copy would not
        # fit comfortably per device -- then cut the data axis too (FSDP /
        # ZeRO-3).  Optimizer state is always data+model cut (ZeRO-1).
        shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        pbytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                     for s in jax.tree.leaves(shapes))
        per_dev = pbytes / mesh.shape["model"]
        fsdp = per_dev > 2 * 2**30

    policy = make_policy(cfg, shape, mesh)
    if variant == "moe_a2a":
        policy["__mesh__"] = mesh
        policy["__fsdp__"] = fsdp

    t0 = time.time()
    with jax.default_device(jax.devices()[0]), \
            sharding_policy(policy):
        if variant == "ring_cache":
            assert shape.kind in ("decode", "long_decode")
            from ..models import transformer as tfm
            p_structs, _ = params_structs(cfg, mesh, fsdp=fsdp)
            G, R = tfm.grouped_layout(cfg)
            W, Hkv, Dh = cfg.sliding_window, cfg.n_kv_heads, cfg.hd
            B = shape.global_batch
            dp = shd.dp_axes(mesh)
            nb = None if B == 1 else dp
            seq_all = tuple(a for a in (*dp, "model")) if B == 1 else "model"
            kv_loc = P(None, None, nb, "model" if B == 1 else None, None, None)
            kv_glob = P(None, nb, seq_all, None, None)
            cache_shapes = jax.eval_shape(
                lambda: tfm.init_grouped_cache(cfg, B, shape.seq_len))
            cache = _tree_sds(
                cache_shapes,
                tfm.GroupedKVCache(k_local=kv_loc, v_local=kv_loc,
                                   k_global=kv_glob, v_global=kv_glob,
                                   pos=P()),
                mesh)
            batch = input_specs(cfg, shape, mesh)

            def serve_ring(params, cache, tokens):
                logits, new_cache = tfm.grouped_decode_step(
                    cfg, params, cache, tokens)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                return nxt, logits, new_cache

            with mesh:
                lowered = jax.jit(serve_ring).lower(p_structs, cache,
                                                    batch["tokens"])
        elif shape.kind == "train":
            p_structs, p_specs = params_structs(cfg, mesh, fsdp=fsdp)
            moment_dt = jnp.bfloat16 if bf16_opt else jnp.float32
            opt_shapes = jax.eval_shape(
                lambda p: adamw.init(p, moment_dt),
                jax.tree.map(lambda s: s, p_structs))
            zaxes = ("data", "pod")  # ZeRO across every pure-DP axis
            opt_specs = adamw.AdamWState(
                step=P(),
                m=shd.param_specs(opt_shapes.m, mesh, fsdp=True,
                                  fsdp_axes=zaxes),
                v=shd.param_specs(opt_shapes.v, mesh, fsdp=True,
                                  fsdp_axes=zaxes),
                master=shd.param_specs(opt_shapes.master, mesh, fsdp=True,
                                       fsdp_axes=zaxes))
            opt_structs = _tree_sds(opt_shapes, opt_specs, mesh)
            batch = input_specs(cfg, shape, mesh)
            step_fn = steps.make_train_step(model, adamw.AdamWConfig())
            with mesh:
                lowered = jax.jit(step_fn).lower(p_structs, opt_structs, batch)
        elif shape.kind == "prefill":
            p_structs, _ = params_structs(cfg, mesh, fsdp=fsdp)
            batch = input_specs(cfg, shape, mesh)
            fn = steps.make_prefill_step(model, shape.seq_len)
            with mesh:
                lowered = jax.jit(fn).lower(p_structs, batch)
        elif variant == "int8_kv":
            assert shape.kind in ("decode", "long_decode")
            from ..models import transformer as tfm
            p_structs, _ = params_structs(cfg, mesh, fsdp=fsdp)
            base_specs = shd.cache_specs(cfg, shape, mesh)
            kv, scale = base_specs.k, P(*base_specs.k[:-1])
            cache_shapes = jax.eval_shape(
                lambda: tfm.init_quant_cache(cfg, shape.global_batch,
                                             shape.seq_len))
            cache = _tree_sds(
                cache_shapes,
                tfm.QuantKVCache(k_q=kv, v_q=kv, k_s=scale, v_s=scale,
                                 pos=P()),
                mesh)
            batch = input_specs(cfg, shape, mesh)

            def serve_q(params, cache, tokens):
                logits, new_cache = tfm.decode_step_quant(cfg, params, cache,
                                                          tokens)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                return nxt, logits, new_cache

            with mesh:
                lowered = jax.jit(serve_q).lower(p_structs, cache,
                                                 batch["tokens"])
        else:  # decode / long_decode
            p_structs, _ = params_structs(cfg, mesh, fsdp=fsdp)
            cache = cache_structs(cfg, shape, mesh)
            batch = input_specs(cfg, shape, mesh)
            fn = steps.make_serve_step(model)
            with mesh:
                lowered = jax.jit(fn).lower(p_structs, cache, batch["tokens"])
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "memory": {
            "bytes_per_device_argument": getattr(
                mem, "argument_size_in_bytes", 0),
            "bytes_per_device_output": getattr(
                mem, "output_size_in_bytes", 0),
            "bytes_per_device_temp": getattr(mem, "temp_size_in_bytes", 0),
            "bytes_per_device_peak": getattr(
                mem, "peak_memory_in_bytes",
                getattr(mem, "temp_size_in_bytes", 0)),
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "moe_a2a", "ring_cache", "int8_kv"])
    ap.add_argument("--bf16-opt", action="store_true",
                    help="bf16 Adam moments (halves optimizer HBM)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        arch = _ALIASES.get(args.arch,
                            args.arch.replace("-", "_").replace(".", "_"))
        cells.append((arch, args.shape))

    meshes = []
    if args.both_meshes:
        meshes = [False, True]
    else:
        meshes = [args.multi_pod]

    results = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        for arch, shape_name in cells:
            key = (arch, shape_name)
            tag = f"{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}"
            if key in SKIPS:
                print(f"SKIP  {tag}: {SKIPS[key]}")
                results.append({"arch": arch, "shape": shape_name,
                                "skipped": SKIPS[key]})
                continue
            try:
                r = lower_cell(arch, shape_name, mesh, variant=args.variant,
                               bf16_opt=args.bf16_opt)
                r["multi_pod"] = multi_pod
                results.append(r)
                print(f"OK    {tag}: compile={r['compile_s']}s "
                      f"flops={r['flops']:.3e} "
                      f"peak={r['memory']['bytes_per_device_peak']/2**30:.2f}GiB "
                      f"coll={ {k: round(v/2**20,1) for k,v in r['collective_bytes'].items()} }MiB")
            except Exception as e:
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape_name,
                                "multi_pod": multi_pod, "error": str(e)[:500]})
                print(f"FAIL  {tag}: {e}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
