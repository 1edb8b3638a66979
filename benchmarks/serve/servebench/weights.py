"""Seeded weights, made on the device in one jitted call, in the types
they are served in.

The tree uses the serving program's leaf names and layer stacking (the
layers stacked on a leading axis), so it can be handed to the program as
its parameters; the reference reads the same tree.  A configuration's
reference may define the tree itself, as ``leaves(spec)`` in the form of
:func:`leaves`; the weights and the operation counts then follow it.  Norm gains are
stored as ``gain - 1`` (the program's convention: a zero leaf is a unit
gain).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

from .modelspec import ModelSpec
from .spec import load_reference

# leaf name -> (shape, dtype name, standard deviation)
Leaves = Dict[str, Tuple[Tuple[int, ...], str, float]]

NORM_STD = 0.1     # spread of the stored gain - 1
BIAS_STD = 0.1


def leaves(s: ModelSpec) -> Dict[str, Leaves]:
    L, D, V = s.layers, s.d_model, s.vocab
    Q, KV = s.heads * s.head_dim, s.kv_heads * s.head_dim
    bf = "bfloat16"
    top: Leaves = {
        "embed": ((V, D), bf, 1.0),
        "ln_f": ((D,), bf, NORM_STD),
        "lm_head": ((V, D), bf, 1 / math.sqrt(D)),
    }
    layer: Leaves = {
        "ln1": ((L, D), bf, NORM_STD),
        "ln2": ((L, D), bf, NORM_STD),
        "wq": ((L, D, Q), bf, 1 / math.sqrt(D)),
        "wk": ((L, D, KV), bf, 1 / math.sqrt(D)),
        "wv": ((L, D, KV), bf, 1 / math.sqrt(D)),
        "wo": ((L, Q, D), bf, 1 / math.sqrt(Q)),
    }
    if s.qkv_bias:
        layer.update({"bq": ((L, Q), bf, BIAS_STD),
                      "bk": ((L, KV), bf, BIAS_STD),
                      "bv": ((L, KV), bf, BIAS_STD)})
    if s.moe:
        E, F = s.experts, s.expert_ff
        layer.update({
            "router": ((L, D, E), "float32", 1 / math.sqrt(D)),
            "we_gate": ((L, E, D, F), bf, 1 / math.sqrt(D)),
            "we_up": ((L, E, D, F), bf, 1 / math.sqrt(D)),
            "we_down": ((L, E, F, D), bf, 1 / math.sqrt(F)),
        })
    else:
        F = s.d_ff
        layer.update({"w_gate": ((L, D, F), bf, 1 / math.sqrt(D)),
                      "w_up": ((L, D, F), bf, 1 / math.sqrt(D)),
                      "w_down": ((L, F, D), bf, 1 / math.sqrt(F))})
    return {"top": top, "layers": layer}


def model_leaves(s: ModelSpec) -> Dict[str, Leaves]:
    """The tree of ``s``: its reference's ``leaves(spec)`` where the
    reference defines one, else :func:`leaves`."""
    return getattr(load_reference(s.raw), "leaves", leaves)(s)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number; bits above 32 are
    folded in rather than dropped."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(s: ModelSpec, seed: int):
    """The whole tree from ``seed``, generated on the default device."""
    import jax
    import jax.numpy as jnp

    spec = model_leaves(s)
    names = sorted(spec["top"]) + [f"layers/{n}" for n in
                                   sorted(spec["layers"])]

    def one(key, name):
        shape, dt, std = (spec["layers"][name[7:]] if name.startswith(
            "layers/") else spec["top"][name])
        dtype = jnp.dtype(dt)
        x = jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)
        return x.astype(dtype)

    @jax.jit
    def gen(key):
        keys = jax.random.split(key, len(names))
        out = {"layers": {}}
        for k, name in zip(keys, names):
            if name.startswith("layers/"):
                out["layers"][name[7:]] = one(k, name)
            else:
                out[name] = one(k, name)
        return out

    return gen(seed_key(seed))


def check_tree(program, benchmark) -> None:
    """Raise unless two trees of shapes agree leaf by leaf (name, shape,
    dtype): the program's parameter layout has to be the one the
    benchmark makes.  The message names each leaf that differs."""
    import jax

    def flat(tree):
        return {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}

    a, b = flat(program), flat(benchmark)
    if a != b:
        differ = [f"{k}: program {a[k]}, benchmark {b[k]}"
                  for k in sorted(set(a) & set(b)) if a[k] != b[k]]
        raise ValueError(
            f"the program's parameter tree differs from the benchmark's: "
            f"leaves the program lacks {sorted(set(b) - set(a))}, leaves "
            f"the benchmark lacks {sorted(set(a) - set(b))}, leaves that "
            f"differ {differ[:8]}")
