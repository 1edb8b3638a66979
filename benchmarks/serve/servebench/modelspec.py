"""A configuration file, read into the sizes the benchmark works with.

The file keeps the published ``config.json`` keys at its top level, as
run (a cut key holds the value run, and is named in ``reduced``), plus
``assumed`` (sizes the published file does not state), ``serve`` (slots,
cache length and page of the served deployment), ``limits`` (what the
correctness comparison allows), ``reference`` (the file of its plain
reference, whose ``compare`` decides ``correct`` and whose ``leaves``, where
it defines one, gives the parameter tree) and, where the program needs
options to run this model, ``program`` (fields of the program's
``ArchConfig`` and their values).  The benchmark's reference, weights and
operation counts read this object, and a reference reads keys it alone
knows from ``raw``; only the harness turns it into the program's own
configuration type.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict


@dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int                 # dense feed-forward width (0 for MoE layers)
    vocab: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    experts: int              # 0 for a dense model
    top_k: int
    expert_ff: int
    norm_topk: bool
    slots: int
    max_len: int
    page: int
    limits: Dict[str, float]
    raw: Dict[str, Any] = field(default_factory=dict, compare=False,
                                repr=False)     # the file as read

    @property
    def moe(self) -> bool:
        return self.experts > 0


def load_spec(path: Path) -> ModelSpec:
    return spec_from_dict(json.loads(Path(path).read_text()))


def spec_from_dict(c: Dict[str, Any]) -> ModelSpec:
    assumed = c.get("assumed", {})

    def key(name):
        if name in c:
            return c[name]
        if name in assumed:
            return assumed[name]["value"]
        raise KeyError(f"configuration {c.get('name')!r} has no {name!r}")

    experts = int(c.get("num_experts", 0))
    heads = int(key("num_attention_heads"))
    d = int(key("hidden_size"))
    head_dim = int(c.get("head_dim") or assumed.get("head_dim", {}).get(
        "value") or d // heads)
    serve = c["serve"]
    return ModelSpec(
        name=c["name"], layers=int(key("num_hidden_layers")), d_model=d,
        heads=heads, kv_heads=int(key("num_key_value_heads")),
        head_dim=head_dim,
        d_ff=0 if experts else int(key("intermediate_size")),
        vocab=int(key("vocab_size")), rope_theta=float(key("rope_theta")),
        norm_eps=float(key("rms_norm_eps")), qkv_bias=bool(key("qkv_bias")),
        experts=experts,
        top_k=int(c.get("num_experts_per_tok", 0)),
        expert_ff=int(key("intermediate_size")) if experts else 0,
        norm_topk=bool(c.get("norm_topk_prob", False)),
        slots=int(serve["slots"]), max_len=int(serve["max_len"]),
        page=int(serve["page"]), limits=dict(c.get("limits", {})), raw=c,
    )
