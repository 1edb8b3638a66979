"""Family dispatch: one uniform Model interface over all ten architectures.

``get_model(cfg)`` returns a ``Model`` with:

* ``init(key)``                          -> params
* ``loss(params, batch)``                -> scalar (train objective)
* ``prefill(params, batch, max_len)``    -> (logits, cache)
* ``decode(params, cache, tokens)``      -> (logits, cache)
* ``init_cache(batch, max_len)``         -> cache
* ``input_specs(shape_cfg)``             handled by launch/dryrun.py

``vlm`` (chameleon) is the dense transformer -- its VQ image tokens live in
the shared 65536 vocabulary, frontend stubbed to token ids.  ``audio``
(whisper) adds precomputed frame embeddings to the batch.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict


from ..configs.base import ArchConfig
from . import encdec, hybrid, moe, ssm, transformer as tfm

Params = Dict[str, Any]


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    loss: Callable                  # (params, batch) -> scalar
    prefill: Callable               # (params, batch, max_len) -> (logits, cache)
    decode: Callable                # (params, cache, tokens) -> (logits, cache)
    init_cache: Callable            # (batch, max_len) -> cache


def get_model(cfg: ArchConfig, moe_impl: str = "sorted") -> Model:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return Model(
            cfg=cfg,
            init=partial(tfm.init_dense_params, cfg),
            loss=partial(tfm.lm_loss, cfg),
            prefill=lambda p, batch, max_len: tfm.prefill(
                cfg, p, batch["tokens"], max_len),
            decode=partial(tfm.decode_step, cfg),
            init_cache=partial(tfm.init_cache, cfg),
        )
    if fam == "moe":
        return Model(
            cfg=cfg,
            init=partial(moe.init_moe_params, cfg),
            loss=partial(moe.lm_loss, cfg, impl=moe_impl),
            prefill=lambda p, batch, max_len: moe.prefill(
                cfg, p, batch["tokens"], max_len, impl=moe_impl),
            decode=partial(moe.decode_step, cfg, impl=moe_impl),
            init_cache=partial(moe.init_cache, cfg),
        )
    if fam == "ssm":
        return Model(
            cfg=cfg,
            init=partial(ssm.init_params, cfg),
            loss=partial(ssm.lm_loss, cfg),
            prefill=lambda p, batch, max_len: ssm.prefill(cfg, p, batch["tokens"]),
            decode=partial(ssm.decode_step, cfg),
            init_cache=lambda batch, max_len: ssm.init_cache(cfg, batch),
        )
    if fam == "hybrid":
        return Model(
            cfg=cfg,
            init=partial(hybrid.init_params, cfg),
            loss=partial(hybrid.lm_loss, cfg),
            prefill=lambda p, batch, max_len: hybrid.prefill(
                cfg, p, batch["tokens"], max_len),
            decode=partial(hybrid.decode_step, cfg),
            init_cache=partial(hybrid.init_cache, cfg),
        )
    if fam in ("encdec", "audio"):
        return Model(
            cfg=cfg,
            init=partial(encdec.init_params, cfg),
            loss=partial(encdec.lm_loss, cfg),
            prefill=lambda p, batch, max_len: encdec.prefill(
                cfg, p, batch["frames"], batch["tokens"], max_len),
            decode=partial(encdec.decode_step, cfg),
            init_cache=None,  # cache comes from prefill (cross-KV needs frames)
        )
    raise ValueError(f"unknown family {fam}")
