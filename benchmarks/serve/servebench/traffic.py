"""One generator for every traffic mix: a mix is a JSON file of parameters.

Lengths come from fixed quantiles of the stated distribution, so every
seed offers the same multiset of lengths and the same number of requests;
the seed permutes their order, draws the token ids and jitters the
arrival times.  A seed therefore never changes how much work a window
holds, only its order.

A mix has two groups of requests:

* ``warm`` -- submitted during set-up.  ``until`` says how far set-up
  serves them: ``"admitted"`` (every one holds a slot when the window
  opens) or ``"finished"`` (the server is idle when it opens).
* ``load`` -- due inside the window.  ``arrival`` is ``"at_open"`` (all
  due when the window opens: an offline backlog) or ``"even"`` (open
  loop at ``rate_per_s``, evenly spaced, each moved by up to
  ``jitter / 2`` of a spacing either way); ``"at_open"`` takes a fixed
  ``requests`` count, ``"even"`` the rate times the window.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_UNIT = NormalDist()


@dataclass
class Planned:
    uid: int
    group: str            # "warm" | "load"
    prompt: np.ndarray    # int32 token ids
    max_new: int
    due_s: float          # seconds after the window opens (load only)


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_UNIT.inv_cdf(float(x)) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def load_count(mix: Dict[str, Any], seconds: float) -> int:
    load = mix["load"]
    if load["arrival"] == "at_open":
        return int(load["requests"])
    if load["arrival"] == "even":
        return max(1, int(round(load["rate_per_s"] * seconds)))
    raise ValueError(f"unknown arrival {load['arrival']!r}")


def _group(rng, spec, n, vocab, group, uid0, dues) -> List[Planned]:
    prompts = rng.permutation(quantile_lengths(spec["prompt"], n))
    outs = rng.permutation(quantile_lengths(spec["output"], n))
    return [Planned(uid=uid0 + i, group=group,
                    prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                        dtype=np.int64).astype(np.int32),
                    max_new=int(outs[i]), due_s=float(dues[i]))
            for i in range(n)]


def generate(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Planned]:
    """Every request of one run: the warm group, then the load in order
    of due time."""
    rng = np.random.default_rng([seed, 0x5E7])
    warm = mix["warm"]
    out = _group(rng, warm, int(warm["requests"]), vocab, "warm", 0,
                 np.zeros(int(warm["requests"])))
    load = mix["load"]
    n = load_count(mix, seconds)
    if load["arrival"] == "at_open":
        dues = np.zeros(n)
    else:
        gap = 1.0 / load["rate_per_s"]
        jitter = load.get("jitter", 0.0) * (rng.random(n) - 0.5)
        dues = (np.arange(n) + 0.5 + jitter) * gap
    out += _group(rng, load, n, vocab, "load", len(out), dues)
    return out


def load_mix(path: Path) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())
