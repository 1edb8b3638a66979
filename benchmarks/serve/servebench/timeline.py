"""End-to-end metrics from the host's record of a window.

Every statistic here is taken over all the samples or all the time of
the window: no median of chunks, no per-tick medians.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Tick:
    start: float          # host clock, seconds
    end: float
    calls: int            # model-step calls made during the tick
    tokens: int           # output tokens appended during the tick
    prefill_tokens: int   # prompt tokens admitted during the tick
    layout: str = ""      # KV layout serving the tick


@dataclass
class ReqTimes:
    uid: int
    due: Optional[float]              # host clock; None for warm requests
    token_ticks: List[int] = field(default_factory=list)   # tick per token


def whole_tick_rate(ticks: Sequence[Tick], t_open: float,
                    t_close: float) -> Optional[float]:
    """Output tokens per second over the ticks that start and end inside
    the window, from the first such tick's start to the last one's end.
    A tick cut by either edge counts neither its tokens nor its time."""
    whole = [t for t in ticks if t.start >= t_open and t.end <= t_close]
    if not whole:
        return None
    span = whole[-1].end - whole[0].start
    if span <= 0:
        return None
    return sum(t.tokens for t in whole) / span


def percentile(samples: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile of all samples (linear interpolation)."""
    if len(samples) == 0:
        return None
    x = np.sort(np.asarray(samples, np.float64))
    rank = p / 100.0 * (len(x) - 1)
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    if np.isinf(x[hi]):            # the percentile lies among the infinite
        return float("inf")
    return float(x[lo] + (x[hi] - x[lo]) * (rank - lo))


def beyond(samples: Sequence[float], value: float) -> int:
    return int(np.sum(np.asarray(samples) > value))


def ttft(reqs: Sequence[ReqTimes], ticks: Sequence[Tick], t_open: float,
         t_close: float) -> List[float]:
    """Seconds from each request's due time to the end of the tick in
    which its first output token appeared, for every request due inside
    the window.  A request that never got a token counts as infinite."""
    out = []
    for r in reqs:
        if r.due is None or not (t_open <= r.due < t_close):
            continue
        if r.token_ticks:
            out.append(ticks[r.token_ticks[0]].end - r.due)
        else:
            out.append(float("inf"))
    return out


def itl(reqs: Sequence[ReqTimes], ticks: Sequence[Tick], t_open: float,
        t_close: float):
    """Every gap between consecutive tokens of a request, both inside the
    window (token time = end of its tick).  Returns the gaps in seconds
    and, for each, whether its later tick admitted a prompt (a gap that
    holds another request's prefill)."""
    gaps, stalled = [], []
    for r in reqs:
        for a, b in zip(r.token_ticks, r.token_ticks[1:]):
            ta, tb = ticks[a].end, ticks[b].end
            if t_open <= ta and tb <= t_close:
                gaps.append(tb - ta)
                stalled.append(any(ticks[i].prefill_tokens > 0
                                   for i in range(a + 1, b + 1)))
    return gaps, stalled


def end_to_end(names: Sequence[str], ticks: Sequence[Tick],
               reqs: Sequence[ReqTimes], t_open: float, t_close: float,
               setup_s: float) -> Dict[str, Optional[float]]:
    """The named end-to-end metrics, in their units."""
    gaps, _ = itl(reqs, ticks, t_open, t_close)
    firsts = ttft(reqs, ticks, t_open, t_close)
    table = {
        "output_tokens_per_s": lambda: whole_tick_rate(ticks, t_open,
                                                       t_close),
        "ttft_p50_s": lambda: percentile(firsts, 50),
        "itl_p50_ms": lambda: _ms(percentile(gaps, 50)),
        "itl_p95_ms": lambda: _ms(percentile(gaps, 95)),
        "setup_s": lambda: setup_s,
    }
    return {n: table[n]() for n in names}


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3
