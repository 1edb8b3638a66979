"""What each step call did for the requests, from the run's record: the
requests it advanced and how many of their own tokens each holds."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .costs import step_cost
from .peaks import roofline_seconds


def call_work(run) -> Dict[int, List[Tuple[int, int]]]:
    """call -> [(slot, own tokens including the one fed)] for every call
    that advanced a request: each prefill call advances its request by a
    prompt token, each decode call every active request by one."""
    work: Dict[int, List[Tuple[int, int]]] = {}
    for r in run.reqs:
        if r.first_call is None or not r.token_slots:
            continue
        slot, n = r.token_slots[0], len(r.prompt)
        p0 = r.first_call - n + 1
        for i in range(n):
            work.setdefault(p0 + i, []).append((slot, i + 1))
        for j, c in enumerate(r.token_calls):
            work.setdefault(c, []).append((slot, n + 1 + j))
    return work


def experts_used(run, call: int, slots: List[int]) -> Optional[List[int]]:
    """Distinct experts per layer that the reference routes the call's
    advanced tokens to (None without a routing record)."""
    if run.routing is None:
        return None
    sel = run.routing[:, slots, call, :]          # (L, n, k)
    return [len(np.unique(sel[layer])) for layer in range(sel.shape[0])]


def window_calls(run) -> range:
    return range(run.first_window_call, run.window_calls_end)


def window_need_s(run):
    """Least seconds every step call in the window needs, summed."""
    work = call_work(run)
    need = 0.0
    for c in window_calls(run):
        items = work.get(c, [])
        if not items:
            continue
        slots = [s for s, _ in items]
        experts = experts_used(run, c, slots) if run.spec.moe else None
        if run.spec.moe and experts is None:
            return None
        need += roofline_seconds(*step_cost(
            run.spec, [n for _, n in items], experts), run.peaks)
    return need


def window_ticks(run):
    """The ticks that started inside the window."""
    return [t for t in run.ticks if run.t_open <= t.start < run.t_close]


def window_span(run) -> float:
    """From the window's opening to the end of its last tick: the stretch
    the trace covers."""
    ticks = window_ticks(run)
    end = max([run.t_close] + [t.end for t in ticks])
    return end - run.t_open
