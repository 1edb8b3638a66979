"""Whole step: 2 x active parameters x tokens the window's step calls
processed for requests, over the window and the chip's bf16 peak."""

from servebench.costs import active_params
from servebench.work import call_work, window_calls, window_span


def read(run):
    work = call_work(run)
    tokens = sum(len(work.get(c, ())) for c in window_calls(run))
    span = window_span(run)
    if span <= 0:
        return None
    flops = 2.0 * active_params(run.spec) * tokens
    return 100.0 * flops / span / run.peaks.bf16_flops
