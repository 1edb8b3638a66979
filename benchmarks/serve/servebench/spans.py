"""The program's own serve-loop spans, split per tick: the time a tick
spends building kernels (JAX's trace, lower and compile phases), the
time the host waits on the device, and the rest.

They are read after the run from ``repro.core.tracing.serve_tracer()``,
its recorder's rolled traces and its live ones.  Its spans are on the
program's ``perf_counter`` clock, which ``run.t_open`` and
``run.t_close`` share.  A program without that tracer gives nothing."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

TICK = "serve.tick"
WAITS = ("serve.step.wait", "serve.gather.wait")


def tick_parts(t0: float, t1: float
               ) -> Optional[List[Tuple[float, float, float]]]:
    """(seconds, build seconds, wait seconds) of every program
    ``serve.tick`` span that starts and ends inside [t0, t1]: build is
    ``build_s`` summed over the tick and every span under it, wait the
    time of its wait spans less any build inside them."""
    try:
        from repro.core.tracing import serve_tracer
    except ImportError:
        return None
    tracer = serve_tracer()
    spans = {s.span_id: s for t in tracer.recorder.traces()
             + tracer.live_traces() for s in t.spans}
    parts = {i: [s.end - s.start, 0.0, 0.0] for i, s in spans.items()
             if s.name == TICK and s.end is not None
             and t0 <= s.start and s.end <= t1}
    for s in spans.values():
        build = float(s.attrs.get("build_s", 0.0))
        wait = s.end - s.start - build if s.name in WAITS else 0.0
        if not (build or wait):
            continue
        node = s
        while node is not None and node.span_id not in parts:
            node = spans.get(node.parent_id)
        if node is not None:
            parts[node.span_id][1] += build
            parts[node.span_id][2] += wait
    return [tuple(p) for p in parts.values()] or None


def tick_means_ms(run) -> Optional[Dict[str, float]]:
    """Mean build, wait and other host milliseconds of the window's
    program ticks; the three add up to the mean tick."""
    parts = tick_parts(run.t_open, run.t_close)
    if parts is None:
        return None
    n = len(parts)
    tick, build, wait = (1e3 * sum(p[k] for p in parts) / n
                         for k in range(3))
    return {"build": build, "wait": wait, "host": tick - build - wait}
