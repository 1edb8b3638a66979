"""Expert layer: the share of the expert weights the window's decode steps
read that their tokens are routed to.  100 x the experts routed to over
the experts whose weights the expert layer multiplies, each summed over
layers and over the ``serve.step`` spans of the program ticks that start
and end inside the window (span attributes ``moe_experts_routed`` and
``moe_experts_read``).  Nothing where the program's steps carry no such
attributes."""

from servebench.spans import TICK

STEP = "serve.step"
ROUTED, READ = "moe_experts_routed", "moe_experts_read"


def read(run):
    try:
        from repro.core.tracing import serve_tracer
    except ImportError:
        return None
    tracer = serve_tracer()
    spans = {s.span_id: s for t in tracer.recorder.traces()
             + tracer.live_traces() for s in t.spans}
    ticks = {i for i, s in spans.items() if s.name == TICK
             and s.end is not None
             and run.t_open <= s.start and s.end <= run.t_close}
    routed = read = 0
    for s in spans.values():
        if s.name != STEP or READ not in s.attrs:
            continue
        node = s
        while node is not None and node.span_id not in ticks:
            node = spans.get(node.parent_id)
        if node is not None:
            routed += s.attrs[ROUTED]
            read += s.attrs[READ]
    return 100.0 * routed / read if read else None
