"""Reduction from a profiler trace to device busy time, idle gaps by what
the host was doing, and device time by kernel.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
A device plane's ``XLA Ops`` line gives the operations that ran and its
``XLA Modules`` line the programs they belong to.  Host spans are the
benchmark's own ``TraceAnnotation``s, read from the host plane; backend
compilations come from JAX's monitoring events and are placed on the
trace's clock by the offset between the benchmark's clock and its spans
in the trace.  All times here are seconds on the trace's clock.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

COMPILE = "backend_compile"
NO_SPAN = "no_host_span"


@dataclass(frozen=True)
class DeviceOp:
    name: str
    module: str
    start: float
    end: float


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def busy(ops: Sequence[DeviceOp], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which some operation ran on the device."""
    return sum(b - a for a, b in merge(clip(((o.start, o.end) for o in ops),
                                            t0, t1)))


def idle_gaps(ops: Sequence[DeviceOp], t0: float, t1: float
              ) -> List[Interval]:
    """The stretches of [t0, t1] in which no operation ran."""
    gaps, cur = [], t0
    for a, b in merge(clip(((o.start, o.end) for o in ops), t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def attribute(gaps: Sequence[Interval], spans: Sequence[Span],
              compiles: Sequence[Interval]) -> Dict[str, float]:
    """Split idle time by what the host was doing: a backend compilation
    first, else the innermost (shortest) benchmark span covering it,
    else ``no_host_span``."""
    out: Dict[str, float] = defaultdict(float)
    cuts = sorted({p for s in spans for p in (s.start, s.end)}
                  | {p for c in compiles for p in c})
    comp = merge(compiles)
    # one label per stretch between consecutive cut points
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(c0 <= mid < c1 for c0, c1 in comp):
            labels.append(COMPILE)
            continue
        cover = [s for s in spans if s.start <= mid < s.end]
        labels.append(min(cover, key=lambda s: s.end - s.start).name
                      if cover else NO_SPAN)
    for g0, g1 in gaps:
        i = bisect.bisect_right(cuts, g0) - 1
        a = g0
        while a < g1:
            b = min(g1, cuts[i + 1]) if 0 <= i < len(cuts) - 1 else (
                g1 if i >= len(cuts) - 1 else min(g1, cuts[0]))
            label = labels[i] if 0 <= i < len(labels) else NO_SPAN
            out[label] += b - a
            a = b
            i += 1
    return dict(out)


def device_time(ops: Sequence[DeviceOp], t0: float, t1: float,
                module_prefix: Optional[str] = None,
                name_prefix: Optional[str] = None,
                exclude_module: Optional[str] = None) -> float:
    """Device time of the matching operations inside [t0, t1]: the union
    of their intervals, so an operation nested in another (the body of a
    loop inside the loop) counts once."""
    keep = [(o.start, o.end) for o in ops
            if (module_prefix is None or o.module.startswith(module_prefix))
            and (name_prefix is None or o.name.startswith(name_prefix))
            and (exclude_module is None
                 or not o.module.startswith(exclude_module))]
    return sum(b - a for a, b in merge(clip(keep, t0, t1)))


def self_times(ops: Sequence[DeviceOp], t0: float, t1: float
               ) -> Dict[str, float]:
    """Device time per operation (``module:name``) less the time of the
    operations nested inside it, within [t0, t1]."""
    acc: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []      # (end, key) of open parents
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        a, b = max(o.start, t0), min(o.end, t1)
        while stack and stack[-1][0] <= o.start:
            stack.pop()
        if b <= a:
            continue
        if stack:
            acc[stack[-1][1]] -= b - a
        key = f"{o.module}:{o.name}"
        acc[key] += b - a
        stack.append((o.end, key))
    return dict(acc)


def top_ops(ops: Sequence[DeviceOp], t0: float, t1: float, n: int = 10
            ) -> List[List]:
    """The ``n`` operations (``module:name``) with the most device time of
    their own."""
    acc = self_times(ops, t0, t1)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:n]]


def clock_offset(mine: Sequence[Tuple[str, float]], spans: Sequence[Span]
                 ) -> Optional[float]:
    """trace clock - benchmark clock, from spans recorded on both: the
    median difference of their starts, matched in order by name."""
    by_name: Dict[str, List[float]] = defaultdict(list)
    for s in sorted(spans, key=lambda s: s.start):
        by_name[s.name].append(s.start)
    seen: Dict[str, int] = defaultdict(int)
    diffs = []
    for name, t in mine:
        i = seen[name]
        seen[name] += 1
        if i < len(by_name[name]):
            diffs.append(by_name[name][i] - t)
    if not diffs:
        return None
    diffs.sort()
    return diffs[len(diffs) // 2]


def read_xplane(path: Path, span_names: Sequence[str]
                ) -> Tuple[List[DeviceOp], List[Span], int]:
    """Device operations, the named host spans and the number of devices
    that ran anything, from one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    ops: List[DeviceOp] = []
    spans: List[Span] = []
    devices = 0
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            op_events = lines.get("XLA Ops", [])
            if op_events:
                devices += 1
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for e in op_events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = (mods[i][2] if i >= 0 and e.start_ns < mods[i][1]
                          else "")
                ops.append(DeviceOp(_op_name(e.name), module.split("(")[0],
                                    e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in wanted:
                        spans.append(Span(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns)
                                          * 1e-9))
    return ops, spans, devices


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ")[0].strip().lstrip("%")


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]
