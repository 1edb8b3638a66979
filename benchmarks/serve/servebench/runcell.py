"""One run of one cell: weights, set-up, window, comparison, metrics."""

from __future__ import annotations

import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import harness, timeline, trace as tr
from .modelspec import ModelSpec
from .spec import Cell, load_reader, load_reference
from .traffic import generate
from .weights import check_tree, make_weights
from .work import window_span


GAP_NUMBERS = ("logit_gap", "logit_gap_mean", "argmax_miss_share",
               "near_tie_share")


def gap_numbers(gaps: np.ndarray, margin: Optional[np.ndarray] = None,
                route_tie: float = 0.0) -> Dict[str, Optional[float]]:
    """The widest gap, the mean gap, and the share of tokens that are not
    the reference's first choice.  Given the route margin of each token
    (a mixture of experts), a token whose margin is under ``route_tie``
    is near-tied: the widest gap leaves it out, and ``near_tie_share``
    is the share left out; the mean and the share missed cover every
    token."""
    out = {"logit_gap": float(np.max(gaps)),
           "logit_gap_mean": float(np.mean(gaps)),
           "argmax_miss_share": float(np.mean(gaps > 0))}
    if margin is not None:
        tied = margin < route_tie
        out["logit_gap"] = float(np.max(gaps[~tied])) if not tied.all() \
            else None
        out["near_tie_share"] = float(np.mean(tied))
    return out


def reduce_trace(run: harness.Run, trace_dir: str) -> Dict[str, Any]:
    """Device busy time, idle gaps by host activity and the top device
    operations over the traced window."""
    ops, spans, devices = tr.read_xplane(tr.find_xplane(Path(trace_dir)),
                                         ["bench.tick", "bench.submit"])
    offset = tr.clock_offset(run.span_log, spans)
    if offset is None:
        return {"ops": ops, "devices": devices, "offset": None}
    t0 = run.t_open + offset
    t1 = t0 + window_span(run)
    compiles = [(a + offset, b + offset) for a, b in run.compile_intervals]
    gaps = tr.idle_gaps(ops, t0, t1)
    by_host = tr.attribute(gaps, spans, compiles)
    return {
        "ops": ops, "spans": spans, "devices": max(1, devices),
        "offset": offset, "t0": t0, "t1": t1, "window_s": t1 - t0,
        "busy_s": tr.busy(ops, t0, t1),
        "idle_gaps": [[k, v] for k, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])[:10]],
        "device_ops": tr.top_ops(ops, t0, t1, 10),
    }


def run_cell(cell: Cell, spec: ModelSpec, mix: Dict[str, Any], seed: int,
             seconds: float, trace: bool, control: bool, peaks,
             t_start: float, device: Dict[str, Any],
             fault: Optional[Callable] = None
             ) -> Tuple[Dict[str, Any], List[str]]:
    """Serve the cell once.  Returns the result object and the lines
    printed before it (the numbers compared come last).  ``fault``, for
    tests, is given the server before set-up and may break its path."""
    import jax

    from repro.runtime.server import Request

    info: List[str] = []
    run = harness.Run(spec, mix, seconds, t_start=t_start, peaks=peaks)
    reference = load_reference(spec.raw)
    params = make_weights(spec, seed)
    check_tree(harness.expected_tree(spec), params)
    jax.block_until_ready(params)
    rec = harness.Recorder()
    server, ticket, service, t_submit = harness.build(spec, params)
    if fault is not None:
        fault(server)
    plans = generate(mix, seed, seconds, spec.vocab)
    with tempfile.TemporaryDirectory() as tdir:
        harness.serve(run, server, ticket, t_submit, plans,
                      harness.tracked_class(Request), rec,
                      tdir if trace else None)
        run.memory_peak_bytes = harness.memory_peak(cell.chips)
        harness.collect(run, rec)
        if trace:
            run.trace = reduce_trace(run, tdir)
    harness.release(server, service, rec)
    del server

    # -- the comparison ------------------------------------------------------
    t_cmp = time.perf_counter()
    s = harness.served(run)
    positions = run.rows.shape[1]
    checks: Dict[str, Dict[str, float]] = {}
    # near_tie_share exists only where the reference gives route margins
    found: Dict[str, Optional[float]] = dict.fromkeys(GAP_NUMBERS[:3])
    judged = found
    if positions <= spec.max_len and len(s.tokens):
        out = reference.compare(spec, params, run.rows, s.calls, s.slots,
                                s.tokens, control=control)
        run.routing = out["routing"]
        margin = out.get("margin")
        tie = float(spec.limits.get("route_tie", 0.0))
        judged = found = gap_numbers(out["gap"], margin, tie)
        if margin is not None:
            info.append(
                f"routes: smallest margin {float(np.min(margin))!r}, "
                f"largest bfloat16 shift "
                f"{float(np.max(out['route_shift']))!r}; "
                f"{int(np.sum(margin < tie))} of {len(margin)} tokens "
                f"near-tied (margin under route_tie {tie!r})")
        if control:
            # the control takes the program's place in the comparison
            judged = gap_numbers(out["control_gap"], margin, tie)
            info.append("control (float8 linear layers) against the "
                        "reference, in the program's place: " + ", ".join(
                            f"{k} {v!r}" for k, v in judged.items()))
    del params
    info.append("program against the reference: " + ", ".join(
        f"{k} {v!r}" for k, v in found.items()))
    for name in GAP_NUMBERS:
        if name in spec.limits:
            checks[name] = {"value": judged.get(name),
                            "limit": float(spec.limits[name])}
    checks["record_mismatches"] = {"value": s.record_mismatches, "limit": 0}
    checks["feed_mismatches"] = {"value": s.feed_mismatches, "limit": 0}
    checks["step_mismatches"] = {"value": s.step_mismatches, "limit": 0}
    checks["positions"] = {"value": positions, "limit": spec.max_len}
    correct = bool(len(s.tokens)) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    info.append(f"compared {len(s.tokens)} served tokens of "
                f"{sum(1 for r in run.reqs if r.out)} requests against the "
                f"float32 reference in {time.perf_counter() - t_cmp:.1f} s; "
                f"record path checked {s.record_checks} decode inputs")
    info.append("window tokens by KV layout: " + ", ".join(
        f"{k}: {v}" for k, v in run.layouts.items()))

    # -- metrics -------------------------------------------------------------
    reqs = run.req_times()
    gaps, stalled = timeline.itl(reqs, run.ticks, run.t_open, run.t_close)
    firsts = timeline.ttft(reqs, run.ticks, run.t_open, run.t_close)
    wticks = [t for t in run.ticks if run.t_open <= t.start < run.t_close]
    p95 = timeline.percentile(gaps, 95)
    info.append(f"window: {len(wticks)} ticks started, "
                f"{sum(t.tokens for t in wticks)} tokens, "
                f"{run.compiles_in_window} backend compilations "
                f"({run.cache_reads} read from the persistent cache, "
                f"{run.slow_compiles} of {run.cache_threshold_s:g} s or more, "
                f"the longest "
                f"{run.longest_compile_s:.3f} s); "
                f"step calls {positions} of max_len {spec.max_len}")
    if len(wticks) >= 8:
        q = len(wticks) // 4
        info.append("tick seconds, first and last quarter of the window: "
                    f"{np.mean([t.end - t.start for t in wticks[:q]]):.4f}, "
                    f"{np.mean([t.end - t.start for t in wticks[-q:]]):.4f}")
    info.append(f"itl samples {len(gaps)}, beyond p95 "
                f"{timeline.beyond(gaps, p95) if p95 is not None else 0}, "
                f"stalled gaps (holding a prefill) "
                f"{sum(stalled)}/{len(gaps)} = "
                f"{(100.0 * sum(stalled) / len(gaps)) if gaps else 0:.2f}%")
    info.append(f"ttft samples {len(firsts)} (requests due in the window), "
                f"without a first token "
                f"{sum(1 for x in firsts if x == float('inf'))}")
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        names = [m["name"] for m in cell.end_to_end]
        values = timeline.end_to_end(names, run.ticks, reqs, run.t_open,
                                     run.t_close, run.setup_s)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for name in names:
            if values[name] is not None and np.isfinite(values[name]):
                metrics[name] = {"value": float(values[name]),
                                 "unit": units[name]}
    device = dict(device, memory_peak_bytes=int(run.memory_peak_bytes))
    result: Dict[str, Any] = {
        "correct": correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace and run.trace.get("offset") is not None:
        device["busy_s"] = run.trace["busy_s"] / run.trace["devices"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    return result, info


def check_lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {k} = {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]
