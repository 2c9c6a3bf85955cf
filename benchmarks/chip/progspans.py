"""The program's own spans (``repro.tracing``, named ``wf.*``) as the
per-layer metrics read them.

The program's tracer records while a JAX profiler session collects host
events, so after a ``--trace 1`` run the spans of the traced part are in
the process's memory; they are read here without being cleared. Where the
program has no tracer, or recorded nothing, every reader returns None.

A span's times are ``perf_counter_ns``, the clock of ``run.window``. The
device trace's clock is mapped onto it by the ``bench.window`` span, whose
bounds are known on both clocks (``run.trace.window``, ``run.traced``).
"""
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import devtrace


def spans(run) -> Optional[list]:
    """The finished ``wf.*`` spans that started inside the run's window."""
    try:
        from repro import tracing
    except ImportError:
        return None
    lo, hi = run.window
    got = [s for s in tracing.peek()
           if s.name.startswith("wf.") and lo <= s.start_ns * 1e-9 <= hi]
    return got or None


def claims(sp: list) -> list:
    """The outermost ``wf.claim`` spans: a router's claim, not the
    per-shard claims inside it."""
    by_id = {s.id: s for s in sp}
    return [s for s in sp if s.name == "wf.claim"
            and not (s.parent in by_id and by_id[s.parent].name == "wf.claim")]


def task_wait_ms(sp: Optional[list]) -> Optional[float]:
    """Mean, over the tasks dispatched, of the start of the task's
    ``wf.dispatch`` minus the end of the claim that claimed it."""
    if sp is None:
        return None
    claimed: Dict[int, int] = {}
    for c in sorted(claims(sp), key=lambda s: s.start_ns):
        for t in c.attrs.get("tasks", ()):
            claimed[t] = c.end_ns
    waits = [s.start_ns - claimed[s.attrs["task"]] for s in sp
             if s.name == "wf.dispatch" and s.attrs.get("task") in claimed]
    return 1e-6 * sum(waits) / len(waits) if waits else None


def claim_cow_ms(sp: Optional[list]) -> Optional[float]:
    """Mean, over the claims, of the summed wall time of the ``wf.cow``
    spans under each (copy-on-write of frozen columns)."""
    roots = claims(sp) if sp is not None else []
    if not roots:
        return None
    by_id = {s.id: s for s in sp}
    cow = {c.id: 0 for c in roots}
    for s in sp:
        if s.name == "wf.cow":
            p = s.parent
            while p in by_id and p not in cow:
                p = by_id[p].parent
            if p in cow:
                cow[p] += s.wall_ns
    return 1e-6 * sum(cow.values()) / len(roots)


def claim_overlap_ms(sp: Optional[list]) -> Optional[float]:
    """Mean, over the claims, of the claim's wall time during which another
    thread was inside a ``wf.*`` span (a sweep, a shard's partial sweep or
    ship, the shipper's encode, send or ack): the claim's exposure to the
    other Python threads it shares the interpreter with."""
    roots = claims(sp) if sp is not None else []
    if not roots:
        return None
    mine = roots[0].thread
    other = [s for s in sp if s.thread != mine]
    if not other:
        return 0.0
    lo = min(s.start_ns for s in sp)
    span_union = devtrace._union(
        np.asarray([s.start_ns - lo for s in other], np.float64),
        np.asarray([s.end_ns - lo for s in other], np.float64),
        0.0, float(max(s.end_ns for s in sp) - lo))
    held = sorted((float(c.start_ns - lo), float(c.end_ns - lo))
                  for c in roots)
    return 1e-6 * _overlap(held, span_union) / len(roots)


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def on_trace_clock(run) -> Callable[[int], float]:
    """``perf_counter_ns`` -> seconds on the device trace's clock, by the
    traced part's bounds on both clocks."""
    (w0, w1), (p0, p1) = run.trace.window, run.traced
    scale = (w1 - w0) / (p1 - p0)
    return lambda ns: w0 + (ns * 1e-9 - p0) * scale


def dispatch_idle_ms(run, sp: Optional[list]) -> Optional[float]:
    """Device idle time of chip 0 in the traced part (holes in the union of
    its ``XLA Ops``) that the program's ``wf.batch`` and ``wf.dispatch``
    spans cover, per step dispatched in the traced part."""
    if sp is None or run.trace is None or not run.trace.ops \
            or run.traced is None:
        return None
    (w0, w1), (p0, p1) = run.trace.window, run.traced
    on_trace = on_trace_clock(run)
    launch = [s for s in sp if s.name in ("wf.batch", "wf.dispatch")
              and p0 <= s.start_ns * 1e-9 <= p1]
    steps = sum(s.name == "wf.dispatch" for s in launch)
    if not steps:
        return None
    st, en, _ = run.trace.ops[0]
    busy = devtrace._union(st, en, w0, w1)
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    host = devtrace._union(
        np.asarray([on_trace(s.start_ns) for s in launch], np.float64),
        np.asarray([on_trace(s.end_ns) for s in launch], np.float64), w0, w1)
    return 1e3 * _overlap(gaps, host) / steps
