"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A TPU trace (``*.xplane.pb``) has one plane per chip (``/device:TPU:<i>``)
with the lines ``XLA Ops`` (one event per operation that ran) and ``XLA
Modules`` (one per compiled program that ran), and the host plane
(``/host:CPU``) whose events include the benchmark's spans, named
``bench.<layer>``, on the same clock. The window is the ``bench.window``
span around the traced ticks.

* busy time: the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips;
* a program's device time: its ``XLA Modules`` events inside the window;
* idle gaps: the holes in the busy union, each charged to the innermost
  ``bench.*`` span that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

import numpy as np

ENVELOPES = ("bench.window", "bench.tick")
OFF_THREAD = ("bench.sweep",)
# control flow whose event spans the operations of its body: left out of the
# ranking of operations (their bodies are ranked), kept in the busy union
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class TraceData:
    """Times in seconds on the trace's clock."""
    window: Tuple[float, float]
    ops: List[Tuple[np.ndarray, np.ndarray, List[str]]]   # per chip
    modules: List[Tuple[str, float, float]]               # all chips
    host: List[Tuple[str, float, float]]                  # bench.* spans


def short_op(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%")


def from_events(device_ops: List[List[Tuple[str, float, float]]],
                modules: List[Tuple[str, float, float]],
                host: List[Tuple[str, float, float]]) -> TraceData:
    """Build from (name, start_s, duration_s) events; the window is the
    ``bench.window`` host span."""
    win = [(s, s + d) for n, s, d in host if n == "bench.window"]
    if not win:
        raise ValueError("the trace has no bench.window span")
    ops = []
    for evs in device_ops:
        st = np.asarray([s for _, s, _ in evs], np.float64)
        en = st + np.asarray([d for _, _, d in evs], np.float64)
        ops.append((st, en, [short_op(n) for n, _, _ in evs]))
    return TraceData(window=win[0], ops=ops,
                     modules=[(n, s, s + d) for n, s, d in modules],
                     host=[(n, s, s + d) for n, s, d in host])


def load(trace_dir: str) -> TraceData:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device_ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events]
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                         for e in line.events if e.name.startswith("bench.")]
    return from_events(device_ops, modules, host)


def _union(st: np.ndarray, en: np.ndarray, lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Merged intervals of [st, en) clipped to [lo, hi]."""
    keep = (en > lo) & (st < hi)
    order = np.argsort(st[keep], kind="stable")
    out: List[List[float]] = []
    for a, b in zip(np.maximum(st[keep][order], lo),
                    np.minimum(en[keep][order], hi)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_and_window(td: TraceData) -> Tuple[float, float]:
    """(device busy seconds averaged over chips, window seconds)."""
    lo, hi = td.window
    busy = [sum(b - a for a, b in _union(st, en, lo, hi))
            for st, en, _ in td.ops]
    return (float(np.mean(busy)) if busy else 0.0), hi - lo


def module_times(td: TraceData, prefix: str) -> List[float]:
    """Device durations (s) of the programs named ``prefix(...)`` that
    started inside the window."""
    lo, hi = td.window
    return [e - s for n, s, e in td.modules
            if n.startswith(prefix + "(") and lo <= s < hi]


def idle_gaps(td: TraceData, chip: int = 0) -> List[Tuple[str, float]]:
    """Idle stretches of one chip in the window, longest first, each named
    after the host span that covers most of it."""
    lo, hi = td.window
    st, en, _ = td.ops[chip]
    busy = _union(st, en, lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    # the producer thread's spans first: the analyst thread's sweep runs
    # beside them and names a gap only where no producer span covers it
    spans = [h for h in td.host if h[0] not in ENVELOPES + OFF_THREAD] + \
        [h for h in td.host if h[0] in OFF_THREAD]
    out = []
    for a, b in gaps:
        best, cover = "host (no bench span)", 0.0
        for n, s, e in spans:
            c = min(b, e) - max(a, s)
            if c > cover and (n not in OFF_THREAD or best.startswith("host")):
                best, cover = n, c
        out.append((best, b - a))
    return sorted(out, key=lambda x: -x[1])


def breakdown(td: TraceData, top: int = 10) -> Dict[str, list]:
    """The device operations (not control flow) that took most time on
    chip 0, and the idle time of chip 0 summed by the host span that covered
    it."""
    if not td.ops:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi = td.window
    tot: Dict[str, float] = {}
    st, en, names = td.ops[0]
    for a, b, n in zip(st, en, names):
        if lo <= a < hi and not n.startswith(CONTAINERS):
            tot[n] = tot.get(n, 0.0) + float(b - a)
    by_span: Dict[str, float] = {}
    for n, d in idle_gaps(td):
        by_span[n] = by_span.get(n, 0.0) + d
    pick = lambda d: [[n, float(v)] for n, v in
                      sorted(d.items(), key=lambda x: -x[1])[:top]]
    return {"device_ops": pick(tot), "idle_gaps": pick(by_span)}
