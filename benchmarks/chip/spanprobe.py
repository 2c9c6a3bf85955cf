"""Run one cell as ``run.py`` does, with the program's tracer on for the
whole window or off, and print what the benchmark's wrappers and the
program's own ``wf.*`` spans each read.

  python3 benchmarks/chip/spanprobe.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1> --tracer <0|1>

The last line of standard output is the cell's result line, as ``run.py``
prints it. Before it, on standard error:

* after the harness's ``slow_tick`` lines, one ``slow_tick_spans`` line per
  slow tick: the program spans that started inside that tick, by name, with
  their summed wall and off-CPU seconds (all threads);
* one ``probe`` line, a JSON object: per call, the wrappers' ``claim``,
  ``commit``, ``ship``, ``sweep`` and ``tick`` means over the window (ms);
  with ``--tracer 1`` also the same means read from ``wf.claim``,
  ``wf.commit``, ``wf.ship``, ``wf.sweep`` and ``wf.tick`` over the same
  window, the three span metrics over the whole window, the spans recorded
  per tick, what one span costs on this host with the tracer off, on, and
  on with the thread CPU clock, and the smallest step of that clock; with ``--trace 1``
  also ``idle_by_span``: the device idle time of chip 0 in the traced part,
  per step, split by the producer thread's span that covered it.

``--out DIR`` writes every recorded span (one JSON object per line) to
``DIR/<workload>.<seed>.spans.jsonl``.

With ``--tracer 1`` the tracer also reads each span's thread CPU time
(``tracing.enable(cpu_time=True)``), which ``run.py`` never asks for, so the
probe's per-layer readings carry more tracer cost than a ``--trace 1`` run
of ``run.py``.

This probe rebinds ``harness.warm_up``, ``harness.slow_ticks`` and
``harness.RunRecord``, so it lives only as long as the harness itself does
not turn the tracer on for the window and print ``slow_tick_spans``
(ROADMAP design debt 10). The change that does that deletes this file.
"""
import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(REPO, "src")]

import numpy as np  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402
import progspans  # noqa: E402
from repro import tracing  # noqa: E402

PROBE = {}
RUNS = []
# the producer thread's spans that follow one another inside a tick
PRODUCER = ("wf.rebalance", "wf.claim", "wf.batch", "wf.dispatch", "wf.sync",
            "wf.commit", "wf.checkpoint", "wf.harvest", "wf.reap",
            "wf.steer_submit")


@dataclasses.dataclass
class CapturedRun(harness.RunRecord):
    """The run record the harness builds, kept for the probe."""

    def __post_init__(self):
        RUNS.append(self)


def mean_ms(xs):
    return 1e3 * statistics.fmean(xs) if xs else None


def span_lines(spans, window, n=2):
    """``slow_tick_spans`` for the ``n`` longest ticks of the window."""
    t0, t1 = window
    ticks = sorted(((b - a, a) for a, b in spans.rec["tick"]
                    if t0 <= a <= t1), reverse=True)[:n]
    rec = tracing.peek()
    out = []
    for d, a in ticks:
        by = {}
        for s in rec:
            if a <= s.start_ns * 1e-9 <= a + d:
                w, o = by.get(s.name, (0.0, 0.0))
                off = s.wall_ns - (s.cpu_ns or 0)
                by[s.name] = (w + s.wall_ns * 1e-9, o + off * 1e-9)
        out.append(f"slow_tick_spans {d:.6f} " + " ".join(
            f"{k} {w:.6f}/{o:.6f}" for k, (w, o) in
            sorted(by.items(), key=lambda x: -x[1][0])))
    return out


def probe(spans, window):
    """The wrappers' and the program's per-call means over the window."""
    t0, t1 = window
    got = {f"bench.{k}_ms": mean_ms(spans.within(k, t0, t1))
           for k in ("claim", "commit", "ship", "sweep", "tick")}
    rec = [s for s in tracing.peek() if t0 <= s.start_ns * 1e-9 <= t1]
    if rec:
        for name in ("commit", "ship", "sweep", "tick"):
            got[f"wf.{name}_ms"] = mean_ms(
                [s.wall_ns * 1e-9 for s in rec if s.name == "wf." + name])
        got["wf.claim_ms"] = mean_ms(
            [s.wall_ns * 1e-9 for s in progspans.claims(rec)])
        got["task.wait_ms"] = progspans.task_wait_ms(rec)
        got["claim.cow_ms"] = progspans.claim_cow_ms(rec)
        got["claim.overlap_ms"] = progspans.claim_overlap_ms(rec)
        ticks = sum(s.name == "wf.tick" for s in rec)
        got["spans_per_tick"] = len(rec) / ticks if ticks else None
    PROBE.update(got)


def idle_by_span(run, rec):
    """Device idle time of chip 0 in the traced part, per step (ms), by the
    producer span that covered it; the rest of a tick is ``wf.tick``, time
    between ticks ``between_ticks``."""
    if run.trace is None or not run.trace.ops or run.traced is None:
        return None
    ticks = [s for s in rec if s.name == "wf.tick"]
    steps = sum(s.name == "wf.dispatch" and run.traced[0] <= s.start_ns * 1e-9
                <= run.traced[1] for s in rec)
    if not ticks or not steps:
        return None
    on_trace = progspans.on_trace_clock(run)
    lo, hi = run.trace.window
    st, en, _ = run.trace.ops[0]
    edges = [lo] + [x for ab in devtrace._union(st, en, lo, hi)
                    for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def idle_under(name):
        mine = [s for s in rec if s.name == name
                and s.thread == ticks[0].thread]
        if not mine:
            return 0.0
        a = np.asarray([on_trace(s.start_ns) for s in mine])
        b = np.asarray([on_trace(s.end_ns) for s in mine])
        return progspans._overlap(gaps, devtrace._union(a, b, lo, hi))
    out = {n: idle_under(n) for n in PRODUCER}
    in_ticks = idle_under("wf.tick")
    out["wf.tick"] = in_ticks - sum(out.values())
    out["between_ticks"] = sum(b - a for a, b in gaps) - in_ticks
    return {k: 1e3 * v / steps for k, v in out.items() if v}


def thread_clock_step_ns(spin_s=0.1):
    """The smallest nonzero step of ``thread_time_ns`` seen while spinning."""
    t_end = time.perf_counter() + spin_s
    last, step = time.thread_time_ns(), None
    while time.perf_counter() < t_end:
        now = time.thread_time_ns()
        if now != last:
            step = now - last if step is None else min(step, now - last)
            last = now
    return step


def dump(rec, path):
    with open(path, "w") as f:
        for s in rec:
            f.write(json.dumps({
                "name": s.name, "id": s.id, "parent": s.parent,
                "thread": s.thread, "start_ns": s.start_ns,
                "end_ns": s.end_ns, "cpu_ns": s.cpu_ns,
                "attrs": {k: v for k, v in s.attrs.items()
                          if k != "tasks"},
                "tasks": s.attrs.get("tasks")}) + "\n")


def span_cost_ns(n=20000):
    """Nanoseconds per ``with span(...)`` on this host: off, on, and on with
    the thread CPU clock read (the faster of two rounds each, so one-time
    start-up costs are left out); and per ``thread_time_ns`` call alone."""
    out = {}
    for label, on in (("off", tracing.disable), ("on", tracing.enable),
                      ("on_cpu", lambda: tracing.enable(cpu_time=True))):
        on()
        for _ in range(2):
            t = time.perf_counter_ns()
            for i in range(n):
                with tracing.span("probe.cost", task=i):
                    pass
            ns = (time.perf_counter_ns() - t) / n
            out[label] = min(ns, out.get(label, ns))
            tracing.drain()
    tracing.disable()
    t = time.perf_counter_ns()
    for _ in range(n):
        time.thread_time_ns()
    out["thread_time_ns"] = (time.perf_counter_ns() - t) / n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    warm_up, slow_ticks = harness.warm_up, harness.slow_ticks

    def warm_then_trace(*a, **kw):
        n = warm_up(*a, **kw)
        if args.tracer:
            tracing.drain()
            tracing.enable(cpu_time=True)
        return n

    def slow_ticks_and_spans(spans, window, n=2):
        tracing.disable()
        probe(spans, window)
        return slow_ticks(spans, window, n) + span_lines(spans, window, n)
    harness.warm_up, harness.slow_ticks = warm_then_trace, slow_ticks_and_spans
    harness.RunRecord = CapturedRun
    resolved = harness.resolve_cell(harness.load_benchmark(REPO),
                                    args.workload)
    result = harness.run_cell(resolved, args.seed, args.seconds,
                              bool(args.trace), T_START)
    rec = tracing.drain()
    if args.tracer:
        if args.trace:
            PROBE["idle_by_span"] = idle_by_span(RUNS[-1], rec)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            dump(rec, os.path.join(
                args.out, f"{args.workload}.{args.seed}.spans.jsonl"))
        PROBE["span_ns"] = span_cost_ns()
        PROBE["thread_clock_step_ns"] = thread_clock_step_ns()
        tracing.drain()
    PROBE.update(workload=args.workload, seed=args.seed,
                 tracer=args.tracer, trace=args.trace)
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    print("probe " + json.dumps(PROBE), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
