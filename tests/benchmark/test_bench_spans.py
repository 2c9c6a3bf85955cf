"""The per-layer metrics that read the program's own ``wf.*`` spans: on
spans and device events built here, on a profiler trace recorded here, and
under a whole run of a cell at smoke size on the CPU."""
import tempfile
import time

import pytest

import bench_smoke
import devtrace
import harness
import progspans
from repro import tracing

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
SPAN_METRICS = ("task.wait_ms", "claim.cow_ms", "claim.overlap_ms")


@pytest.fixture(autouse=True)
def clean_tracer():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


def made(name, sid, start_ms, end_ms, parent=None, thread=1, **attrs):
    """A finished span with the times given (ms on ``perf_counter``)."""
    s = tracing.Span(tracing.Tracer(), name, parent, attrs)
    s.id, s.thread = sid, thread
    s.start_ns, s.end_ns = int(start_ms * 1e6), int(end_ms * 1e6)
    return s


def record(trace=None, traced=None):
    return harness.RunRecord(
        spans=harness.Spans(), window=(40.0, 56.0), traced=traced,
        trace=trace, tasks=2, tasks_traced=2, s_per_step=[], sweeps=[],
        flops_per_task=1.0, peaks={}, chips=1)


def test_claim_and_wait_readers_on_built_spans():
    # a router's claim of tasks 7 and 8 over two shard claims; the second
    # shard claim copies two frozen columns
    sp = [made("wf.tick", 1, 0.0, 200.0),
          made("wf.claim", 2, 1.0, 3.0, parent=1, tasks=[7, 8]),
          made("wf.claim", 3, 1.1, 1.5, parent=2, tasks=[7]),
          made("wf.claim", 4, 1.6, 2.9, parent=2, tasks=[8]),
          made("wf.cow", 5, 1.7, 2.0, parent=4),
          made("wf.log_append", 6, 2.0, 2.6, parent=4),
          made("wf.cow", 7, 2.1, 2.2, parent=6),
          made("wf.dispatch", 8, 3.5, 4.0, parent=1, task=7),
          made("wf.dispatch", 9, 83.0, 84.0, parent=1, task=8),
          made("wf.dispatch", 10, 90.0, 91.0, parent=1, task=99)]
    assert [c.id for c in progspans.claims(sp)] == [2]
    assert progspans.task_wait_ms(sp) == pytest.approx((0.5 + 80.0) / 2)
    assert progspans.claim_cow_ms(sp) == pytest.approx(0.4)
    assert progspans.claim_overlap_ms(sp) == 0.0
    for reader in (progspans.task_wait_ms, progspans.claim_cow_ms,
                   progspans.claim_overlap_ms):
        assert reader(None) is None
        assert reader([made("wf.tick", 1, 0.0, 1.0)]) is None


def test_claim_overlap_is_the_claims_time_beside_other_threads_spans():
    # two claims on thread 1; a sweep on thread 2 with a nested partial,
    # and a shipper span on thread 3 that overlaps the sweep
    sp = [made("wf.claim", 1, 10.0, 12.0, tasks=[1]),
          made("wf.claim", 2, 20.0, 22.0, tasks=[2]),
          made("wf.commit", 3, 12.0, 19.0),
          made("wf.sweep", 4, 11.0, 15.0, thread=2),
          made("wf.partial", 5, 11.5, 14.0, parent=4, thread=2),
          made("wf.encode", 6, 14.0, 16.0, thread=3),
          made("wf.send", 7, 21.5, 23.0, thread=3)]
    # first claim: 11-12 beside the sweep; second: 21.5-22 beside the send
    assert progspans.claim_overlap_ms(sp) == pytest.approx((1.0 + 0.5) / 2)


def test_dispatch_idle_is_the_launch_spans_share_of_the_device_gaps():
    # device busy 100-101, 102-103, 105-106 on the trace's clock; the
    # traced part is 50-56 s on perf_counter, so the clocks differ by 50 s
    ops = [[("op.a", 100.0, 1.0), ("op.b", 102.0, 1.0), ("op.c", 105.0, 1.0)]]
    td = devtrace.from_events(ops, [], [("bench.window", 100.0, 6.0)])
    run = record(td, (50.0, 56.0))
    sp = [made("wf.batch", 1, 51200.0, 51500.0, task=1),
          made("wf.dispatch", 2, 51500.0, 52500.0, task=1),
          made("wf.dispatch", 3, 53000.0, 53100.0, task=2),
          made("wf.claim", 4, 54000.0, 55000.0),
          made("wf.dispatch", 5, 45000.0, 46000.0, task=0)]
    # covered: 0.3 + 0.5 of the first gap, 0.1 of the second; two steps
    assert progspans.dispatch_idle_ms(run, sp) == pytest.approx(450.0)
    assert progspans.dispatch_idle_ms(run, None) is None
    assert progspans.dispatch_idle_ms(record(), sp) is None
    assert progspans.dispatch_idle_ms(
        record(devtrace.from_events([], [], [("bench.window", 0.0, 1.0)]),
               (0.0, 1.0)), sp) is None


def test_program_spans_land_in_the_trace_on_the_mapped_clock():
    """The program's spans are annotations in the profiler's trace, and the
    bench.window bounds map their in-memory times onto the trace's clock."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                with tracing.span("wf.dispatch", task=i):
                    time.sleep(0.01)
                time.sleep(0.005)
        tb = time.perf_counter()
        jax.profiler.stop_trace()
        run = record(devtrace.load(d), (ta, tb))
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[-1]
        events = sorted(
            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "wf.dispatch")
    spans = sorted(tracing.drain(), key=lambda s: s.start_ns)
    assert len(events) == len(spans) == 3
    on_trace = progspans.on_trace_clock(run)
    for s, (a, b) in zip(spans, events):
        assert on_trace(s.start_ns) == pytest.approx(a, abs=1e-4)
        assert on_trace(s.end_ns) == pytest.approx(b, abs=1e-4)


def run_smoke(tmp_path, workload, trace):
    base, bench = bench_smoke.smoke_base(tmp_path, tasks=3000)
    resolved = harness.resolve_cell(bench, workload, base)
    return harness.run_cell(resolved, 2**31 + 11, 1.5, trace,
                            time.perf_counter(), require_platform=None)


def test_traced_rehearsal_reports_the_program_span_metrics(tmp_path):
    out = run_smoke(tmp_path, CELLS[0], trace=True)
    assert out["correct"], harness.check_lines(out)
    for name in SPAN_METRICS:
        assert isinstance(out["metrics"][name]["value"], float), name
    # the steering snapshot makes every claim copy the columns it writes
    assert out["metrics"]["claim.cow_ms"]["value"] > 0
    assert out["metrics"]["task.wait_ms"]["value"] > 0
    assert "dispatch.idle_ms" not in out["metrics"]    # no device ops here


def test_untraced_rehearsal_leaves_the_tracer_empty(tmp_path):
    out = run_smoke(tmp_path, CELLS[0], trace=False)
    assert out["correct"], harness.check_lines(out)
    assert tracing.peek() == []
