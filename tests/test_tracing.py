"""The in-program tracer: off by default and free of records when off; on,
spans nest by thread, name their cause across threads, and the executor's
tick, claim, commit and copy-on-write are recorded where the work happens."""
import tempfile
import threading

import pytest

from repro import tracing
from repro.configs import smoke_config
from repro.core.workqueue import WorkQueue
from repro.data.pipeline import DataConfig
from repro.runtime.executor import TrainExecutor

# what one claim_all writes (the RUNNING flip and its lease stamps)
CLAIM_COLUMNS = {"status", "start_time", "claimed_at", "heartbeat_at",
                 "expires_at"}


@pytest.fixture(autouse=True)
def clean_tracer():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_off_span_is_the_shared_no_op_and_nothing_is_recorded(monkeypatch):
    def refuse(name):
        raise AssertionError("an annotation was made while off")
    monkeypatch.setattr(tracing, "_ANNOTATION", refuse)
    sp = tracing.span("wf.x", task=3)
    assert sp is tracing.OFF and not sp
    with tracing.span("wf.y") as inner:
        inner.set(rows=1)
        assert tracing.current() is None

    def work():
        return 7
    assert tracing.handoff(work, "wf.z") is work
    wq = WorkQueue(num_workers=2, capacity=256)
    wq.add_tasks(0, 8, now=0.0)
    wq.store.snapshot_view()
    wq.finish(wq.claim_all(k=1, now=1.0)[0], now=2.0)
    assert tracing.drain() == []


def test_spans_nest_by_thread_and_name_their_cause_across_threads():
    tracing.enable()
    box = {}
    with tracing.span("wf.outer", task=1) as outer:
        with tracing.span("wf.inner") as inner:
            assert tracing.current() == inner.id
        job = tracing.handoff(lambda x: box.setdefault("x", x), "wf.job")
    t = threading.Thread(target=job, args=(5,))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and box == {"x": 5}
    spans = tracing.drain()
    got = {s.name: s for s in spans}
    assert got["wf.outer"].parent is None
    assert got["wf.outer"].attrs == {"task": 1}
    assert got["wf.inner"].parent == outer.id
    assert got["wf.job"].parent == outer.id
    assert got["wf.job"].thread != got["wf.outer"].thread
    for s in spans:
        assert s.end_ns >= s.start_ns and s.cpu_ns is None


def test_thread_cpu_time_is_read_only_when_asked(monkeypatch):
    import time
    reads = []
    real = time.thread_time_ns

    def counted():
        reads.append(1)
        return real()
    monkeypatch.setattr(time, "thread_time_ns", counted)
    tracing.enable()
    with tracing.span("wf.plain"):
        pass
    assert reads == []
    tracing.enable(cpu_time=True)
    with tracing.span("wf.timed"):
        sum(range(10000))
    assert len(reads) == 2
    tracing.disable()
    plain, timed = tracing.drain()
    assert plain.cpu_ns is None
    assert 0 <= timed.cpu_ns
    assert not tracing.TRACER.cpu_time


def test_spans_past_the_cap_are_counted_not_kept():
    tr = tracing.Tracer(max_spans=2)
    tr.enabled = True
    for _ in range(3):
        with tr.span("wf.x"):
            pass
    assert len(tr.drain()) == 2
    assert tr.dropped == 0
    for _ in range(3):
        with tr.span("wf.x"):
            pass
    assert len(tr.peek()) == 2 and tr.dropped == 1


def test_tracer_records_while_a_profiler_session_collects():
    import jax
    assert tracing.span("wf.before") is tracing.OFF
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with tracing.span("wf.profiled"):
                pass
    assert tracing.span("wf.after") is tracing.OFF
    assert [s.name for s in tracing.drain()] == ["wf.profiled"]


@pytest.mark.parametrize("snapshot", [True, False])
def test_copy_on_write_spans_the_frozen_columns_the_claim_wrote(snapshot):
    cap = 1024
    wq = WorkQueue(num_workers=4, capacity=cap)
    wq.add_tasks(0, 40, now=0.0)
    if snapshot:
        wq.store.snapshot_view()
    tracing.enable()
    rows = wq.claim_all(k=1, now=1.0)
    spans = tracing.drain()
    assert sum(len(r) for r in rows.values()) == 4
    cows = by_name(spans, "wf.cow")
    if not snapshot:
        assert cows == []
        return
    want = sum(cap * wq.store.cols[c].dtype.itemsize for c in CLAIM_COLUMNS)
    assert sum(s.attrs["bytes"] for s in cows) == want
    assert len(cows) == len(CLAIM_COLUMNS)
    (claim,) = by_name(spans, "wf.claim")
    assert {s.attrs["column"] for s in cows} == CLAIM_COLUMNS
    assert all(s.parent == claim.id for s in cows)


@pytest.mark.parametrize("shards", [1, 2])
def test_one_tick_records_each_claimed_task(shards):
    cfg = smoke_config("qwen2-0.5b")
    ex = TrainExecutor(cfg, num_workers=2, shards=shards, steer_every=1,
                       data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                           seq_len=16, batch_size=2))
    try:
        ex.submit_steps(8)
        ex.tick()                       # compiles the step, hands a sweep
        ex._drain_steering()
        tracing.enable()
        ex.tick()
    finally:
        ex.close()                      # waits for the tick's sweep
    spans = tracing.drain()
    by_id = {s.id: s for s in spans}
    (tick,) = by_name(spans, "wf.tick")
    assert tick.parent is None
    root = [c for c in by_name(spans, "wf.claim") if c.parent == tick.id]
    assert len(root) == 1
    tasks = root[0].attrs["tasks"]
    assert len(tasks) == 2 and root[0].attrs["rows"] == 2
    inner = [c for c in by_name(spans, "wf.claim") if c is not root[0]]
    assert len(inner) == (shards if shards > 1 else 0)
    assert all(c.parent == root[0].id for c in inner)
    for name in ("wf.batch", "wf.dispatch", "wf.sync"):
        got = sorted(s.attrs["task"] for s in by_name(spans, name))
        assert got == sorted(tasks), name
        assert all(s.parent == tick.id for s in by_name(spans, name))
    for s in by_name(spans, "wf.dispatch"):     # the step's plan, per step
        assert s.attrs["residuals"] == ex.step_plan.residuals == "stored"
        assert s.attrs["residual_bytes"] == ex.step_plan.residual_bytes > 0
    commits = by_name(spans, "wf.commit")
    assert sorted(t for s in commits for t in s.attrs["tasks"]) \
        == sorted(tasks)
    for s in by_name(spans, "wf.log_append"):
        assert by_id[s.parent].name in ("wf.claim", "wf.commit")
    (submit,) = by_name(spans, "wf.steer_submit")
    (sweep,) = by_name(spans, "wf.sweep")
    assert submit.parent == tick.id
    assert sweep.parent == submit.id and sweep.thread != tick.thread
    if shards > 1:
        parts = by_name(spans, "wf.partial")
        assert sorted(p.attrs["shard"] for p in parts) == [0, 1]
        assert all(p.parent == sweep.id for p in parts)
        assert by_name(spans, "wf.merge")[0].parent == sweep.id
    # the snapshot the first tick handed over is copied by this claim
    cows = [s for s in by_name(spans, "wf.cow")
            if by_id[s.parent].name == "wf.claim"]
    assert len(cows) == shards * len(CLAIM_COLUMNS)
    assert {s.attrs["column"] for s in cows} == CLAIM_COLUMNS


@pytest.mark.parametrize("limit,want,remat", [
    (None, "stored", False), (1 << 40, "stored", "dots"),
    (1 << 20, "recomputed", True)])
def test_dispatch_spans_carry_the_planned_residuals(monkeypatch, limit, want,
                                                    remat):
    """The executor plans its step from the device's reported limit (none on
    the CPU: the configuration's ``remat`` stands) and every dispatched
    step's span says which plan ran."""
    import repro.runtime.executor as executor
    monkeypatch.setattr(executor, "device_bytes_limit", lambda: limit)
    cfg = smoke_config("qwen2-0.5b")            # remat off
    ex = TrainExecutor(cfg, num_workers=2,
                       data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                           seq_len=16, batch_size=2))
    try:
        assert ex.step_plan.residuals == want
        assert ex.step_plan.cfg.remat == remat
        assert ex.step_plan.bytes_limit == limit
        ex.submit_steps(4)
        tracing.enable()
        ex.tick()
        ex.tick()
    finally:
        ex.close()
    spans = by_name(tracing.drain(), "wf.dispatch")
    assert len(spans) == 4
    assert {s.attrs["residuals"] for s in spans} == {want}
    assert {s.attrs["residual_bytes"] for s in spans} == \
        {ex.step_plan.residual_bytes}
