"""Snapshot isolation (HTAP): steering sweeps on immutable store versions
while claims mutate the live arrays — plus the COW mechanics behind it."""
import threading
import time

import numpy as np
import pytest

from repro.core import Status, SteeringEngine, WorkQueue
from repro.core.store import ColumnStore


def make_wq(workers=4, tasks=32):
    wq = WorkQueue(num_workers=workers)
    wq.add_tasks(0, tasks)
    return wq


def status_counts(view):
    st = view.col("status")
    return {s: int((st == int(s)).sum()) for s in Status}


def test_snapshot_pins_version_across_claims():
    wq = make_wq(tasks=32)
    snap = wq.store.snapshot_view()
    v0 = snap.version
    wq.claim_all(k=2, now=1.0)
    rows = np.nonzero(wq.store.col("status") == int(Status.RUNNING))[0]
    wq.finish(rows[:4], now=2.0, domain_out=np.ones((4, 3)))
    # live store moved on ...
    assert wq.store.version > v0
    live = status_counts(wq.store)
    assert live[Status.RUNNING] == len(rows) - 4
    assert live[Status.FINISHED] == 4
    # ... but the snapshot still shows the pre-claim state, untouched
    old = status_counts(snap)
    assert old[Status.READY] == 32
    assert old[Status.RUNNING] == 0 and old[Status.FINISHED] == 0
    assert snap.version == v0


def test_snapshot_survives_store_growth():
    wq = WorkQueue(num_workers=2, capacity=16)
    wq.add_tasks(0, 12)
    snap = wq.store.snapshot_view()
    wq.add_tasks(0, 100)                     # forces _grow + reallocation
    assert wq.store.n_rows == 112
    assert snap.n_rows == 12
    assert (snap.col("status") == int(Status.READY)).all()


def test_run_all_on_mid_claim_snapshot_is_internally_consistent():
    """The sweep sees ONE version: no READY+RUNNING double-count even though
    claims commit between the sweep's individual queries."""
    wq = make_wq(workers=4, tasks=40)
    steer = SteeringEngine(wq)
    wq.claim_all(k=1, now=1.0)                    # 4 RUNNING
    snap = wq.store.snapshot_view()               # <- mid-workload snapshot
    # concurrent-looking mutation: more claims + finishes AFTER the snapshot
    out = wq.claim_all(k=2, now=2.0)
    rows = np.concatenate([v for v in out.values() if len(v)])
    wq.finish(rows, now=3.0, domain_out=np.ones((len(rows), 3)))
    res = steer.run_all(4.0, view=snap)
    # on the snapshot: 4 running + 36 ready, nothing finished yet
    assert res["q4"] == 40
    assert res["version"] == snap.version
    c = status_counts(snap)
    assert c[Status.READY] + c[Status.RUNNING] == 40
    assert c[Status.RUNNING] == 4 and c[Status.FINISHED] == 0
    # live sweep sees the later version
    live = steer.run_all(4.0)
    assert live["q4"] == 40 - len(rows)
    assert live["version"] > snap.version


def test_concurrent_steering_never_tears(n_tasks=1500, workers=8):
    """Analyst thread sweeps on snapshots while the main thread claims and
    finishes; every sweep must conserve the task count across its separate
    queries (the READY->FINISHED double-count a live read would produce)."""
    wq = WorkQueue(num_workers=workers, capacity=4 * n_tasks)
    wq.add_tasks(0, n_tasks)
    steer = SteeringEngine(wq)
    errors = []
    stop = threading.Event()

    def analyst():
        while not stop.is_set():
            with steer.snapshot_scope() as v:
                left = steer.q4_tasks_left()          # query 1
                time.sleep(0.0005)                    # let claims commit
                c = status_counts(v)                  # query 2, same view
                total = (left + c[Status.FINISHED] + c[Status.FAILED]
                         + c[Status.PRUNED] + c[Status.EMPTY])
                if total != v.n_rows:
                    errors.append((v.version, left, c))
                run = np.nonzero(v.col("status") == int(Status.RUNNING))[0]
                if np.isnan(v.col("start_time")[run]).any():
                    errors.append(("torn start_time", v.version))

    t = threading.Thread(target=analyst)
    t.start()
    try:
        done = 0
        while done < n_tasks:
            out = wq.claim_all(k=2, now=float(done))
            rows = np.concatenate([v for v in out.values() if len(v)]) \
                if any(len(v) for v in out.values()) else np.empty(0, int)
            if len(rows) == 0:
                break
            wq.finish(rows, now=float(done) + 0.5,
                      domain_out=np.ones((len(rows), 3)))
            done += len(rows)
    finally:
        stop.set()
        t.join()
    assert not errors, errors[:3]
    assert wq.counts()["FINISHED"] == n_tasks


def test_q8_and_prune_write_live_store_inside_sweep():
    """Adaptations are transactions: even inside a snapshot scope they read
    and write the LIVE store, never the pinned view."""
    wq = WorkQueue(num_workers=2)
    wq.add_tasks(0, 10, domain_in=np.linspace(0, 9, 10)[:, None]
                 * np.ones((10, 3)))
    steer = SteeringEngine(wq)
    with steer.snapshot_scope():
        n = steer.q8_patch_ready(0, "in0", 42.0, predicate=lambda v: v > 5.0)
        assert n == 4
    assert (wq.store.col("in0") == 42.0).sum() == 4


def test_device_claim_flag_matches_reference():
    from repro.flags import device_claims, wq_device_claim
    assert not wq_device_claim()
    with device_claims("interpret"):
        wq_dev = WorkQueue(num_workers=3)        # picks the flag up
        assert wq_dev.device_claim == "interpret"
    wq_ref = WorkQueue(num_workers=3)
    assert not wq_ref.device_claim
    wq_dev.add_tasks(0, 20)
    wq_ref.add_tasks(0, 20)
    for r in range(3):
        o1 = wq_dev.claim_all(k=2, now=float(r))
        o2 = wq_ref.claim_all_reference(k=2, now=float(r))
        for w in range(3):
            assert np.array_equal(o1[w], o2[w])


def test_device_claim_routes_orphaned_partitions_to_steal_pool():
    """Shrink-resize can leave retried tasks with worker_id >= W; the kernel
    'claims' those at rank 0, so the device path must divert them to the
    steal pool exactly like the host path does."""
    results = {}
    for device in (False, "interpret"):
        wq = WorkQueue(num_workers=4, device_claim=device)
        wq.add_tasks(0, 12)
        out = wq.claim_all(k=1, now=0.0)          # 4 RUNNING, one per worker
        running = np.concatenate(list(out.values()))
        wq.resize(2)                              # RUNNING rows keep wid 2,3
        wq.fail(running, max_trials=5)            # ... and retry to READY
        assert (wq.store.col("worker_id")[running] >= 2).sum() > 0
        # quota-exact round: in-range workers fill without touching the
        # orphans, so their cursors advance past the orphan rows — the
        # orphan watermark must keep those rows visible to later steals
        mid = wq.claim_all(k=4, now=0.5)
        res = wq.claim_all(k=20, now=1.0)         # budget >> tasks: steal all
        rows = np.concatenate([v for v in list(mid.values())
                               + list(res.values()) if len(v)])
        assert len(np.unique(rows)) == len(rows)
        assert wq.counts()["READY"] == 0          # orphans claimed via steal
        results[device] = (mid, res)
    for phase in (0, 1):                          # device path == host path
        for w in results[False][phase]:
            assert np.array_equal(results[False][phase][w],
                                  results["interpret"][phase][w])


def test_snapshot_id_index_and_q7_vectorized_walk():
    """Q7's iterative parent-gather on a snapshot equals the per-hit walk."""
    wq = WorkQueue(num_workers=2)
    rng = np.random.default_rng(0)
    parents = wq.add_tasks(0, 6)
    wq.finish(np.concatenate(list(wq.claim_all(k=3, now=0.0).values())),
              now=1.0, domain_out=rng.normal(0.6, 0.2, (6, 3)))
    kids = wq.add_tasks(1, 6, parent_task=parents,
                        domain_in=rng.normal(0.5, 0.2, (6, 3)))
    wq.finish(np.concatenate(list(wq.claim_all(k=3, now=1.0).values())),
              now=2.0, domain_out=rng.normal(0.6, 0.2, (6, 3)))
    grand = wq.add_tasks(2, 6, parent_task=kids,
                         domain_in=rng.normal(0.5, 0.2, (6, 3)))
    rows = np.concatenate(list(wq.claim_all(k=3, now=2.0).values()))
    # two finish batches with different durations so "slower than the
    # activity average" selects a real subset
    wq.finish(rows[:3], now=3.0, domain_out=rng.normal(0.6, 0.2, (3, 3)))
    wq.finish(rows[3:], now=6.0, domain_out=rng.normal(0.6, 0.2, (3, 3)))
    steer = SteeringEngine(wq)
    with steer.snapshot_scope() as v:
        got = steer.q7_provenance_join(act_a=0, act_b=2, thr=0.3)
    # oracle: the seed per-hit Python walk
    st = wq.store.col("status")
    act = wq.store.col("activity_id")
    t0, t1 = wq.store.col("start_time"), wq.store.col("end_time")
    f1 = wq.store.col("out0")
    parent = wq.store.col("parent_task")
    tid = wq.store.col("task_id")
    fin_b = (st == int(Status.FINISHED)) & (act == 2)
    dur = t1 - t0
    slow = dur > np.nanmean(dur[fin_b])
    hits = np.nonzero(fin_b & (f1 > 0.3) & slow)[0]
    id_to_row = {int(t): i for i, t in enumerate(tid)}
    want = []
    for row in hits:
        r = int(row)
        while act[r] > 0 and parent[r] >= 0:
            r = id_to_row.get(int(parent[r]), -1)
            if r < 0:
                break
        if r >= 0 and act[r] == 0:
            want.append(r)
    assert np.array_equal(got, np.asarray(want, np.int64))
    assert len(got) > 0                       # the join actually fired
