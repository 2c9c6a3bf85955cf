"""Plain reference of the paper's Q1-Q7 steering sweep (d-Chiron Table 2).

Computes the sweep from the final relation of a run, as that relation stood
at the moment ``now`` at which the executor cut the sweep. One thread
claims, commits and cuts sweeps, each stamped from one clock, so at ``now``
a row had been claimed iff ``start_time <= now`` and committed iff also
``end_time <= now``; until its claim it was READY with no times or outputs.
The harness holds those times, and each sweep's ``now``, to its own clock
around the calls that made them (``harness.check_stamps``).
This holds for the benchmark's traffic, which inserts every task before the
first sweep and never fails, reaps, prunes or steals a task; a run that did
would compare unequal and show as incorrect, never as falsely correct.

Imports nothing of the program. Rows of all primaries are taken together,
the worker of a row being ``shard * workers_per_shard + worker_id``: the
single-primary answer that a sharded sweep must equal.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

BLOCKED, READY, RUNNING, FINISHED, FAILED, PRUNED = 1, 2, 3, 4, 5, 6
Q7_ACT_A, Q7_ACT_B, Q7_THR = 0, 2, 0.5


def at_time(cols: Dict[str, np.ndarray], now: float) -> Dict[str, np.ndarray]:
    """The relation's columns as they stood at ``now``."""
    t0, t1 = cols["start_time"], cols["end_time"]
    with np.errstate(invalid="ignore"):
        claimed = t0 <= now
        done = claimed & (t1 <= now)
        present = cols["submit_time"] <= now
    st = cols["status"].copy()
    moved = np.isin(st, [READY, RUNNING, FINISHED])
    st[moved & ~claimed] = READY
    st[moved & claimed & ~done] = RUNNING
    st[moved & done] = FINISHED
    st[~present] = 0
    out = dict(cols)
    out["status"] = st
    out["start_time"] = np.where(claimed, t0, np.nan)
    out["end_time"] = np.where(done, t1, np.nan)
    out["out0"] = np.where(done, cols["out0"], np.nan)
    return out


def sweep(relations: Sequence[Dict[str, np.ndarray]], workers_per_shard: int,
          now: float, horizon: float = 60.0) -> Dict[str, Any]:
    cut = [at_time(c, now) for c in relations]
    cat = {k: np.concatenate([c[k] for c in cut])
           for k in ("task_id", "activity_id", "status", "start_time",
                     "end_time", "fail_trials", "out0", "parent_task")}
    gw = np.concatenate([s * workers_per_shard + c["worker_id"]
                         for s, c in enumerate(cut)])
    st, act = cat["status"], cat["activity_id"]
    t0, t1 = cat["start_time"], cat["end_time"]
    W = workers_per_shard * len(relations)
    out: Dict[str, Any] = {}

    with np.errstate(invalid="ignore"):
        recent = (t0 >= now - horizon) & (st != 0)
    q1 = {}
    for w in np.unique(gw[recent]):
        m = recent & (gw == w)
        q1[int(w)] = {"started": int(m.sum()),
                      "finished": int((m & (st == FINISHED)).sum()),
                      "failures": int(cat["fail_trials"][m].sum())}
    out["q1"] = q1

    with np.errstate(invalid="ignore"):
        m3 = (st == FAILED) & (t1 >= now - horizon)
    if m3.any():
        counts = np.bincount(gw[m3], minlength=W)
        out["q3"] = [int(w) for w in np.nonzero(counts == counts.max())[0]]
    else:
        out["q3"] = []

    open_ = np.isin(st, [READY, RUNNING, BLOCKED])
    out["q4"] = int(open_.sum())
    if open_.any():
        c = np.bincount(act[open_])
        out["q5"] = (int(np.argmax(c)), int(c.max()))
    else:
        out["q5"] = (-1, 0)

    fin = st == FINISHED
    open_acts = set(np.unique(act[np.isin(st, [READY, RUNNING])]).tolist())
    q6 = {}
    for a in sorted(set(act[fin].tolist()) & open_acts):
        d = (t1 - t0)[fin & (act == a)]
        q6[int(a)] = (float(np.mean(d)), float(np.max(d)))
    out["q6"] = q6

    out["q7"] = _q7(cat, fin)
    return out


def _q7(cat: Dict[str, np.ndarray], fin: np.ndarray) -> List[int]:
    act, st = cat["activity_id"], cat["status"]
    fin_b = fin & (act == Q7_ACT_B)
    if not fin_b.any():
        return []
    dur = cat["end_time"] - cat["start_time"]
    mean = float(np.nanmean(dur[fin_b]))
    row_of: Dict[int, int] = {}
    for r, (tid, s) in enumerate(zip(cat["task_id"], st)):
        if s == 0:
            continue
        if s != PRUNED or int(tid) not in row_of:
            row_of[int(tid)] = r          # a live row shadows its tombstone
    hits = []
    for r in np.nonzero(fin_b & (cat["out0"] > Q7_THR) & (dur > mean))[0]:
        cur = int(r)
        while cur >= 0 and act[cur] > Q7_ACT_A and cat["parent_task"][cur] >= 0:
            cur = row_of.get(int(cat["parent_task"][cur]), -1)
        if cur >= 0 and act[cur] == Q7_ACT_A:
            hits.append(int(cat["task_id"][cur]))
    return sorted(hits)


def equal(got: Dict[str, Any], want: Dict[str, Any],
          relations: Sequence[Dict[str, np.ndarray]],
          rel_tol: float = 1e-9) -> bool:
    """The program's sweep against the reference. Counts and ids compare
    exactly; Q6's mean and max durations to ``rel_tol``, because float64
    sums of up to 23.4k durations in another order differ in the last bits
    (about 1e-12 relative)."""
    if isinstance(got.get("version"), (int, np.integer)):
        q7 = sorted(int(relations[0]["task_id"][r]) for r in got["q7"])
    else:
        q7 = sorted(int(t) for t in got["q7"])
    if q7 != want["q7"]:
        return False
    if {int(k): v for k, v in got["q1"].items()} != want["q1"]:
        return False
    if list(got["q3"]) != want["q3"] or int(got["q4"]) != want["q4"]:
        return False
    if tuple(int(x) for x in got["q5"]) != want["q5"]:
        return False
    g6 = {int(k): v for k, v in got["q6"].items()}
    if sorted(g6) != sorted(want["q6"]):
        return False
    for a, (m, x) in want["q6"].items():
        gm, gx = g6[a]
        if not (abs(gm - m) <= rel_tol * abs(m) and abs(gx - x) <= rel_tol
                * abs(x)):
            return False
    return True
