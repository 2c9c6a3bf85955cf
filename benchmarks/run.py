"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per harness contract) and
writes the full records to results/bench/*.json.

``--scale`` scales the paper's task counts (default 0.1 => 1.3k-2.3k tasks
per run; the paper's ratios are scale-invariant here because store-op cost
is measured at true partition sizes).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


# what each registered experiment measures — what `--list` prints and
# `--only` accepts (substring match); kept in lockstep with `runs` below
# (main() fails loudly if the two ever drift)
DESCRIPTIONS = {
    "e1_strong_scaling": "Fig 9a: fixed 13k tasks, 120->960 cores, "
                         "threads sweep (makespan efficiency)",
    "e2_weak_scaling": "Fig 9b: workload grows with cores "
                       "(6k/12k/23.4k tasks on 10/20/39 nodes)",
    "e3_workload_tasks": "Fig 10a: fixed duration, varying #tasks, "
                         "paper vs adapted access latency",
    "e4_workload_duration": "Fig 10b: fixed #tasks, varying duration, "
                            "paper vs adapted access latency",
    "e5_dbms_overhead": "Fig 11: DBMS access time vs total makespan "
                        "across task durations",
    "e6_access_breakdown": "Fig 12: time share per DBMS access kind "
                           "(claims/finishes dominate)",
    "e7_steering_overhead": "Fig 13 at 10x tasks: makespan with vs "
                            "without concurrent snapshot steering sweeps",
    "e8_centralized_vs_distributed": "Fig 14: Chiron (one master) vs "
                                     "d-Chiron (partitioned WQ) makespan",
    "e_replica_lag": "delta txn-log replay vs full-copy replica sync "
                     "(encoded wire bytes; parity across a truncate)",
    "e_wire_ship": "cross-process replicas over pipe/TCP: pipelined "
                   "bulk + incremental ship throughput, varint "
                   "compression, concurrent 3-replica fan-out parity + "
                   "leader-kill promote (all hard-checked)",
    "e_sharded": "N-shard multi-primary router: scatter-gather Q1-Q7 "
                 "parity vs a single-primary oracle, cross-shard steal "
                 "conservation + per-shard replica parity (hard-checked), "
                 "weak-scaling claim throughput (the "
                 "--min-sharded-scaleup gate), concurrent remote steering "
                 "scatter with per-shard partial sweeps in replica "
                 "processes (bit-checked; the --min-steer-fanout-speedup "
                 "gate)",
    "e_chaos": "kill-drill: >=2 workers go silent + replica process "
               "killed mid-run (one batch DURING a pool resize); lease "
               "reap + steal + snapshot respawn must conserve the "
               "task-id set, drain every task and keep replica "
               "bit-parity (the --max-recovery-s gate)",
    "e_shard_failover": "shard-primary failover: two shard primaries "
                        "killed mid-run with claims in flight; promote "
                        "must drain the WAL tail, keep survivors "
                        "claiming, restore checkpoints at the exact "
                        "version vector and stay sweep-bit-identical to "
                        "a single-primary oracle (the "
                        "--max-shard-failover-s gate)",
    "claim_kernel": "claim_all fast-path vs seed loop at k=1/k=4 "
                    "(the >=5x gate) + device wq_claim op latency",
    "replay_throughput": "batched hot-plane txn-log replay vs "
                         "record-at-a-time (the >=10x gate, bit-parity)",
    "steering_sweep": "full Q1-Q7 sweep latency on a ~100k-row snapshot "
                      "(the --max-sweep-ms gate)",
}


def main() -> None:
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))          # the benchmarks package itself
    sys.path.insert(0, str(root / "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--only", default="")
    ap.add_argument("--list", action="store_true",
                    help="print every registered experiment with its "
                         "one-line description (what --only accepts) and "
                         "exit")
    ap.add_argument("--min-claim-speedup", type=float, default=0.0,
                    help="exit nonzero unless the claim_kernel host "
                         "speedup (vectorized vs seed loop) meets this "
                         "floor — the CI regression gate")
    args = ap.parse_args()

    if args.list:
        for name, desc in DESCRIPTIONS.items():
            print(f"{name:32s} {desc}")
        return

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import experiments as E

    runs = {
        "e1_strong_scaling": lambda: E.exp1_strong_scaling(args.scale),
        "e2_weak_scaling": lambda: E.exp2_weak_scaling(args.scale),
        "e3_workload_tasks": lambda: E.exp3_workload_tasks(args.scale),
        "e4_workload_duration": lambda: E.exp4_workload_duration(args.scale),
        "e5_dbms_overhead": lambda: E.exp5_dbms_overhead(args.scale),
        "e6_access_breakdown": lambda: E.exp6_access_breakdown(args.scale),
        "e7_steering_overhead": lambda: E.exp7_steering_overhead(args.scale),
        "e8_centralized_vs_distributed":
            lambda: E.exp8_centralized_vs_distributed(args.scale),
        "e_replica_lag": lambda: E.exp_replica_lag(args.scale),
        "e_wire_ship": lambda: E.exp_wire_ship(args.scale),
        "e_sharded": lambda: E.exp_sharded(args.scale),
        "e_chaos": lambda: E.exp_chaos(args.scale),
        "e_shard_failover": lambda: E.exp_shard_failover(args.scale),
        "claim_kernel": lambda: E.exp_kernel_claim(args.scale),
        "replay_throughput": lambda: E.exp_replay_throughput(args.scale),
        "steering_sweep": lambda: E.exp_steering_sweep(args.scale),
    }
    missing = set(runs) ^ set(DESCRIPTIONS)
    if missing:                            # keep --list honest forever
        raise RuntimeError(f"experiments without (or with stale) "
                           f"descriptions: {sorted(missing)}")
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    only = [t for t in args.only.split(",") if t]
    print("name,us_per_call,derived")
    for name, fn in runs.items():
        if only and not any(t in name for t in only):
            continue
        t0 = time.perf_counter()
        rows = fn()
        dt_us = (time.perf_counter() - t0) * 1e6
        (out_dir / f"{name}.json").write_text(json.dumps(rows, indent=1))
        derived = _headline(name, rows)
        print(f"{name},{dt_us / max(len(rows), 1):.1f},{derived}")
        if name == "claim_kernel" and args.min_claim_speedup > 0:
            spd = min(r["speedup"] for r in rows
                      if r.get("impl") == "speedup")
            if spd < args.min_claim_speedup:
                print(f"FAIL: claim host speedup {spd}x < "
                      f"{args.min_claim_speedup}x gate", file=sys.stderr)
                sys.exit(1)


def _headline(name: str, rows) -> str:
    try:
        if name.startswith("e1"):
            best = max(r["efficiency"] for r in rows if r["nodes"] == 40)
            return f"efficiency@960cores={best}"
        if name.startswith("e2"):
            return f"vs_linear@39nodes={rows[-1]['vs_linear']}"
        if name.startswith("e3"):
            worst = max(r["gap"] for r in rows)
            return f"max_gap={worst}"
        if name.startswith("e4"):
            worst = max(r["gap"] for r in rows)
            return f"max_gap={worst}"
        if name.startswith("e5"):
            fr = {(r["mode"], r["task_dur_s"]): r["dbms_frac"] for r in rows}
            return (f"paper@1s={fr.get(('paper',1.0))};"
                    f"paper@60s={fr.get(('paper',60.0))};"
                    f"adapted@1s={fr.get(('adapted',1.0))}")
        if name.startswith("e6"):
            top = rows[0]
            return f"top_op={top['op']}:{top['share']}"
        if name.startswith("e7"):
            return f"steering_overhead={rows[-1]['overhead']}"
        if name.startswith("e8"):
            p = max(r["speedup"] for r in rows if r["mode"] == "paper")
            a = max(r["speedup"] for r in rows if r["mode"] == "adapted")
            return f"paper_speedup={p}x;adapted={a}x"
        if name == "e_replica_lag":
            sp = [r for r in rows if r["mode"] == "speedup"]
            br = min(r["bytes_ratio_full_over_delta"] for r in sp)
            eq = all(r.get("sweep_equal", True) for r in rows
                     if r["mode"] == "delta")
            return f"full/delta_bytes_min={br}x;sweep_equal={eq}"
        if name == "e_wire_ship":
            mbps = min(r["ship_mbps_bulk"] for r in rows)
            inc = min(r["ship_mbps"] for r in rows)
            comp = min(r["compression_ratio"] for r in rows)
            eq = all(r["cols_equal"] and r["sweep_equal"]
                     and r["fanout_sweep_equal"] for r in rows)
            tr = rows[0]["transport"]
            return (f"ship_mbps_bulk_min={mbps};ship_mbps_inc_min={inc};"
                    f"compression={comp}x;"
                    f"transport={tr};remote+fanout_parity={eq}")
        if name == "e_sharded":
            r = rows[0]
            return (f"scaleup={r['scaleup']}x@{r['shards']}shards;"
                    f"sweep_equal={r['sweep_equal']};"
                    f"steal_moved={r['steal_moved']};"
                    f"steal_conserved={r['steal_conserved']};"
                    f"steer_fanout={r['steer_fanout_speedup']}x;"
                    f"steer_remote_parity={r['steer_remote_sweep_equal']}")
        if name == "e_chaos":
            r = rows[0]
            return (f"recovery_s={r['recovery_s']};"
                    f"conserved={r['conserved']};drained={r['drained']};"
                    f"reaped={r['reaped']};"
                    f"respawns={r['replica_respawns']}")
        if name == "e_shard_failover":
            r = rows[0]
            return (f"failover_wall_s={r['failover_wall_s']};"
                    f"promote_s_max={r['promote_s_max']};"
                    f"survivor_min_claims={r['survivor_min_claims']};"
                    f"conserved={r['conserved']};"
                    f"sweep_equal={r['sweep_equal']};"
                    f"ckpt_vector_match={r['ckpt_vector_match']}")
        if name == "claim_kernel":
            spd = min(r["speedup"] for r in rows if r.get("impl") == "speedup")
            dev = min(r["us_per_task"] for r in rows if "us_per_task" in r)
            return f"host_speedup_min={spd}x;device_us_per_task_min={dev}"
        if name == "replay_throughput":
            spd = next(r["speedup"] for r in rows if r["impl"] == "speedup")
            return f"batched_vs_record_speedup={spd}x"
        if name == "steering_sweep":
            return f"ms_per_sweep={rows[0]['ms_per_sweep']}@" \
                   f"{rows[0]['rows']}rows"
    except Exception as e:  # noqa: BLE001
        return f"err:{e}"
    return ""


if __name__ == "__main__":
    main()
