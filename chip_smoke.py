"""Bring-up smoke run of the workflow executor on one TPU chip.

  python chip_smoke.py

Drives the system's main path once through its normal entry points, at the
full qwen2-0.5b configuration (24 layers, d_model 896, vocab 151936; random
weights from a seed):

1. ``TrainExecutor`` with ``analyst="snapshot"``: training-step tasks are
   claimed from the partitioned work queue, run by the jitted train step on
   the chip, committed back with ``finish`` (loss, grad norm, step time), and
   steering sweeps run on the analyst thread against store snapshots.
2. The same with ``analyst="remote"``: the sweeps run in a spawned replica
   process fed wire-encoded deltas. That child imports no JAX and never
   touches the chip, which this process holds.
3. The claim phase: 100k-row ``WorkQueue``s at W in {64, 936} workers and
   claim budgets k in {1, 4}. ``claim_all`` through the compiled ``wq_claim``
   Pallas kernel must return exactly what the host path and
   ``claim_all_reference`` return, round after round, and the lowered kernel
   must be a ``tpu_custom_call`` (compiled, not interpreted).

Each phase prints lines that name the device. Any failed check raises, so
the script exits non-zero; it also exits non-zero, printing no result, when
JAX finds no TPU. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

All JAX work sits under the ``__main__`` guard: the replica child re-imports
this module by path and must not reach for the chip.
"""
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-0.5b"
TRAIN_STEPS = 8
CLAIM_ROWS = 100_000
CLAIM_ROUNDS = 3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def train_phase(analyst: str, kind: str) -> None:
    import jax

    from repro.configs import get_config
    from repro.runtime.executor import TrainExecutor

    cfg = get_config(ARCH)
    ex = TrainExecutor(cfg, num_workers=2, steer_every=2, analyst=analyst)
    try:
        ex.submit_steps(TRAIN_STEPS)
        hist = ex.run()
        for rec in hist:
            print(f"train analyst={analyst} device={kind} arch={ARCH} "
                  f"step={rec['step']} loss={rec['loss']!r} "
                  f"grad_norm={rec['grad_norm']!r} "
                  f"step_s={rec['s_per_step']!r}", flush=True)
        check(len(hist) == TRAIN_STEPS, f"{len(hist)} of {TRAIN_STEPS} steps")
        check(all(math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"])
                  for r in hist), "finite loss and grad norm")
        uniform = math.log(cfg.vocab_size)
        check(abs(hist[0]["loss"] - uniform) < 1.0,
              f"first loss {hist[0]['loss']} near ln(vocab) = {uniform}")
        sweeps = sum("steering" in r for r in hist)
        check(sweeps >= 1, "at least one steering sweep harvested")
        extra = ""
        if analyst == "remote":
            check(ex.replica.remote_pid != os.getpid(),
                  "the sweep ran in another process")
            check(ex.replica.records_applied > 0,
                  "the replica applied shipped log records")
            extra = (f" replica_pid={ex.replica.remote_pid} "
                     f"records_applied={ex.replica.records_applied}")
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"train analyst={analyst} device={kind} "
              f"peak_bytes_in_use={peak} sweeps_harvested={sweeps}{extra}",
              flush=True)
    finally:
        ex.close()


def claim_phase(kind: str, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.workqueue import WorkQueue
    from repro.flags import device_claims
    from repro.kernels.wq_claim.ops import wq_claim

    rng = np.random.default_rng(seed)
    for w in (64, 936):
        for k in (1, 4):
            col = jax.ShapeDtypeStruct((CLAIM_ROWS,), jnp.int32)
            hlo = wq_claim.lower(col, col, num_workers=w, k=k).compile() \
                .as_text()
            check("tpu_custom_call" in hlo, "wq_claim compiles to a kernel")
            with device_claims():
                dev = WorkQueue(w, capacity=CLAIM_ROWS)
            check(dev.device_claim is True, "compiled device claims")
            host = WorkQueue(w, capacity=CLAIM_ROWS, device_claim=False)
            ref = WorkQueue(w, capacity=CLAIM_ROWS, device_claim=False)
            for q in (dev, host, ref):
                q.add_tasks(0, CLAIM_ROWS, now=0.0)
            dev_s = []
            for r in range(CLAIM_ROUNDS):
                now = float(r + 1)
                t0 = time.perf_counter()
                got = dev.claim_all(k=k, now=now)
                dev_s.append(time.perf_counter() - t0)
                want = host.claim_all(k=k, now=now)
                oracle = ref.claim_all_reference(k=k, now=now)
                for i in range(w):
                    check(np.array_equal(got[i], want[i])
                          and np.array_equal(got[i], oracle[i]),
                          f"W={w} k={k} round {r} worker {i} claims agree")
                # finish a seeded half of the claims everywhere, so later
                # rounds scan a store of mixed READY/RUNNING/FINISHED rows
                rows = np.concatenate(list(got.values()))
                done = np.sort(rows[rng.random(rows.size) < 0.5])
                for q in (dev, host, ref):
                    q.finish(done, now=now)
            check(all(np.array_equal(q.store.col("status"),
                                     dev.store.col("status"))
                      for q in (host, ref)), "final status columns agree")
            print(f"claim device={kind} rows={CLAIM_ROWS} workers={w} k={k} "
                  f"rounds={CLAIM_ROUNDS} claimed_last_round={rows.size} "
                  f"equal_to_host_and_reference=True tpu_custom_call=True "
                  f"device_claim_all_s={dev_s!r}", flush=True)


def main() -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {d0.platform}); "
              f"this check runs only on the chip", file=sys.stderr)
        sys.exit(2)
    kind = d0.device_kind.replace(" ", "_")
    train_phase("snapshot", kind)
    gc.collect()            # drop the first run's train state before the next
    train_phase("remote", kind)
    gc.collect()
    claim_phase(kind)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
