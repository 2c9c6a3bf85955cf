"""Readings that the correctness limits of a configuration are set from.

  python benchmarks/chip/calibrate.py --config stream-qwen2-0.5b \\
      --seeds 12 --control-seeds 3 [--first-seed N]

For each seed, in one process: the program's first three train steps,
driven through ``TrainExecutor.tick`` exactly as a benchmark run's set-up
drives them, against the plain float32 reference (the lower readings). For
the first ``--control-seeds`` seeds also the control, the reference computed
with fp8 matrix products, and a planted fault, the reference fed half of
each batch, both against the float32 reference (the upper readings). A
state left unchanged reads 1 on ``delta_leaf_gap`` by construction and is
not run. One JSON line per reading goes to standard output. Runs on the
chip only.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(REPO, "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found", file=sys.stderr)
        return 2
    cfg_file = harness.load_config(args.config)
    for i in range(args.seeds):
        for obj in readings(cfg_file, args.first_seed + i,
                            control=i < args.control_seeds):
            print(json.dumps(obj), flush=True)
    return 0


def readings(cfg_file, seed: int, control: bool):
    """The numbers compared for one seed: the program's against the
    reference, and with ``control`` the control's and the half-batch
    fault's against the reference."""
    import harness
    t0 = time.perf_counter()
    traffic = harness.load_traffic("steer-off")
    cfg_file = dict(cfg_file, tasks=64)     # three steps need a few rows
    reference = harness.load_reference(cfg_file)
    ex, rec, _ = harness.build(cfg_file, traffic, seed, harness.Spans(),
                               reference)
    try:
        while len(rec.step_metrics) < 3:
            ex.tick()
        relations = harness.relations_of(ex)
        prog = harness.program_readings(rec)
    finally:
        ex.close()
    ex.state = None
    del ex
    gc.collect()
    ref = harness.reference_readings(cfg_file, reference, rec, relations,
                                     seed)
    g1 = ref["grad1"]
    med = float(np.median(np.concatenate([np.ravel(g1[n]) for n in g1])))
    out = [{"kind": "program", "seed": seed,
            **harness.train_numbers(prog, ref),
            "leaves": int(sum(np.size(g1[n]) for n in g1)),
            "leaves_left_out": int(sum((np.ravel(g1[n]) < 1e-3 * med).sum()
                                       for n in g1)),
            "loss": prog["loss"], "ref_loss": ref["loss"],
            "seconds": time.perf_counter() - t0}]
    if control:
        half = int(cfg_file["payload"]["batch_size"]) // 2
        for kind, kw in (("control_fp8", {"quant": "fp8"}),
                         ("fault_half_batch", {"rows": half})):
            other = harness.reference_readings(cfg_file, reference, rec,
                                               relations, seed, **kw)
            out.append({"kind": kind, "seed": seed,
                        **harness.train_numbers(other, ref),
                        "loss": other["loss"]})
    return out


if __name__ == "__main__":
    sys.exit(main())
