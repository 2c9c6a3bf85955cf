"""Run one benchmark cell once on the chip and print its result line.

  python benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

The cell, its configuration and traffic are looked up by name from
``BENCHMARK.json`` at the root of the checkout (see ``harness.py``). The
last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: every number compared, with its limit.
The same numbers end standard error. Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when a
file the cell needs is missing. JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(REPO, "src")]
    import harness

    try:
        resolved = harness.resolve_cell(harness.load_benchmark(REPO),
                                        args.workload)
        import jax
        jax.config.update("jax_compilation_cache_dir", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        result = harness.run_cell(resolved, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
