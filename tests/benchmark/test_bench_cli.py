"""The command refuses to measure without a TPU and prints no result."""
import os
import shutil
import subprocess
import sys

import bench_smoke

CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "stream-qwen2-0.5b.steer-max", "--seed", str(2**31 + 3),
       "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(bench_smoke.REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(bench_smoke.REPO, "BENCHMARK.json"), tmp_path)
    for d in ("benchmarks/chip", "tests/benchmark"):
        shutil.copytree(os.path.join(bench_smoke.REPO, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
