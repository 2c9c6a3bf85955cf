"""Smoke-size copies of the benchmark's cells, for CPU tests of the harness."""
import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(REPO, "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "seq_len": 16,
         "batch_size": 4}


def smoke_base(tmp, tasks=400, dtype="float32", size=None):
    """A copy of the benchmark's files with every configuration cut to a
    CPU-sized payload (``SMOKE``, or ``size``) and backlog. Returns
    (base dir, BENCHMARK dict)."""
    base = os.path.join(str(tmp), "chip")
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    for f in os.listdir(os.path.join(base, "configs")):
        p = os.path.join(base, "configs", f)
        c = json.load(open(p))
        c["payload"].update(size or SMOKE, dtype=dtype)
        c["tasks"] = tasks
        json.dump(c, open(p, "w"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return base, copy.deepcopy(bench)


def renamed_reference(base, module, config="stream-qwen2-0.5b", name=None):
    """A copy of ``reference.py`` in ``base`` as ``<module>.py``, and a
    configuration ``name`` (default ``<config>-<module>``), otherwise a copy
    of ``config``, whose payload names it. Returns the new name."""
    name = name or f"{config}-{module}"
    shutil.copy(os.path.join(base, "reference.py"),
                os.path.join(base, f"{module}.py"))
    with open(os.path.join(base, "configs", f"{config}.json")) as f:
        c = json.load(f)
    c["name"] = name
    c["payload"]["reference"] = module
    with open(os.path.join(base, "configs", f"{name}.json"), "w") as f:
        json.dump(c, f)
    return name
