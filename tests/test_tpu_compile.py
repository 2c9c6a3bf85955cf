"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(an op Mosaic cannot lower, a block over the fast-memory limit, a program
over the device memory). These compiles are the cheap guard that the
``wq_claim`` kernel, the attention kernels and the qwen2-0.5b train step still
build for the chip.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file. Keep every described-chip compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.wq_claim.ops import wq_claim
from repro.launch.steps import (RESIDUAL_MARGIN, abstract_train_state,
                                jit_train_step, plan_train_step)

HBM_BYTES = 16 * 2 ** 30            # one TPU v5e chip
QWEN = get_config("qwen2-0.5b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("w,k", [(64, 1), (64, 4), (936, 1), (936, 4)])
def test_wq_claim_compiles_for_v5e(one_chip, w, k):
    col = _sds((100_000,), jnp.int32, one_chip)
    compiled = jax.jit(lambda s, wk: wq_claim(s, wk, num_workers=w, k=k)) \
        .lower(col, col).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_kernels_compile_for_v5e(one_chip):
    hq, hkv, dh = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
    bf = jnp.bfloat16
    q = _sds((8, 2048, hq, dh), bf, one_chip)
    kv = _sds((8, 2048, hkv, dh), bf, one_chip)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)) \
        .lower(q, kv, kv).compile()
    assert "tpu_custom_call" in fa.as_text()
    q1 = _sds((4, 1, hq, dh), bf, one_chip)
    cache = _sds((4, 4096, hkv, dh), bf, one_chip)
    da = jax.jit(lambda q, k, v: decode_attention(q, k, v, kv_len=4000)) \
        .lower(q1, cache, cache).compile()
    assert "tpu_custom_call" in da.as_text()


def test_qwen2_train_step_fits_one_v5e(one_chip):
    """The executor's step (donated state) at its default 8 x 128 batch,
    built with the plan one v5e gets: every projection's output stored, none
    recomputed, and the compiled total inside the planner's estimate."""
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         abstract_train_state(QWEN))
    batch = {"tokens": _sds((8, 128), jnp.int32, one_chip),
             "labels": _sds((8, 128), jnp.int32, one_chip)}
    knobs = {"lr": _sds((), jnp.float32, one_chip)}
    plan = plan_train_step(QWEN, batch, HBM_BYTES)
    assert plan.residuals == "stored" and plan.cfg.remat == "dots"
    lowered = jit_train_step(plan.cfg).lower(state, batch, knobs)
    # the scan body's seven projections and the LM head: each once forward
    # and twice backward (input and weight gradients), none recomputed
    products = sum("dot_general" in line and "batching_dims" not in line
                   for line in lowered.as_text().splitlines())
    assert products == 3 * 8, products
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # the state is donated
    planned = (plan.state_bytes + plan.residual_bytes) * (1 + RESIDUAL_MARGIN)
    assert total <= planned <= HBM_BYTES, (mem, plan)
