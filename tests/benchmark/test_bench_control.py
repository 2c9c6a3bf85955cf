"""The control, the reference computed with fp8 matrix products, fails the
configuration's limits; so does the planted half-batch fault; the program
in float32 passes them. These are the readings ``calibrate.py`` takes on the
chip at the cell's own size, here at a size the CPU can hold: 4 layers of
width 256 and a vocabulary of 8192, where the control's worst gradient leaf
still lies above the limit (at 24 layers it lies 2x to 5x above it). Each
verdict is the harness's own (``harness._passes``), and the control is also
put in the program's place under a whole run, whose ``correct`` it turns
false.

The program's own bfloat16 path is not tested here: the CPU computes
bfloat16 matrix products otherwise than the chip, and its readings are not
the chip's."""
import time

import jax
import jax.numpy as jnp
import pytest

import bench_smoke
import calibrate
import harness

SIZE = {"num_layers": 4, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 64, "d_ff": 1024, "vocab_size": 8192, "seq_len": 64,
        "batch_size": 8}
SEED = 4_000_000_002


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    base, _ = bench_smoke.smoke_base(tmp_path_factory.mktemp("control"),
                                     tasks=64, dtype="float32", size=SIZE)
    cfg = harness.load_config("stream-qwen2-0.5b", base)
    return cfg["limits"], {r["kind"]: r for r in
                           calibrate.readings(cfg, SEED, True)}


def _failed(reading, limits):
    return [k for k, lim in limits.items()
            if not harness._passes(k, {"value": reading[k], "limit": lim})]


def test_program_passes(readings):
    limits, r = readings
    assert _failed(r["program"], limits) == []


@pytest.mark.parametrize("kind", ["control_fp8", "fault_half_batch"])
def test_control_and_fault_fail(readings, kind):
    limits, r = readings
    assert _failed(r[kind], limits), r[kind]


def _fp8_step(reference, payload):
    """The program's train step replaced by the reference's AdamW step with
    fp8 forward matrix products, on the program's own state tree."""
    def make(cfg):
        def step(state, batch, knobs):
            like = state["params"]
            flat = {k: harness.from_program_tree(t) for k, t in
                    (("w", like), ("m", state["opt"]["inner"]["m"]),
                     ("v", state["opt"]["inner"]["v"]))}
            n = state["opt"]["step"] + 1
            with jax.default_matmul_precision("highest"):
                w, m, v, loss, gnorm, _ = reference._adamw_step(
                    flat["w"], flat["m"], flat["v"], batch["tokens"],
                    batch["labels"], n.astype(jnp.float32), payload, "fp8")
            opt = {"step": n, "inner": {
                "m": harness.to_program_tree(like, m),
                "v": harness.to_program_tree(like, v)}}
            return ({"params": harness.to_program_tree(like, w), "opt": opt},
                    {"loss": loss, "grad_norm": gnorm})
        return jax.jit(step, donate_argnums=(0,))
    return make


def test_control_in_the_programs_place_reads_incorrect(tmp_path,
                                                       monkeypatch):
    import repro.runtime.executor as executor
    base, bench = bench_smoke.smoke_base(tmp_path, tasks=200,
                                         dtype="float32", size=SIZE)
    resolved = harness.resolve_cell(bench, "stream-qwen2-0.5b.steer-off",
                                    base)
    cfg = resolved["config"]
    monkeypatch.setattr(executor, "jit_train_step", _fp8_step(
        harness.load_reference(cfg, base), cfg["payload"]))
    out = harness.run_cell(resolved, SEED, 0.5, False, time.perf_counter(),
                           require_platform=None)
    failed = [k for k, c in out["checks"].items()
              if not harness._passes(k, c)]
    assert out["correct"] is False, harness.check_lines(out)
    assert "grad_leaf_gap" in failed, harness.check_lines(out)
    assert out["checks"]["provenance_mismatches"]["value"] == 0
