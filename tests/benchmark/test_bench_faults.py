"""A run whose timed path is broken underneath reads ``correct`` false.

Each fault is planted in the program as the benchmark drives it (the
executor's train step, its analyst's sweep) and the rest of the run goes
as a benchmark run goes, at smoke size on the CPU, against the limits of
the real configuration."""
import time

import jax
import pytest

import bench_smoke
import harness

CELL = "stream-qwen2-0.5b.steer-max"


def _step_unchanged(cfg):
    from repro.launch.steps import make_train_step
    real = jax.jit(make_train_step(cfg))

    def step(state, batch, knobs):
        _, metrics = real(state, batch, knobs)
        return state, metrics
    return step


def _step_half_batch(cfg):
    from repro.launch.steps import make_train_step
    real = jax.jit(make_train_step(cfg), donate_argnums=(0,))

    def step(state, batch, knobs):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return real(state, half, knobs)
    return step


def _step_loss_altered(cfg):
    from repro.launch.steps import make_train_step
    real = jax.jit(make_train_step(cfg), donate_argnums=(0,))

    def step(state, batch, knobs):
        state, metrics = real(state, batch, knobs)
        return state, dict(metrics, loss=metrics["loss"] + 0.5)
    return step


def _plant(monkeypatch, fault):
    import repro.core.steering as steering
    import repro.core.workqueue as workqueue
    import repro.runtime.executor as executor
    if fault in ("claim_stamped_late", "commit_stamped_late"):
        name = "claim_all" if fault == "claim_stamped_late" else "finish"
        real = getattr(workqueue.WorkQueue, name)

        def late(self, *a, now=0.0, **kw):
            return real(self, *a, now=now + 0.05, **kw)
        monkeypatch.setattr(workqueue.WorkQueue, name, late)
    elif fault == "sweep_answer_altered":
        real = steering.SteeringEngine.run_all

        def run_all(self, now, view=None):
            out = real(self, now, view)
            return dict(out, q4=out["q4"] + 1)
        monkeypatch.setattr(steering.SteeringEngine, "run_all", run_all)
    else:
        monkeypatch.setattr(executor, "jit_train_step", {
            "state_unchanged": _step_unchanged,
            "half_batch": _step_half_batch,
            "loss_altered": _step_loss_altered}[fault])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered", "sweep_answer_altered",
                                   "claim_stamped_late",
                                   "commit_stamped_late"])
def test_planted_fault_reads_incorrect(tmp_path, monkeypatch, fault):
    _plant(monkeypatch, fault)
    base, bench = bench_smoke.smoke_base(tmp_path, tasks=3000)
    resolved = harness.resolve_cell(bench, CELL, base)
    out = harness.run_cell(resolved, 2**31 + 11, 1.0, False,
                           time.perf_counter(), require_platform=None)
    failed = [k for k, c in out["checks"].items()
              if not harness._passes(k, c)]
    assert out["correct"] is False, harness.check_lines(out)
    assert failed
    if fault.endswith("stamped_late"):
        assert "time_stamp_mismatches" in failed, harness.check_lines(out)
