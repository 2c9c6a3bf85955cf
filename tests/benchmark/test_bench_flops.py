"""The benchmark's FLOP arithmetic against the program's roofline model."""
import pytest

import bench_smoke  # noqa: F401  (puts benchmarks/chip on sys.path)
import flops
import harness


def _payload():
    return harness.load_config("stream-qwen2-0.5b")["payload"]


def test_flops_agree_with_the_roofline_model_for_qwen2_at_8x128():
    from repro.analysis.roofline import analytic_model_flops
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    p = _payload()
    want = analytic_model_flops(get_config("qwen2-0.5b"),
                                ShapeConfig("task", 128, 8, "train"))
    assert flops.dense_train_step_flops(p) == pytest.approx(want, rel=1e-12)
    assert flops.dense_param_count(p) == get_config("qwen2-0.5b").param_count


def test_every_configuration_runs_the_published_widths():
    """Through the payload's reference module: the program's configuration
    and the FLOP count that ``train_step.mfu`` divides by."""
    from repro.configs import get_config
    for name in ("stream-qwen2-0.5b", "sharded4-qwen2-0.5b"):
        cfg_file = harness.load_config(name)
        p = cfg_file["payload"]
        ref = harness.load_reference(cfg_file)
        cfg = harness.model_config(p, ref.program_overrides(p))
        assert cfg == get_config("qwen2-0.5b")
        assert ref.train_step_flops(p) == 3052073385984.0


def test_mfu_reader_is_a_share_of_peak():
    mfu = harness.load_metric("train_step.mfu")

    class Trace:
        window = (1.0, 3.0)
    run = harness.RunRecord(
        spans=harness.Spans(), window=(0.0, 4.0), traced=(1.0, 3.0),
        trace=Trace(), tasks=50, tasks_traced=25, s_per_step=[],
        sweeps=[], flops_per_task=3.0e12, peaks={"bf16_flops_per_s": 197e12},
        chips=1)
    assert mfu(run) == pytest.approx(100 * 25 * 3.0e12 / (2.0 * 197e12))
    run.trace = None
    assert mfu(run) is None


def test_unknown_device_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")
