"""Each cell's set-up, window and checks at smoke size on the CPU, through
the harness's own functions: the result line is well formed and correct."""
import json
import time

import pytest

import bench_smoke
import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def run_smoke(tmp_path, workload, trace=False, seconds=1.5, tasks=3000,
              seed=2**31 + 7):
    base, bench = bench_smoke.smoke_base(tmp_path, tasks=tasks)
    resolved = harness.resolve_cell(bench, workload, base)
    return resolved, harness.run_cell(resolved, seed, seconds, trace,
                                      time.perf_counter(),
                                      require_platform=None)


def assert_well_formed(resolved, out, trace):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    json.loads(json.dumps(out))
    want = {m["name"] for m in resolved["per_layer" if trace
                                         else "end_to_end"]}
    assert set(out["metrics"]) <= want
    for name, m in out["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_is_correct_and_well_formed(tmp_path, workload):
    resolved, out = run_smoke(tmp_path, workload)
    assert_well_formed(resolved, out, trace=False)
    assert out["correct"], harness.check_lines(out)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"]
                                   for m in resolved["end_to_end"]}
    assert out["checks"]["window_compilations"]["value"] == 0
    if "steer-max" in workload:
        assert out["checks"]["sweeps_compared"]["value"] > 1
    if workload.startswith("sharded4"):
        assert out["checks"]["replicas_compared"]["value"] == 4


def test_traced_rehearsal_reports_host_layers(tmp_path):
    resolved, out = run_smoke(tmp_path, CELLS[1], trace=True)
    assert_well_formed(resolved, out, trace=True)
    assert out["correct"], harness.check_lines(out)
    for name in ("tick.host_ms", "claim.ms", "commit.ms", "sweep.busy_ms",
                 "ship.ms"):
        assert out["metrics"][name]["value"] > 0
    assert {"busy_s", "window_s", } <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
