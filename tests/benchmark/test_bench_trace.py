"""The trace reduction on a small trace recorded on the chip."""
import json
import os

import numpy as np
import pytest

import bench_smoke  # noqa: F401  (puts benchmarks/chip on sys.path)
import devtrace

FIXTURE = os.path.join(os.path.dirname(__file__), "trace_fixture.json")


@pytest.fixture(scope="module")
def td():
    with open(FIXTURE) as f:
        fx = json.load(f)
    return devtrace.from_events(fx["device_ops"], fx["modules"], fx["host"])


def _busy_by_grid(td, step=1e-7):
    """Busy time by marking a fine time grid: an independent union."""
    lo, hi = td.window
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    st, en, _ = td.ops[0]
    for a, b in zip(st, en):
        i0 = int(np.ceil((max(a, lo) - lo) / step))
        i1 = int(np.floor((min(b, hi) - lo) / step))
        if i1 >= i0:
            grid[i0:i1 + 1] = True
    return grid.sum() * step


def test_busy_time_is_the_union_of_device_ops(td):
    busy, window = devtrace.busy_and_window(td)
    assert window == pytest.approx(0.0851, abs=1e-9)
    assert busy == pytest.approx(_busy_by_grid(td), abs=2e-5)
    # one step of 73.6 ms and the start of the next in an 85 ms window
    assert 0.05 < 1 - busy / window < 0.12


def test_step_program_time_from_the_modules_line(td):
    times = devtrace.module_times(td, "jit_step")
    assert len(times) == 2
    assert times[0] == pytest.approx(0.073621876, rel=1e-9)
    assert devtrace.module_times(td, "jit_other") == []


def test_idle_gaps_add_up_and_breakdown_is_bounded(td):
    busy, window = devtrace.busy_and_window(td)
    gaps = devtrace.idle_gaps(td)
    assert sum(d for _, d in gaps) == pytest.approx(window - busy, abs=1e-9)
    bd = devtrace.breakdown(td)
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        assert 0 < len(bd[key]) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in bd[key])
    assert bd["device_ops"] == sorted(bd["device_ops"], key=lambda x: -x[1])


def test_idle_gap_is_charged_to_the_covering_span():
    ops = [[("op.a", 0.0, 1.0), ("op.b", 2.0, 1.0), ("op.c", 5.0, 1.0)]]
    host = [("bench.window", 0.0, 6.0), ("bench.tick", 0.0, 6.0),
            ("bench.sweep", 0.5, 5.0), ("bench.commit", 1.0, 0.9),
            ("bench.claim", 3.5, 1.0)]
    td = devtrace.from_events(ops, [], host)
    assert devtrace.busy_and_window(td) == (3.0, 6.0)
    gaps = dict(devtrace.breakdown(td)["idle_gaps"])
    # the producer's spans name the gaps they cover; the analyst's sweep
    # names only what no producer span covers
    assert gaps == {"bench.commit": pytest.approx(1.0),
                    "bench.claim": pytest.approx(2.0)}


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        devtrace.from_events([[("op", 0.0, 1.0)]], [], [])
