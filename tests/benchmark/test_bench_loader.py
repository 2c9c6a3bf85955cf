"""The harness finds configurations, traffic mixes and metrics by name, and
BENCHMARK.json keeps to the shape its contract sets."""
import json
import os
import re

import pytest

import bench_smoke
import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_config_mix_and_metric_are_found_from_their_own_files(tmp_path):
    base, bench = bench_smoke.smoke_base(tmp_path)
    bench_smoke.renamed_reference(base, "another_payload",
                                  name="another-deployment")
    with open(os.path.join(base, "traffic", "steer-every-4.json"), "w") as f:
        json.dump(dict(harness.load_traffic("steer-max", base),
                       name="steer-every-4", steer_every=4), f)
    with open(os.path.join(base, "metrics", "tick.count.py"), "w") as f:
        f.write("def reduce(run):\n"
                "    t0, t1 = run.window\n"
                "    return float(len(run.spans.within('tick', t0, t1)))\n")
    bench["workloads"].append({
        "name": "another-deployment.steer-every-4",
        "config": "another-deployment", "traffic": "steer-every-4",
        "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "tick.count", "unit": "ticks", "better": "higher",
        "source": "host_clock", "layer": "scheduler tick",
        "moves": "tasks_per_s",
        "workloads": ["another-deployment.steer-every-4"]})
    got = harness.resolve_cell(bench, "another-deployment.steer-every-4", base)
    assert got["config"]["name"] == "another-deployment"
    ref = harness.load_reference(got["config"], base)
    assert ref.__file__ == os.path.join(base, "another_payload.py")
    assert got["traffic"]["steer_every"] == 4
    assert "tick.count" in [m["name"] for m in got["per_layer"]]
    assert "sweep_p90_ms" not in [m["name"] for m in got["end_to_end"]]
    spans = harness.Spans()
    spans.rec["tick"] = [(1.0, 1.5), (1.5, 2.0), (9.0, 9.5)]
    run = harness.RunRecord(
        spans=spans, window=(0.0, 5.0), traced=None, trace=None, tasks=4,
        tasks_traced=0, s_per_step=[0.2] * 4, sweeps=[], flops_per_task=1.0,
        peaks={}, chips=1)
    assert harness.load_metric("tick.count", base)(run) == 2.0
    with pytest.raises(harness.BenchError):
        harness.resolve_cell(bench, "no-such.cell", base)
    with pytest.raises(harness.BenchError):
        harness.load_metric("no.such.metric", base)


@pytest.mark.parametrize("change", [{"loop": "open"},
                                    {"backlog": "poisson"},
                                    {"rate_per_s": 4.0},
                                    {"steer_every": -1}])
def test_traffic_the_generator_does_not_implement_is_refused(tmp_path,
                                                             change):
    base, bench = bench_smoke.smoke_base(tmp_path)
    mix = dict(harness.load_traffic("steer-max", base), name="odd")
    mix.update(change)
    with open(os.path.join(base, "traffic", "odd.json"), "w") as f:
        json.dump(mix, f)
    with pytest.raises(harness.BenchError):
        harness.load_traffic("odd", base)


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_benchmark_file_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(bench_smoke.REPO, p))
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in bench[section]]
        assert len(names) == len(set(names))
        for e in bench[section]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                                 "higher")
    for e in bench["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [e["name"] for e in bench["end_to_end"]]


def test_every_cell_resolves_and_reports_enough(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            harness.HERE, "metrics", m["name"] + ".py"))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(bench_smoke.REPO, c["file"]))
        assert harness.load_config(c["name"])["name"] == c["name"]
        assert harness.load_config(c["name"])["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        r = harness.resolve_cell(bench, w["name"])
        names = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert r["per_layer"]
        for m in r["per_layer"]:
            assert m["moves"] in names, (w["name"], m["name"])
