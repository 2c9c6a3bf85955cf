"""A payload's plain reference is the module its configuration names, found
by file like a metric reader; the harness takes from it the weights, one
leaf at a time, the program's configuration and the FLOP count."""
import dataclasses
import os
import time

import jax
import numpy as np
import pytest

import bench_smoke
import harness

SIZE = {"num_layers": 4, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 64, "d_ff": 1024, "vocab_size": 8192}


def test_reference_module_is_found_from_the_configuration(tmp_path):
    base, _ = bench_smoke.smoke_base(tmp_path)
    name = bench_smoke.renamed_reference(base, "plain_qwen2")
    ref = harness.load_reference(harness.load_config(name, base), base)
    assert ref.__file__ == os.path.join(base, "plain_qwen2.py")
    for f in harness.REFERENCE_API:
        assert callable(getattr(ref, f))


@pytest.mark.parametrize("lacks", [None, "weight_leaf", "program_overrides",
                                   "train_step_flops"])
def test_missing_or_incomplete_reference_is_refused(tmp_path, lacks):
    base, _ = bench_smoke.smoke_base(tmp_path)
    name = bench_smoke.renamed_reference(base, "partial_ref")
    path = os.path.join(base, "partial_ref.py")
    if lacks is None:
        os.remove(path)
    else:
        with open(path, "a") as f:
            f.write(f"\ndel {lacks}\n")
    with pytest.raises(harness.BenchError, match=lacks or "missing"):
        harness.load_reference(harness.load_config(name, base), base)


def test_nested_override_reaches_the_nested_dataclass():
    from repro.configs import get_config
    arch = "granite-moe-3b-a800m"
    published = get_config(arch)
    cfg = harness.model_config(
        {"arch": arch, "optimizer": {"name": published.optimizer}},
        {"num_layers": 2, "moe": {"num_experts": 8, "top_k": 2}})
    assert cfg.moe == dataclasses.replace(published.moe, num_experts=8,
                                          top_k=2)
    assert cfg == dataclasses.replace(published, num_layers=2, moe=cfg.moe)


@pytest.mark.parametrize("arch, overrides", [
    ("granite-moe-3b-a800m", {"no_such_field": 1}),
    ("granite-moe-3b-a800m", {"moe": {"no_such_field": 1}}),
    ("qwen2-0.5b", {"moe": {"top_k": 2}}),
])
def test_override_the_config_has_no_field_for_is_refused(arch, overrides):
    from repro.configs import get_config
    payload = {"arch": arch,
               "optimizer": {"name": get_config(arch).optimizer}}
    with pytest.raises(harness.BenchError):
        harness.model_config(payload, overrides)


def test_weights_remade_leaf_by_leaf_and_changes_read_by_groups():
    """``make_weights`` is ``weight_leaf`` leaf by leaf, bit for bit; the
    harness reads the change in groups of leaves no larger than the largest
    leaf, agreeing with the whole-tree reading it replaces, and no group's
    call holds more than a few copies of the leaves it reads."""
    cfg_file = harness.load_config("stream-qwen2-0.5b")
    p = dict(cfg_file["payload"], **SIZE)
    ref = harness.load_reference(cfg_file)
    key = harness.weights_key(harness.derive_seeds(2**31 + 19))
    w0 = ref.make_weights(p, key)
    assert sorted(w0) == sorted(ref.weight_shapes(p))
    for n, a in w0.items():
        assert np.array_equal(np.asarray(a),
                              np.asarray(ref.weight_leaf(p, key, n))), n
    params = {n: a + 1e-3 * jax.random.normal(jax.random.PRNGKey(i), a.shape)
              for i, (n, a) in enumerate(sorted(w0.items()))}
    whole = jax.jit(lambda a, b: ref.leaf_norms({n: a[n] - b[n] for n in a}))(
        params, ref.make_weights(p, key))
    change = harness.change_call(ref, p)
    groups = harness.leaf_groups(ref.weight_shapes(p))
    assert sorted(n for g in groups for n in g) == sorted(w0)
    largest = max(a.nbytes for a in w0.values())
    for g in groups:
        xs = {n: params[n] for n in g}
        got = change(xs, key)
        for n in g:
            np.testing.assert_allclose(np.asarray(got[n]),
                                       np.asarray(whole[n]), rtol=1e-6)
        nbytes = sum(x.nbytes for x in xs.values())
        assert nbytes <= largest, g
        temp = change.lower(xs, key).compile().memory_analysis()
        assert temp.temp_size_in_bytes <= 5 * nbytes, g


def test_cell_through_a_renamed_reference_checks_the_same(tmp_path,
                                                          monkeypatch):
    base, bench = bench_smoke.smoke_base(tmp_path, tasks=400)
    name = bench_smoke.renamed_reference(base, "plain_qwen2")
    bench["workloads"].append({"name": f"{name}.steer-off", "config": name,
                               "traffic": "steer-off", "chips": 1,
                               "why": "a test"})
    loaded = []
    load = harness.load_reference

    def spy(cfg_file, base=harness.HERE):
        mod = load(cfg_file, base)
        loaded.append(mod.__file__)
        return mod
    monkeypatch.setattr(harness, "load_reference", spy)
    checks = []
    for cell in ("stream-qwen2-0.5b.steer-off", f"{name}.steer-off"):
        out = harness.run_cell(harness.resolve_cell(bench, cell, base),
                               2**31 + 23, 0.5, False, time.perf_counter(),
                               require_platform=None)
        assert out["correct"], harness.check_lines(out)
        checks.append(out["checks"])
    assert loaded == [os.path.join(base, "reference.py"),
                      os.path.join(base, "plain_qwen2.py")]
    assert checks[0] == checks[1]
