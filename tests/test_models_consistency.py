"""Cross-implementation consistency: decode==full forward, chunked==ref
attention, MoE dispatch paths agree, microbatching is loss-neutral."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import smoke_config
from repro.models import build_model
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.attention import sdpa_ref
from repro.models.chunked_attn import chunked_sdpa

CONSISTENCY_ARCHS = ["qwen2-0.5b", "mamba2-1.3b", "recurrentgemma-9b",
                     "granite-moe-3b-a800m", "qwen2-vl-2b"]


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = smoke_config(arch)
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="dense"))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                                cfg.vocab_size)
    if cfg.embed_stub:
        emb = jax.random.normal(jax.random.PRNGKey(2),
                                (B, S + 1, cfg.d_model)) * 0.1
        full, pre = {"embeds": emb}, {"embeds": emb[:, :S]}
        if cfg.mrope:
            mp = jnp.broadcast_to(jnp.arange(S + 1)[None, None],
                                  (3, B, S + 1)).astype(jnp.int32)
            full["mrope_positions"], pre["mrope_positions"] = mp, mp[:, :, :S]
        last = emb[:, S:S + 1]
    else:
        full, pre = {"tokens": tokens}, {"tokens": tokens[:, :S]}
        last = tokens[:, S:S + 1]
    x = T._embed_inputs(cfg, params, full)
    pos = jnp.arange(S + 1)[None, :]
    x, _, _ = T._run_stack(cfg, params, x, positions=pos,
                           mrope=full.get("mrope_positions"))
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    ref = x[:, -1] @ T._head_table(cfg, params).T
    _, cache = m.prefill(params, pre, S + 4)
    got, _ = m.decode_step(params, last, cache)
    assert float(jnp.max(jnp.abs(got[:, 0] - ref))) < 2e-3


@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([64, 128, 256]),
       hq=st.sampled_from([2, 4]), g=st.sampled_from([1, 2]),
       causal=st.booleans(), packed=st.booleans(),
       qc=st.sampled_from([16, 32, 64]))
def test_property_chunked_attention_matches_ref(s, hq, g, causal, packed, qc):
    hkv = max(1, hq // g)
    ks = jax.random.split(jax.random.PRNGKey(s + hq + qc), 3)
    q = jax.random.normal(ks[0], (1, s, hq, 16))
    k = jax.random.normal(ks[1], (1, s, hkv, 16))
    v = jax.random.normal(ks[2], (1, s, hkv, 16))
    ref = sdpa_ref(q, k, v, causal=causal, window=0)
    got = chunked_sdpa(q, k, v, causal=causal, q_chunk=qc, packed=packed)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-5


def test_moe_sort_matches_dense_oracle():
    cfg = smoke_config("granite-moe-3b-a800m")
    p = M.moe_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model)) * 0.5
    yd, auxd = M.moe_ffn_dense(p, x, cfg)
    ys, auxs = M.moe_ffn_sort(p, x, cfg, capacity_factor=8.0)
    assert float(jnp.max(jnp.abs(ys - yd))) < 1e-4
    assert abs(float(auxd) - float(auxs)) < 1e-6


def test_moe_capacity_drops_tokens_but_stays_finite():
    cfg = smoke_config("granite-moe-3b-a800m")
    p = M.moe_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model))
    y, _ = M.moe_ffn_sort(p, x, cfg, capacity_factor=0.25)   # heavy drops
    assert bool(jnp.isfinite(y).all())


def test_microbatching_is_gradient_neutral():
    """mb=1 vs mb=4 must produce the same loss and (averaged) grads."""
    from repro.launch.steps import init_train_state, make_train_step
    cfg1 = smoke_config("qwen2-0.5b")
    cfg4 = dataclasses.replace(cfg1, microbatches=4)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (8, 32),
                                          0, cfg1.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32),
                                          0, cfg1.vocab_size)}
    knobs = {"lr": jnp.float32(1e-3)}
    s1 = init_train_state(cfg1, jax.random.PRNGKey(2))
    s4 = init_train_state(cfg4, jax.random.PRNGKey(2))
    o1, m1 = jax.jit(make_train_step(cfg1))(s1, batch, knobs)
    o4, m4 = jax.jit(make_train_step(cfg4))(s4, batch, knobs)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    assert abs(float(m1["grad_norm"]) - float(m4["grad_norm"])) < 1e-3


def test_grad_compression_roundtrip_small_error():
    from repro.optim.compression import compress_grads, init_error
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64))}
    e = init_error(g)
    total = jnp.zeros((64, 64))
    exact = jnp.zeros((64, 64))
    for i in range(10):
        gc, e = compress_grads(g, e)
        total = total + gc["w"]
        exact = exact + g["w"]
    # error feedback: accumulated compressed grads track the exact sum
    rel = float(jnp.linalg.norm(total - exact) / jnp.linalg.norm(exact))
    assert rel < 0.01


# --------------------------------------------------------------------------
# residuals: stored (no rematerialisation) against recomputed
# --------------------------------------------------------------------------
def _step_batch(cfg, b=4, s=32):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(0), (b, s),
                                         0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (b, s),
                                         0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-1.3b"])
def test_stored_and_recomputed_residuals_give_the_same_step(arch):
    """The plan changes where the backward pass reads its activations from,
    not what it computes: loss, grad norm and updated params agree to f32
    round-off."""
    from repro.launch.steps import (init_train_state, jit_train_step,
                                    plan_train_step)
    cfg = smoke_config(arch)
    batch = _step_batch(cfg)
    plans = {name: plan_train_step(cfg, batch, limit)
             for name, limit in (("stored", 1 << 40), ("recomputed", 1))}
    assert {n: p.residuals for n, p in plans.items()} == \
        {"stored": "stored", "recomputed": "recomputed"}
    assert plans["stored"].residual_bytes == \
        plans["recomputed"].residual_bytes > 0
    out = {}
    for name, plan in plans.items():
        assert plan.cfg.remat == {"stored": "dots", "recomputed": True}[name]
        state = init_train_state(plan.cfg, jax.random.PRNGKey(2))
        out[name] = jit_train_step(plan.cfg)(state, batch,
                                             {"lr": jnp.float32(1e-3)})
    (s_st, m_st), (s_re, m_re) = out["stored"], out["recomputed"]
    assert float(m_st["loss"]) == pytest.approx(float(m_re["loss"]),
                                                rel=1e-6)
    assert float(m_st["grad_norm"]) == pytest.approx(
        float(m_re["grad_norm"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(s_st), jax.tree.leaves(s_re)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def _plain_xent(x, table, labels):
    logits = (x @ table.T).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def test_one_chunk_xent_matches_the_checkpointed_loss():
    """At one chunk the loss body is not rematerialised; value and
    gradients equal the same loss under ``jax.checkpoint``."""
    cfg = smoke_config("qwen2-0.5b")
    b, s = 2, 16
    assert s <= cfg.loss_chunk                   # one chunk
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, cfg.d_model))
    table = jax.random.normal(jax.random.PRNGKey(1),
                              (cfg.vocab_size, cfg.d_model)) * 0.05
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                cfg.vocab_size)
    got, (gx, gt) = jax.value_and_grad(
        lambda x, t: T.chunked_xent(cfg, x, t, labels), (0, 1))(x, table)
    want, (wx, wt) = jax.value_and_grad(
        jax.checkpoint(lambda x, t: _plain_xent(x, t, labels)),
        (0, 1))(x, table)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(jnp.max(jnp.abs(gx - wx))) < 1e-6
    assert float(jnp.max(jnp.abs(gt - wt))) < 1e-6


@pytest.mark.parametrize("s,remat", [(16, False), (64, True)])
def test_xent_checkpoints_only_past_one_chunk(s, remat):
    cfg = dataclasses.replace(smoke_config("qwen2-0.5b"), loss_chunk=16)
    x = jnp.zeros((2, s, cfg.d_model))
    table = jnp.zeros((cfg.vocab_size, cfg.d_model))
    labels = jnp.zeros((2, s), jnp.int32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x: T.chunked_xent(cfg, x, table, labels)))(x))
    assert ("remat" in jaxpr or "checkpoint" in jaxpr) == remat


@pytest.mark.parametrize("res,state,limit,want", [
    (3, 5, 10, "stored"),           # (3 + 5) x 1.25 = 10: fits exactly
    (4, 5, 10, "recomputed"),       # 11.25 over the limit
    (0, 9, 10, "recomputed"),       # the state alone leaves no margin
    (3, 5, None, None),             # no limit reported: no choice made
    (3, 5, 0, None),
])
def test_plan_residuals_on_explicit_budgets(res, state, limit, want):
    from repro.launch.steps import RESIDUAL_MARGIN, plan_residuals
    assert RESIDUAL_MARGIN == 0.25
    assert plan_residuals(res, state, limit) == want


@pytest.mark.parametrize("remat", [True, False, "dots"])
def test_plan_without_a_limit_keeps_the_configuration(remat):
    from repro.launch.steps import plan_train_step
    cfg = dataclasses.replace(smoke_config("qwen2-0.5b"), remat=remat)
    plan = plan_train_step(cfg, _step_batch(cfg), None)
    assert plan.cfg is cfg
    assert plan.residuals == ("recomputed" if remat is True else "stored")
    assert plan.bytes_limit is None and plan.state_bytes > 0


def projections(cfg, batch) -> int:
    """Matrix products without batch dimensions in the lowered train step:
    the layers' projections, each counted again where it is recomputed."""
    from repro.launch.steps import abstract_train_state, make_train_step
    knobs = {"lr": jax.ShapeDtypeStruct((), jnp.float32)}
    txt = jax.jit(make_train_step(cfg)).lower(
        abstract_train_state(cfg), batch, knobs).as_text()
    return sum("dot_general" in line and "batching_dims" not in line
               for line in txt.splitlines())


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-1.3b"])
def test_stored_plan_recomputes_no_projection(arch):
    """The stored step keeps every projection's output: it lowers with as
    many unbatched products as a step that recomputes nothing, and fewer
    than one that recomputes each layer."""
    cfg = smoke_config(arch)
    batch = jax.eval_shape(lambda: _step_batch(cfg))
    n = {r: projections(dataclasses.replace(cfg, remat=r), batch)
         for r in (False, "dots", True)}
    assert n["dots"] == n[False] < n[True], n


def test_plan_counts_the_residuals_of_one_microbatch():
    from repro.launch.steps import plan_train_step
    cfg = smoke_config("qwen2-0.5b")
    batch = _step_batch(cfg, b=8)
    one = plan_train_step(cfg, batch, None).residual_bytes
    four = plan_train_step(dataclasses.replace(cfg, microbatches=4), batch,
                           None).residual_bytes
    assert four < one
