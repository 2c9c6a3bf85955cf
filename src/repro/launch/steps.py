"""Jitted train / serve step builders with full sharding annotations.

``build_train_step`` returns (step_fn, state_shardings, batch_shardings);
``build_serve_step`` the decode equivalent. Task-level knobs (lr scale, seed,
sweep parameters from the SchalaDB work queue) enter as traced scalars so
different tasks share one executable.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

from repro.flags import scan as _flags_scan
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import shardrules as SR
from repro.models.registry import (Model, build_model, decode_input_specs,
                                   train_input_specs)
from repro.optim import apply_updates, init_opt
from repro.optim.clipping import clip_by_global_norm, global_norm
from repro.optim.compression import compress_grads
from repro.sharding import Rules, use_rules


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def _cast_params_pinned(cfg, rules, params, dtype):
    """Cast master params to compute dtype WITH sharding pinned to the
    storage sharding — forces XLA to cast-then-gather (bf16 moves over the
    wire) instead of gather-then-cast (f32 moves: 2x FSDP bytes)."""
    if rules is None:
        return _cast_tree(params, dtype)
    shardings = SR.param_shardings(cfg, rules, params)

    def one(x, sh):
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(dtype)
        return jax.lax.with_sharding_constraint(x, sh)
    return jax.tree.map(one, params, shardings)


def _split_micro(batch: Dict[str, Any], mb: int) -> Dict[str, Any]:
    """[B, ...] -> [mb, B/mb, ...] (mrope carries batch at dim 1)."""
    out = {}
    for k, x in batch.items():
        if k == "mrope_positions":        # [3,B,S] -> [mb,3,B/mb,S]
            b = x.shape[1]
            assert b % mb == 0, (k, x.shape, mb)
            out[k] = jnp.moveaxis(
                x.reshape(3, mb, b // mb, *x.shape[2:]), 1, 0)
        else:
            b = x.shape[0]
            assert b % mb == 0, (k, x.shape, mb)
            out[k] = x.reshape(mb, b // mb, *x.shape[1:])
    return out


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, rules: Optional[Rules] = None,
                    grad_compression: bool = False):
    """(state, batch, knobs) -> (state, metrics).

    state = {"params", "opt", "err"?}; knobs = {"lr": f32[]}.
    """
    model = build_model(cfg)
    dt = jnp.dtype(cfg.dtype)

    def step(state, batch, knobs):
        with use_rules(rules):
            def loss_fn(params, mbatch):
                loss, metrics = model.train_loss(
                    _cast_params_pinned(cfg, rules, params, dt), mbatch)
                return loss, metrics

            mb = max(1, cfg.microbatches)
            if mb == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"], batch)
            else:
                # gradient accumulation: scan over microbatches; residual
                # activations live only for one microbatch at a time
                mbatch0 = _split_micro(batch, mb)

                def micro(acc, mbatch):
                    (l, met), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(state["params"], mbatch)
                    acc = jax.tree.map(jnp.add, acc, g)
                    return acc, (l, met)

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, p.dtype), state["params"])
                grads, (losses, metrics) = _flags_scan(micro, zero, mbatch0)
                grads = jax.tree.map(lambda g: g / mb, grads)
                loss = jnp.mean(losses)
                metrics = jax.tree.map(jnp.mean, metrics)
            gnorm = global_norm(grads)
            gscale = jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12))
            if grad_compression:
                grads, new_err = compress_grads(grads, state["err"])
            new_params, new_opt, stats = apply_updates(
                cfg, state["params"], grads, state["opt"], knobs["lr"],
                gscale=gscale)
            out = {"params": new_params, "opt": new_opt}
            if grad_compression:
                out["err"] = new_err
            metrics = dict(metrics, grad_norm=gnorm, **stats)
            return out, metrics

    return step


def jit_train_step(cfg: ModelConfig):
    """The executor's single-device train step. The state is donated: each
    step's output reuses its buffers, so one copy of params + optimizer state
    is live (for qwen2-0.5b about 8.4 GiB in all instead of 12.7 GiB)."""
    return jax.jit(make_train_step(cfg), donate_argnums=(0,))


# The stored-residual step must fit with this share of (state + residuals)
# again on top, for its other temporaries: the f32 gradient tree, the
# optimizer's fusions and the cotangents of the backward pass.
RESIDUAL_MARGIN = 0.25


def plan_residuals(residual_bytes: int, state_bytes: int,
                   bytes_limit: Optional[int]) -> Optional[str]:
    """"stored" when the donated state and the stored step's residuals,
    with ``RESIDUAL_MARGIN`` on top, fit in ``bytes_limit``; "recomputed"
    when they do not; None when no limit is known."""
    if not bytes_limit:
        return None
    need = (state_bytes + residual_bytes) * (1 + RESIDUAL_MARGIN)
    return "stored" if need <= bytes_limit else "recomputed"


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """How the executor's step treats the forward pass's residuals."""
    residuals: str              # "stored" | "recomputed"
    residual_bytes: int         # what the stored step keeps for the backward
    state_bytes: int            # the donated params + optimizer state
    bytes_limit: Optional[int]  # the device's, None where it reports none
    cfg: ModelConfig            # what ``jit_train_step`` is built from


def _nbytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def device_bytes_limit(device=None) -> Optional[int]:
    """The device's allocatable bytes; None where the backend reports none
    (the CPU)."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("bytes_limit")


def plan_train_step(cfg: ModelConfig, batch,
                    bytes_limit: Optional[int]) -> StepPlan:
    """Plan the single-device step at ``batch``'s shapes (arrays or
    ShapeDtypeStructs) from shapes alone: no compile, no allocation.

    The stored step keeps every projection's output (``remat="dots"``) and
    recomputes only elementwise work and attention's batched products, which
    on a v5e costs less than moving those residuals through HBM; the
    recomputed step recomputes each layer's forward (``remat=True``). The
    residual bytes are the leaves of the vjp of the stored step's loss, for
    one microbatch. Where no limit is known the configuration's ``remat``
    stands."""
    stored = dataclasses.replace(cfg, remat="dots")
    model = build_model(stored)
    dt = jnp.dtype(cfg.dtype)
    mb = max(1, cfg.microbatches)
    state = abstract_train_state(cfg)

    def residuals(params, mbatch):
        def loss(p):
            return model.train_loss(_cast_tree(p, dt), mbatch)[0]
        return jax.tree.leaves(jax.vjp(loss, params)[1])

    def one_micro(b):
        return jax.tree.map(lambda x: x[0], _split_micro(b, mb))

    micro = jax.eval_shape(one_micro, batch)
    res = _nbytes(jax.eval_shape(residuals, state["params"], micro))
    state_bytes = _nbytes(state)
    choice = plan_residuals(res, state_bytes, bytes_limit)
    if choice is None:
        choice = "recomputed" if cfg.remat is True else "stored"
        planned = cfg
    else:
        planned = stored if choice == "stored" else \
            dataclasses.replace(cfg, remat=True)
    return StepPlan(residuals=choice, residual_bytes=res,
                    state_bytes=state_bytes, bytes_limit=bytes_limit,
                    cfg=planned)


def init_train_state(cfg: ModelConfig, rng, grad_compression: bool = False):
    model = build_model(cfg)
    params = model.init(rng)
    state = {"params": params, "opt": init_opt(cfg, params)}
    if grad_compression:
        from repro.optim.compression import init_error
        state["err"] = init_error(params)
    return state


def train_state_shardings(cfg: ModelConfig, rules: Rules, state) -> Any:
    out = {"params": SR.param_shardings(cfg, rules, state["params"]),
           "opt": SR.opt_shardings(cfg, rules, state["params"], state["opt"])}
    if "err" in state:
        out["err"] = SR.param_shardings(cfg, rules, state["err"])
    return out


def abstract_train_state(cfg: ModelConfig, grad_compression: bool = False):
    """ShapeDtypeStructs of the train state — no allocation (dry-run path)."""
    return jax.eval_shape(
        functools.partial(init_train_state, cfg,
                          grad_compression=grad_compression),
        jax.random.PRNGKey(0))


def lower_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                     grad_compression: bool = False):
    """Lower (not run) the train step on the production mesh."""
    rules = SR.make_rules(cfg, shape, mesh)
    step = make_train_step(cfg, rules, grad_compression)
    state_sds = abstract_train_state(cfg, grad_compression)
    state_sh = train_state_shardings(cfg, rules, state_sds)
    batch_sds = train_input_specs(cfg, shape)
    batch_sh = SR.batch_shardings(cfg, rules, batch_sds)
    knob_sds = {"lr": jax.ShapeDtypeStruct((), jnp.float32)}
    knob_sh = {"lr": NamedSharding(mesh, P())}
    jitted = jax.jit(step,
                     in_shardings=(state_sh, batch_sh, knob_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
    with mesh:
        lowered = jitted.lower(state_sds, batch_sds, knob_sds)
    return lowered


# ---------------------------------------------------------------------------
# serve (decode)
# ---------------------------------------------------------------------------
def make_serve_step(cfg: ModelConfig, rules: Optional[Rules] = None,
                    temperature: float = 0.0):
    """(params, tokens, cache, rng) -> (next_tokens, cache, logprobs)."""
    model = build_model(cfg)
    dt = jnp.dtype(cfg.dtype)

    def step(params, tokens, cache, rng):
        with use_rules(rules):
            logits, new_cache = model.decode_step(_cast_tree(params, dt),
                                                  tokens, cache)
            logits = logits[:, -1].astype(jnp.float32)
            if temperature > 0:
                nxt = jax.random.categorical(rng, logits / temperature)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            lp = jax.nn.log_softmax(logits)
            sel = jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]
            return nxt[:, None].astype(jnp.int32), new_cache, sel

    return step


def lower_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    rules = SR.make_rules(cfg, shape, mesh)
    step = make_serve_step(cfg, rules)
    model = build_model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = SR.param_shardings(cfg, rules, params_sds)
    specs = decode_input_specs(cfg, shape)
    tok_sh = rules.sharding("batch", None)
    cache_sh = SR.cache_shardings(cfg, rules, specs["cache"])
    rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jitted = jax.jit(step,
                     in_shardings=(params_sh, tok_sh, cache_sh,
                                   NamedSharding(mesh, P())),
                     out_shardings=(tok_sh, cache_sh, None),
                     donate_argnums=(2,))
    with mesh:
        lowered = jitted.lower(params_sds, specs["tokens"], specs["cache"],
                               rng_sds)
    return lowered


def shape_cells(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Dispatch: train shapes lower train_step; decode shapes serve_step."""
    if shape.kind == "train":
        return lower_train_step(cfg, shape, mesh)
    if shape.kind == "decode":
        return lower_serve_step(cfg, shape, mesh)
    # prefill: lower the prefill forward (serve-side compute)
    return lower_prefill_step(cfg, shape, mesh)


def make_prefill_step(cfg: ModelConfig, rules: Optional[Rules], max_len: int):
    model = build_model(cfg)
    dt = jnp.dtype(cfg.dtype)

    def step(params, batch):
        with use_rules(rules):
            logits, cache = model.prefill(_cast_tree(params, dt), batch,
                                          max_len)
            return jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32), \
                cache

    return step


def lower_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    from repro.models.registry import prefill_input_specs
    rules = SR.make_rules(cfg, shape, mesh)
    # decode cache allocated at prefill length + headroom
    max_len = shape.seq_len + 128
    step = make_prefill_step(cfg, rules, max_len)
    model = build_model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = SR.param_shardings(cfg, rules, params_sds)
    specs = prefill_input_specs(cfg, shape)
    batch_sh = SR.batch_shardings(cfg, rules, specs)
    jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
    with mesh:
        lowered = jitted.lower(params_sds, specs)
    return lowered
