"""Mixture-of-Experts FFN: top-k router + three dispatch paths.

- "dense": all-experts einsum oracle (exact, FLOP-wasteful x E/top_k). Tests.
- "sort": capacity-bounded sort-based dispatch, single-device reference of the
  production algorithm.
- EP (automatic when a mesh rule set is active and the "model" axis >1):
  ``shard_map`` expert parallelism with *local* dispatch — routing runs under
  GSPMD, token->expert scatter happens per data shard against the local expert
  slab, partial outputs are psum'd over the "model" axis. This avoids the
  GSPMD failure mode where the [T*k, D] dispatch gather is replicated per
  device (measured: 1.17 TB/device temp on kimi-k2 train_4k; see EXPERIMENTS
  §Perf) and is the TPU-native analogue of all-to-all MoE dispatch.

Expert weights are stored padded to a multiple of EP_SHARDS (=16, the "model"
axis of the production mesh) so the expert dim always shards evenly; padding
experts receive no routing mass (router emits only the true E logits).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import current_rules, shard

Params = Dict[str, Any]

_shard_map = functools.partial(jax.shard_map, check_vma=False)

EP_SHARDS = 16          # production "model" axis size; expert-dim padding unit
CAPACITY_FACTOR = 1.25


def _epad(e: int) -> int:
    return ((e + EP_SHARDS - 1) // EP_SHARDS) * EP_SHARDS


def moe_init(rng, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_ff, m.num_experts
    ep = _epad(e)
    kr, ku, kg, kd = jax.random.split(rng, 4)
    scale = d ** -0.5
    p: Params = {
        "router": jax.random.normal(kr, (d, e), jnp.float32) * scale,
        "up": jax.random.normal(ku, (ep, d, f), dtype) * scale,
        "down": jax.random.normal(kd, (ep, f, d), dtype) * (f ** -0.5),
    }
    if cfg.glu:
        p["gate"] = jax.random.normal(kg, (ep, d, f), dtype) * scale
    return p


def route(p: Params, x2d: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router: returns (weights [T,k], expert_idx [T,k], aux_loss scalar)."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ p["router"])          # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)                     # [T,k]
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                               # mean prob per e
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=1),
        axis=0)                                                # frac routed per e
    aux = m.num_experts * jnp.sum(me * ce)
    return w.astype(x2d.dtype), idx, aux


def _expert_ffn(p: Params, buf: jax.Array, cfg: ModelConfig,
                annotate: bool = True) -> jax.Array:
    """buf: [E(,loc), C, D] -> same, via per-expert batched matmuls."""
    up, gate, down = p["up"], p.get("gate"), p["down"]
    h = jnp.einsum("ecd,edf->ecf", buf, up)
    if cfg.glu:
        h = L.act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", buf, gate)) * h
    else:
        h = L.act_fn(cfg.act)(h)
    if annotate:
        h = shard(h, "model_expert", None, None)
    return jnp.einsum("ecf,efd->ecd", h, down)


def _rank_in_expert(ek: jax.Array, counts: jax.Array, num_e: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-token rank among same-expert assignments + updated counts.

    ek: [T] expert ids for this top-k slot; counts: [E] running totals from
    earlier slots. All O(T) / O(E) memory (no [T,E] one-hots).
    """
    t = ek.shape[0]
    order = jnp.argsort(ek)
    sorted_e = ek[order]
    cnt = jax.ops.segment_sum(jnp.ones((t,), jnp.int32), sorted_e,
                              num_segments=num_e)
    starts = jnp.cumsum(cnt) - cnt
    rank_sorted = jnp.arange(t, dtype=jnp.int32) - starts[sorted_e]
    rank = jnp.zeros((t,), jnp.int32).at[order].set(rank_sorted)
    return rank + counts[ek], counts + cnt


def _dispatch_compute(p_local: Params, x2d: jax.Array, idx: jax.Array,
                      w: jax.Array, cfg: ModelConfig, *, e_base,
                      e_loc: int, cap: int) -> jax.Array:
    """Scatter tokens to the local expert slab, run FFN, gather back.

    x2d: [T,D]; idx/w: [T,k]; expert slab covers [e_base, e_base+e_loc).
    Loops over the k slots so no [T*k, D] intermediate is ever built.
    """
    m = cfg.moe
    t, d = x2d.shape
    counts = jnp.zeros((m.num_experts,), jnp.int32)
    buf = jnp.zeros((e_loc * cap + 1, d), x2d.dtype)
    dests = []
    for kk in range(m.top_k):
        ek = idx[:, kk]
        rank, counts = _rank_in_expert(ek, counts, m.num_experts)
        loc = ek - e_base
        keep = (loc >= 0) & (loc < e_loc) & (rank < cap)
        dest = jnp.where(keep, loc * cap + rank, e_loc * cap)
        buf = buf.at[dest].add(x2d * keep[:, None].astype(x2d.dtype))
        dests.append((dest, keep))
    out_buf = _expert_ffn(p_local, buf[:-1].reshape(e_loc, cap, d), cfg,
                          annotate=False)
    out_buf = jnp.concatenate([out_buf.reshape(e_loc * cap, d),
                               jnp.zeros((1, d), x2d.dtype)], axis=0)
    out2d = jnp.zeros((t, d), x2d.dtype)
    for kk, (dest, keep) in enumerate(dests):
        gk = w[:, kk] * keep.astype(x2d.dtype)
        out2d = out2d + out_buf[dest] * gk[:, None]
    return out2d


def moe_ffn_sort(p: Params, x: jax.Array, cfg: ModelConfig,
                 capacity_factor: float = CAPACITY_FACTOR
                 ) -> Tuple[jax.Array, jax.Array]:
    """Single-device reference of the capacity dispatch. x: [B,S,D]."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2d = x.reshape(t, d)
    w, idx, aux = route(p, x2d, cfg)
    cap = int(max(1, (t * m.top_k * capacity_factor) // m.num_experts))
    out2d = _dispatch_compute(p, x2d, idx, w, cfg, e_base=0,
                              e_loc=_epad(m.num_experts), cap=cap)
    return out2d.reshape(b, s, d), aux


def moe_ffn_ep(p: Params, x: jax.Array, cfg: ModelConfig,
               capacity_factor: float = CAPACITY_FACTOR
               ) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert-parallel path (see module docstring)."""
    rules = current_rules()
    mesh = rules.mesh
    m = cfg.moe
    b, s, d = x.shape
    x = shard(x, "batch", None, None)
    w, idx, aux = route(p, x.reshape(b * s, d), cfg)
    w3 = shard(w.reshape(b, s, m.top_k), "batch", None, None)
    i3 = shard(idx.reshape(b, s, m.top_k), "batch", None, None)

    batch_phys = rules.physical("batch")
    ep = _epad(m.num_experts)
    e_loc = ep // mesh.shape["model"]
    dp = 1
    if batch_phys:
        for a in (batch_phys if isinstance(batch_phys, tuple)
                  else (batch_phys,)):
            dp *= mesh.shape[a]
    t_loc = (b // dp) * s
    cap = int(max(1, (t_loc * m.top_k * capacity_factor) // m.num_experts))

    bspec = P(batch_phys, None, None)
    wspecs = {k: P("model", None, None) for k in ("up", "gate", "down")
              if k in p}

    def local_fn(up, gate, down, xl, wl, il):
        rank_m = jax.lax.axis_index("model")
        bl, sl, dl = xl.shape
        pl = {"up": up, "down": down}
        if gate is not None:
            pl["gate"] = gate
        out2d = _dispatch_compute(
            pl, xl.reshape(bl * sl, dl), il.reshape(bl * sl, m.top_k),
            wl.reshape(bl * sl, m.top_k), cfg,
            e_base=rank_m * e_loc, e_loc=e_loc, cap=cap)
        out2d = jax.lax.psum(out2d, "model")
        return out2d.reshape(bl, sl, dl)

    fn = _shard_map(
        local_fn, mesh=mesh,
        in_specs=(P("model", None, None),
                  P("model", None, None) if "gate" in p else P(),
                  P("model", None, None), bspec, bspec, bspec),
        out_specs=bspec)
    out = fn(p["up"], p.get("gate"), p["down"], x, w3, i3)
    return out, aux


def moe_ffn_dense(p: Params, x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """All-experts oracle (exact, no capacity drops)."""
    m = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    w, idx, aux = route(p, x2d, cfg)
    up, down = p["up"][: m.num_experts], p["down"][: m.num_experts]
    h = jnp.einsum("td,edf->tef", x2d, up)
    if cfg.glu:
        gate = p["gate"][: m.num_experts]
        h = L.act_fn(cfg.act)(jnp.einsum("td,edf->tef", x2d, gate)) * h
    else:
        h = L.act_fn(cfg.act)(h)
    y_all = jnp.einsum("tef,efd->ted", h, down)                # [T,E,D]
    sel = jax.nn.one_hot(idx, m.num_experts, dtype=x.dtype)    # [T,k,E]
    gates = jnp.einsum("tk,tke->te", w, sel)                   # [T,E]
    out2d = jnp.einsum("te,ted->td", gates, y_all)
    return out2d.reshape(b, s, d), aux


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe.dispatch == "dense":
        return moe_ffn_dense(p, x, cfg)
    rules = current_rules()
    if rules is not None and "model" in rules.mesh.axis_names \
            and rules.mesh.shape["model"] > 1:
        return moe_ffn_ep(p, x, cfg)
    return moe_ffn_sort(p, x, cfg)
