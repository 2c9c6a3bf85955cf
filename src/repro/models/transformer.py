"""Decoder-only LM assembly for all decoder families (dense / moe / ssm /
hybrid / vlm), with scan-over-layers, remat, KV / recurrent caches, and
sequence-chunked cross-entropy for big vocabularies.

Params are dict pytrees whose per-layer leaves are stacked on a leading [L]
axis so the whole stack lowers as one ``lax.scan`` body (small HLO, fast
compiles at 512 devices).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

from repro.flags import scan as _flags_scan
import jax
import jax.numpy as jnp

from repro.configs.base import (FAMILY_DENSE, FAMILY_HYBRID, FAMILY_MOE,
                                FAMILY_SSM, FAMILY_VLM, ModelConfig)
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.sharding import shard

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------
def _attn_block_init(rng, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    p = {"ln1": L.norm_init(cfg.d_model, cfg.norm, dtype),
         "attn": A.attn_init(k1, cfg, dtype)}
    if cfg.d_ff or cfg.moe:
        p["ln2"] = L.norm_init(cfg.d_model, cfg.norm, dtype)
        if cfg.family == FAMILY_MOE:
            p["moe"] = M.moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.glu, dtype)
    return p


def _ssm_block_init(rng, cfg: ModelConfig, dtype) -> Params:
    return {"ln1": L.norm_init(cfg.d_model, cfg.norm, dtype),
            "mixer": S.ssd_init(rng, cfg, dtype)}


def _rec_block_init(rng, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    return {"ln1": L.norm_init(cfg.d_model, cfg.norm, dtype),
            "mixer": R.rglru_init(k1, cfg, dtype),
            "ln2": L.norm_init(cfg.d_model, cfg.norm, dtype),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.glu, dtype)}


def _stack_init(rng, n: int, init_fn) -> Params:
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def _hybrid_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_groups, n_tail) for the (rec,rec,attn) pattern."""
    plen = len(cfg.rglru.pattern)
    return cfg.num_layers // plen, cfg.num_layers % plen


def init_params(cfg: ModelConfig, rng) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)   # master params; steps cast to cfg.dtype
    k_embed, k_layers, k_head, k_tail = jax.random.split(rng, 4)
    p: Params = {"embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                                       dtype),
                 "final_norm": L.norm_init(cfg.d_model, cfg.norm, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = L.embed_init(k_head, cfg.vocab_size, cfg.d_model, dtype)

    if cfg.family in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        p["layers"] = _stack_init(
            k_layers, cfg.num_layers,
            lambda r: _attn_block_init(r, cfg, dtype))
    elif cfg.family == FAMILY_SSM:
        p["layers"] = _stack_init(
            k_layers, cfg.num_layers,
            lambda r: _ssm_block_init(r, cfg, dtype))
    elif cfg.family == FAMILY_HYBRID:
        ng, nt = _hybrid_counts(cfg)

        def group_init(r):
            ks = jax.random.split(r, len(cfg.rglru.pattern))
            g = {}
            for i, kind in enumerate(cfg.rglru.pattern):
                g[f"pos{i}"] = (_rec_block_init(ks[i], cfg, dtype)
                                if kind == "rec"
                                else _attn_block_init(ks[i], cfg, dtype))
            return g
        p["groups"] = _stack_init(k_layers, ng, group_init)
        if nt:
            p["tail"] = _stack_init(
                k_tail, nt, lambda r: _rec_block_init(r, cfg, dtype))
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# blocks (train/prefill: cache=None; decode: cache per layer)
# ---------------------------------------------------------------------------
def _attn_block(p, x, cfg: ModelConfig, *, positions, window=0, cache=None,
                idx=None, mrope=None):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    out, new_kv = A.attention(
        p["attn"], h, cfg, positions=positions, causal=True, window=window,
        cache_kv=cache, cache_idx=idx, mrope_positions=mrope)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        out, aux = M.moe_ffn(p["moe"], h, cfg)
        x = x + out
    elif "mlp" in p:
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        x = x + L.mlp(p["mlp"], h, cfg.act, cfg.glu)
    return x, new_kv, aux


def _ssm_block(p, x, cfg: ModelConfig, *, cache=None):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    out, new_state = S.ssd_mixer(p["mixer"], h, cfg, state=cache)
    return x + out, new_state


def _rec_block(p, x, cfg: ModelConfig, *, cache=None):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    out, new_state = R.rglru_block(p["mixer"], h, cfg, state=cache)
    x = x + out
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    x = x + L.mlp(p["mlp"], h, cfg.act, cfg.glu)
    return x, new_state


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------
def _maybe_ckpt(cfg: ModelConfig, fn):
    # prevent_cse=False: safe under scan (which already isolates iterations)
    # and lets XLA keep the bf16 carry as the saved residual instead of an
    # upcast f32 copy (halves per-layer activation stash)
    if not cfg.remat:
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else None)
    return jax.checkpoint(fn, prevent_cse=False, policy=policy)


def _run_stack(cfg: ModelConfig, params: Params, x, *, positions,
               caches=None, idx=None, mrope=None):
    """Returns (x, new_caches, total_aux)."""
    fam = cfg.family

    with_cache = caches is not None

    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        if with_cache:
            def body(carry, layer):
                h, aux = carry
                lp, lc = layer
                h, new_kv, a = _attn_block(lp, h, cfg, positions=positions,
                                           cache=(lc["k"], lc["v"]), idx=idx,
                                           mrope=mrope)
                return (h, aux + a), {"k": new_kv[0], "v": new_kv[1]}
            xs = (params["layers"], caches)
        else:
            def body(carry, lp):
                h, aux = carry
                h, _, a = _attn_block(lp, h, cfg, positions=positions,
                                      mrope=mrope)
                return (h, aux + a), None
            xs = params["layers"]
        (x, aux), new_caches = _flags_scan(_maybe_ckpt(cfg, body),
                                            (x, jnp.zeros((), jnp.float32)),
                                            xs)
        return x, new_caches, aux

    if fam == FAMILY_SSM:
        if with_cache:
            def body(h, layer):
                lp, lc = layer
                return _ssm_block(lp, h, cfg, cache=lc)
            xs = (params["layers"], caches)
        else:
            def body(h, lp):
                h, _ = _ssm_block(lp, h, cfg)
                return h, None
            xs = params["layers"]
        x, new_caches = _flags_scan(_maybe_ckpt(cfg, body), x, xs)
        return x, new_caches, jnp.zeros((), jnp.float32)

    if fam == FAMILY_HYBRID:
        pattern = cfg.rglru.pattern
        window = cfg.rglru.window

        def make_body(has_cache):
            def body(h, layer):
                lp, lc = layer if has_cache else (layer, None)
                outs = {}
                for i, kind in enumerate(pattern):
                    key = f"pos{i}"
                    c = None if lc is None else lc.get(key)
                    if kind == "rec":
                        h, st = _rec_block(lp[key], h, cfg, cache=c)
                        if has_cache:
                            outs[key] = st
                    else:
                        kv = None if c is None else (c["k"], c["v"])
                        h, new_kv, _ = _attn_block(
                            lp[key], h, cfg, positions=positions,
                            window=window, cache=kv, idx=idx)
                        if has_cache:
                            outs[key] = {"k": new_kv[0], "v": new_kv[1]}
                return h, (outs if has_cache else None)
            return body

        if with_cache:
            xs = (params["groups"], caches["groups"])
        else:
            xs = params["groups"]
        x, new_g = _flags_scan(_maybe_ckpt(cfg, make_body(with_cache)), x, xs)

        new_tail = None
        if "tail" in params:
            if with_cache:
                def tail_body(h, layer):
                    lp, lc = layer
                    return _rec_block(lp, h, cfg, cache=lc)
                xs = (params["tail"], caches["tail"])
            else:
                def tail_body(h, lp):
                    h, _ = _rec_block(lp, h, cfg)
                    return h, None
                xs = params["tail"]
            x, new_tail = _flags_scan(_maybe_ckpt(cfg, tail_body), x, xs)
        if not with_cache:
            return x, None, jnp.zeros((), jnp.float32)
        return x, {"groups": new_g, "tail": new_tail}, \
            jnp.zeros((), jnp.float32)

    raise ValueError(fam)


# ---------------------------------------------------------------------------
# losses / heads
# ---------------------------------------------------------------------------
def _head_table(cfg: ModelConfig, params: Params) -> jax.Array:
    return params["embed"]["table"] if cfg.tie_embeddings \
        else params["head"]["table"]


def chunked_xent(cfg: ModelConfig, x: jax.Array, table: jax.Array,
                 labels: jax.Array) -> jax.Array:
    """Sequence-chunked mean cross-entropy. x: [B,S,D]; labels: [B,S].

    Never materializes [B,S,V]; peak is [B,chunk,V] (sharded over model_vocab).

    The table is resharded to a VOCAB-sharded view once per step: tied archs
    store it D-sharded (cheap embedding lookups), but contracting a D-sharded
    table in the loss produces [B,chunk,V] all-reduces/gathers (measured
    4 x 32 GiB f32 AGs on recurrentgemma-9b; see EXPERIMENTS §Perf). With the
    V-sharded view each model rank computes its V/16 logit slice locally.
    """
    table = shard(table, "model_vocab", None)
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, d)
    lc = labels.reshape(b, nc, chunk)

    def body(tot, args):
        xi, li = args                       # [B,chunk,D], [B,chunk]
        logits = (xi @ table.T).astype(jnp.float32)
        logits = shard(logits, "batch", None, "model_vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    if nc > 1:      # one chunk: recomputing it would save no memory
        body = jax.checkpoint(body)
    tot, _ = _flags_scan(body, jnp.zeros((), jnp.float32),
                          (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    return tot / (b * s)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    if cfg.embed_stub:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        # gather from an explicitly replicated table view: XLA's SPMD
        # partitioner mis-partitions the gather when the table is sharded on
        # the offset dim (verifier failure: all-reduce + oversized
        # dynamic-slice at 512 devices). The forced replication costs one
        # table all-gather per microbatch — visible in the collective
        # roofline term and tracked as a §Perf hillclimb item.
        table = shard(params["embed"]["table"], None, None)
        x = jnp.take(table, batch["tokens"], axis=0)
    return shard(x, "batch", "seq", None)


def train_loss(cfg: ModelConfig, params: Params, batch: Dict[str, Any]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = _embed_inputs(cfg, params, batch)
    b, s = x.shape[:2]
    positions = jnp.arange(s)[None, :]
    mrope = batch.get("mrope_positions") if cfg.mrope else None
    x, _, aux = _run_stack(cfg, params, x, positions=positions, mrope=mrope)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    loss = chunked_xent(cfg, x, _head_table(cfg, params), batch["labels"])
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss, {"loss": loss, "aux_loss": aux}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    dtype = _dtype(cfg)
    fam = cfg.family
    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        cache = A.init_kv_cache(cfg, batch, max_len, dtype, cfg.num_layers)
        return {"layers": {"k": cache["k"], "v": cache["v"]},
                "idx": jnp.zeros((), jnp.int32)}
    if fam == FAMILY_SSM:
        st = S.init_ssm_state(cfg, batch, cfg.num_layers, dtype)
        return {"layers": st, "idx": jnp.zeros((), jnp.int32)}
    if fam == FAMILY_HYBRID:
        ng, nt = _hybrid_counts(cfg)
        w = min(cfg.rglru.window, max_len)
        hd = cfg.resolved_head_dim
        groups: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.rglru.pattern):
            if kind == "rec":
                st = R.init_rglru_state(cfg, batch, ng, dtype)
            else:
                st = {"k": jnp.zeros((ng, batch, w, cfg.num_kv_heads, hd),
                                     dtype),
                      "v": jnp.zeros((ng, batch, w, cfg.num_kv_heads, hd),
                                     dtype)}
            groups[f"pos{i}"] = st
        tail = R.init_rglru_state(cfg, batch, nt, dtype) if nt else None
        return {"layers": {"groups": groups, "tail": tail},
                "idx": jnp.zeros((), jnp.int32)}
    raise ValueError(fam)


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            max_len: int) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt, build the decode cache, return last-position logits."""
    x = _embed_inputs(cfg, params, batch)
    b, s = x.shape[:2]
    positions = jnp.arange(s)[None, :]
    mrope = batch.get("mrope_positions") if cfg.mrope else None
    cache = init_cache(cfg, b, max_len)
    fam = cfg.family

    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        # run without cache, then scatter fresh K/V into the cache
        def body(carry, lp):
            h, aux = carry
            h, kv, a = _attn_block(lp, h, cfg, positions=positions,
                                   mrope=mrope)
            return (h, aux + a), {"k": kv[0], "v": kv[1]}
        (x, _), fresh = _flags_scan(_maybe_ckpt(cfg, body),
                                     (x, jnp.zeros((), jnp.float32)),
                                     params["layers"])
        ck = jax.lax.dynamic_update_slice(
            cache["layers"]["k"], fresh["k"].astype(_dtype(cfg)),
            (0, 0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["layers"]["v"], fresh["v"].astype(_dtype(cfg)),
            (0, 0, 0, 0, 0))
        cache = {"layers": {"k": ck, "v": cv},
                 "idx": jnp.asarray(s, jnp.int32)}
    elif fam == FAMILY_SSM:
        def body(carry, layer):
            h = carry
            lp = layer
            hn = L.apply_norm(lp["ln1"], h, cfg.norm)
            out, st = S.ssd_mixer(lp["mixer"], hn, cfg, state=None)
            # recover final conv state from the tail of the conv input
            return h + out, st
        # For prefill we recompute states via the chunked form; conv state is
        # the last (conv_width-1) conv inputs — handled inside ssd_mixer when
        # state propagation is requested. Simpler: run mixers individually.
        x, states = _ssm_prefill(cfg, params, x)
        cache = {"layers": states, "idx": jnp.asarray(s, jnp.int32)}
    elif fam == FAMILY_HYBRID:
        x, states = _hybrid_prefill(cfg, params, x, positions, max_len)
        cache = {"layers": states, "idx": jnp.asarray(s, jnp.int32)}
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = x[:, -1:] @ _head_table(cfg, params).T
    return logits, cache


def _ssm_prefill(cfg, params, x):
    def body(h, lp):
        hn = L.apply_norm(lp["ln1"], h, cfg.norm)
        out, st = S.ssd_mixer(lp["mixer"], hn, cfg, state=None)
        return h + out, st
    x, states = _flags_scan(_maybe_ckpt(cfg, body), x, params["layers"])
    return x, states


def _hybrid_prefill(cfg, params, x, positions, max_len):
    w = min(cfg.rglru.window, max_len)
    s = x.shape[1]

    def scatter_window(kv):
        k, v = kv
        # place the last w entries at slot = pos % w (ring layout)
        pos = jnp.arange(s - w, s) if s >= w else jnp.arange(s)
        slots = jnp.mod(pos, w)
        ck = jnp.zeros((k.shape[0], w) + k.shape[2:], k.dtype)
        ck = ck.at[:, slots].set(k[:, -len(slots):] if s >= w else k)
        cv = jnp.zeros_like(ck)
        cv = cv.at[:, slots].set(v[:, -len(slots):] if s >= w else v)
        return {"k": ck, "v": cv}

    def body(h, lp):
        outs = {}
        for i, kind in enumerate(cfg.rglru.pattern):
            key = f"pos{i}"
            if kind == "rec":
                hn = L.apply_norm(lp[key]["ln1"], h, cfg.norm)
                out, st = R.rglru_block(lp[key]["mixer"], hn, cfg, state=None)
                h = h + out
                hn = L.apply_norm(lp[key]["ln2"], h, cfg.norm)
                h = h + L.mlp(lp[key]["mlp"], hn, cfg.act, cfg.glu)
                outs[key] = st
            else:
                h, kv, _ = _attn_block(lp[key], h, cfg, positions=positions,
                                       window=cfg.rglru.window)
                outs[key] = scatter_window(kv)
        return h, outs

    x, groups = _flags_scan(_maybe_ckpt(cfg, body), x, params["groups"])
    tail = None
    if "tail" in params:
        def tail_body(h, lp):
            hn = L.apply_norm(lp["ln1"], h, cfg.norm)
            out, st = R.rglru_block(lp["mixer"], hn, cfg, state=None)
            h = h + out
            hn = L.apply_norm(lp["ln2"], h, cfg.norm)
            h = h + L.mlp(lp["mlp"], hn, cfg.act, cfg.glu)
            return h, st
        x, tail = _flags_scan(_maybe_ckpt(cfg, tail_body), x, params["tail"])
    return x, {"groups": groups, "tail": tail}


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step. tokens: [B,1] (or embeds [B,1,D] for stub archs)."""
    if cfg.embed_stub and tokens.ndim == 3:
        x = tokens.astype(_dtype(cfg))
    else:
        x = L.embed(params["embed"], tokens)
    idx = cache["idx"]
    positions = idx[None, None] * jnp.ones((x.shape[0], 1), jnp.int32)
    mrope = None
    if cfg.mrope:
        mrope = jnp.broadcast_to(positions[None], (3,) + positions.shape)
    x, new_caches, _ = _run_stack(cfg, params, x, positions=positions,
                                  caches=cache["layers"], idx=idx,
                                  mrope=mrope)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    logits = x[:, -1:] @ _head_table(cfg, params).T
    return logits, {"layers": new_caches, "idx": idx + 1}
