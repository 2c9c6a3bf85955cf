"""GQA attention with KV cache, causal / sliding-window / cross variants.

The compute core dispatches to the compiled Pallas flash/decode kernels when
``cfg.attn_impl == "pallas"``; otherwise to the chunked or pure-jnp reference
path (identical math).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import shard

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]   # {"k": [B,Smax,Hkv,Dh], "v": ..., "idx": scalar}

NEG_INF = -1e30


def attn_init(rng, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(rng, 4)
    return {
        "q": L.dense_init(kq, d, cfg.num_heads * hd, dtype, bias=cfg.qkv_bias),
        "k": L.dense_init(kk, d, cfg.num_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "v": L.dense_init(kv, d, cfg.num_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "o": L.dense_init(ko, cfg.num_heads * hd, d, dtype),
    }


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


def _merge_heads(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def sdpa_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
             causal: bool, window: int = 0,
             q_offset: jax.Array | int = 0,
             kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Reference scaled-dot-product attention with GQA.

    q: [B,Sq,Hq,Dh], k/v: [B,Skv,Hkv,Dh]. ``q_offset`` is the absolute position
    of q[0] (for decode). ``kv_len`` masks positions >= kv_len (cache tail).
    ``window > 0`` restricts attention to the last ``window`` positions.
    """
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    scale = dh ** -0.5
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset          # [Sq,1]
    kpos = jnp.arange(skv)[None, :]                    # [1,Skv]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def _sdpa(q, k, v, *, cfg: ModelConfig, causal, window=0, q_offset=0,
          kv_len=None):
    impl = cfg.attn_impl
    if impl == "pallas":
        if q.shape[1] == 1:                # decode: 1 query token
            from repro.kernels.decode_attention.ops import decode_attention
            return decode_attention(q, k, v, kv_len=kv_len)
        if kv_len is None and isinstance(q_offset, int) and q_offset == 0:
            from repro.kernels.flash_attention.ops import flash_attention
            return flash_attention(q, k, v, causal=causal, window=window)
        impl = "chunked"                   # kernel has no cache-tail variant
    if impl == "chunked" and q.shape[1] > 1 and kv_len is None \
            and isinstance(q_offset, int) and q_offset == 0:
        from repro.models.chunked_attn import chunked_sdpa
        return chunked_sdpa(q, k, v, causal=causal, window=window,
                            q_chunk=cfg.q_chunk, packed=cfg.packed_causal)
    return sdpa_ref(q, k, v, causal=causal, window=window, q_offset=q_offset,
                    kv_len=kv_len)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  layers: int) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "idx": jnp.zeros((), jnp.int32)}


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array,
              causal: bool = True,
              window: int = 0,
              cache_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              cache_idx: Optional[jax.Array] = None,
              mrope_positions: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Self-attention over x: [B,S,D].

    Training/prefill: cache_kv=None -> attends within x (returns fresh K/V so
    prefill can populate the cache).
    Decode: cache_kv=(k,v) [B,Smax,Hkv,Dh] and cache_idx = #valid entries;
    x is the new token(s); returns updated (k, v).
    """
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = _split_heads(L.dense(p["q"], x), hq)
    k = _split_heads(L.dense(p["k"], x), hkv)
    v = _split_heads(L.dense(p["v"], x), hkv)
    q = shard(q, "batch", None, "model_heads")
    k = shard(k, "batch", None, "model_kv")
    v = shard(v, "batch", None, "model_kv")
    if mrope_positions is not None:
        dh = q.shape[-1]
        sec = (dh // 2 - 2 * (dh // 6), dh // 6, dh // 6)
        q = L.apply_mrope(q, mrope_positions, cfg.rope_theta, sec)
        k = L.apply_mrope(k, mrope_positions, cfg.rope_theta, sec)
    elif cfg.rope_theta > 0:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    if cache_kv is None:
        out = _sdpa(q, k, v, cfg=cfg, causal=causal, window=window)
        new_kv = (k, v)
    elif window and cache_kv[0].shape[1] == window:
        # rotating ring-buffer cache for sliding-window decode (bounded memory
        # at long_500k): slot s holds absolute position p(s) = t - ((t-s) % W)
        ck, cv = cache_kv
        t = cache_idx                       # absolute position of the new token
        slot = jnp.mod(t, window)
        ck = jax.lax.dynamic_update_slice(ck, k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, slot, 0, 0))
        slots = jnp.arange(window)
        valid = (t >= window) | (slots <= t)           # unwritten slots masked
        logits_mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None, None, :]
        b, hkv_, dh_ = ck.shape[0], ck.shape[2], ck.shape[3]
        g = hq // hkv_
        qg = q.reshape(b, 1, hkv_, g, dh_)
        lg = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (dh_ ** -0.5)
        lg = lg + logits_mask.reshape(1, 1, 1, 1, window)
        w = jax.nn.softmax(lg, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", w, cv.astype(jnp.float32))
        out = out.reshape(b, 1, hq, dh_).astype(q.dtype)
        new_kv = (ck, cv)
    else:
        ck, cv = cache_kv
        ck = jax.lax.dynamic_update_slice(ck, k, (0, cache_idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, cache_idx, 0, 0))
        kv_len = cache_idx + x.shape[1]
        out = _sdpa(q, ck, cv, cfg=cfg, causal=causal, window=window,
                    q_offset=cache_idx, kv_len=kv_len)
        new_kv = (ck, cv)
    out = _merge_heads(out)
    out = L.dense(p["o"], out)
    return shard(out, "batch", None, None), new_kv


def cross_attention(p: Params, x: jax.Array, enc_kv: Tuple[jax.Array, jax.Array],
                    cfg: ModelConfig) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V."""
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    q = _split_heads(L.dense(p["q"], x), hq)
    k, v = enc_kv
    out = _sdpa(q, k, v, cfg=cfg, causal=False)
    return L.dense(p["o"], _merge_heads(out))


def encode_cross_kv(p: Params, enc_out: jax.Array, cfg: ModelConfig
                    ) -> Tuple[jax.Array, jax.Array]:
    k = _split_heads(L.dense(p["k"], enc_out), cfg.num_kv_heads)
    v = _split_heads(L.dense(p["v"], enc_out), cfg.num_kv_heads)
    return k, v
