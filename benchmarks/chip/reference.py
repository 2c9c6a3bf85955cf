"""Plain float32 reference of the qwen2 train-step payload, and its control.

Follows the published Qwen2 decoder (arXiv:2407.10671): token embedding,
per layer an RMSNorm, grouped-query attention with biased q/k/v projections,
rotary positions (half-split form, theta from the configuration) and a
causal softmax, an unbiased output projection, a residual add, an RMSNorm
and a SwiGLU MLP; a final RMSNorm and logits through the tied embedding
table; mean next-token cross-entropy. The optimizer is AdamW with the
gradient clipped to a global norm of 1, as the configuration states.

Imports nothing of the program. Every matrix product runs in float32 at
``highest`` precision. The control (``quant="fp8"``) rounds the operands of
every forward matrix product to float8_e4m3fn with a per-tensor scale (the
backward pass sees the rounded operands through a straight-through
estimator): the step below bfloat16 that a later change might take.

Weights are made here, on the device, from a key (``weight_leaf``, one
leaf, and ``make_weights``, all of them in one jitted call): the benchmark
gives them to the program and to this reference alike, and makes leaves
again where it needs their initial values.

The interface the harness loads a payload's reference by (``payload
["reference"]`` names this file): ``weight_shapes``, ``weight_leaf``,
``make_weights``, ``leaf_norms``, ``train_readings``, ``program_overrides``
and ``train_step_flops``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops

Params = Dict[str, jax.Array]


def weight_shapes(p: Dict[str, object]) -> Dict[str, Tuple[int, ...]]:
    """Flat weight names and shapes; per-layer weights stacked on [L]."""
    L, d = int(p["num_layers"]), int(p["d_model"])
    hq, hkv, hd = int(p["num_heads"]), int(p["num_kv_heads"]), \
        int(p["head_dim"])
    ff, v = int(p["d_ff"]), int(p["vocab_size"])
    return {
        "embed": (v, d), "final_norm": (d,),
        "layers.ln1": (L, d), "layers.ln2": (L, d),
        "layers.q.w": (L, d, hq * hd), "layers.q.b": (L, hq * hd),
        "layers.k.w": (L, d, hkv * hd), "layers.k.b": (L, hkv * hd),
        "layers.v.w": (L, d, hkv * hd), "layers.v.b": (L, hkv * hd),
        "layers.o.w": (L, hq * hd, d),
        "layers.gate.w": (L, d, ff), "layers.up.w": (L, d, ff),
        "layers.down.w": (L, ff, d),
    }


_NORMS = ("final_norm", "layers.ln1", "layers.ln2")


# jitted, so that a leaf made alone rounds as it does inside make_weights
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _normal(key: jax.Array, index: int, count: int,
            shape: Tuple[int, ...], std: float) -> jax.Array:
    return std * jax.random.normal(jax.random.split(key, count)[index],
                                   shape, jnp.float32)


def weight_leaf(p: Dict[str, object], key: jax.Array, name: str) -> jax.Array:
    """One weight of the published Qwen2 initialisation, float32 on the
    default device: normal(0, initializer_range) for every projection and
    the embedding, from the key's ``i``-th split, ``i`` the name's place in
    sorted order; zero biases; unit norm scales."""
    shapes = weight_shapes(p)
    shape = shapes[name]
    if name.endswith(".b"):
        return jnp.zeros(shape, jnp.float32)
    if name in _NORMS:
        return jnp.ones(shape, jnp.float32)
    return _normal(key, sorted(shapes).index(name), len(shapes), shape,
                   float(p["initializer_range"]))


def make_weights(p: Dict[str, object], key: jax.Array) -> Params:
    """Every weight in one jitted call: the dict of ``weight_leaf`` over
    ``weight_shapes``."""
    names = sorted(weight_shapes(p))
    return jax.jit(lambda k: {n: weight_leaf(p, k, n) for n in names})(key)


# the program's ModelConfig fields that the payload sets
_PROGRAM_FIELDS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
                   "head_dim", "d_ff", "vocab_size", "rope_theta", "qkv_bias",
                   "tie_embeddings", "dtype", "param_dtype")


def program_overrides(p: Dict[str, object]) -> Dict[str, object]:
    """The fields of the program's configuration that the payload sets,
    by name, laid over the published configuration of ``p["arch"]``."""
    return {k: p[k] for k in _PROGRAM_FIELDS}


def train_step_flops(p: Dict[str, object]) -> float:
    """Analytic FLOPs of one task's train step (``flops.py``)."""
    return flops.dense_train_step_flops(p)


def _fake_fp8(x: jax.Array) -> jax.Array:
    """Per-tensor scaled float8_e4m3fn rounding, straight-through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(quant: Optional[str]):
    if quant is None:
        return lambda a, b, spec: jnp.einsum(spec, a, b)
    if quant == "fp8":
        return lambda a, b, spec: jnp.einsum(spec, _fake_fp8(a), _fake_fp8(b))
    raise ValueError(f"unknown quant {quant!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, Dh]: rotate the first half against the second half."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(w: Params, tokens: jax.Array, labels: jax.Array,
            p: Dict[str, object], quant: Optional[str] = None) -> jax.Array:
    mm = _mm(quant)
    eps, theta = float(p["rms_norm_eps"]), float(p["rope_theta"])
    hq, hkv, hd = int(p["num_heads"]), int(p["num_kv_heads"]), \
        int(p["head_dim"])
    b, s = tokens.shape
    x = w["embed"][tokens]
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = (mm(h, lw["q.w"], "bsd,de->bse") + lw["q.b"]).reshape(b, s, hq, hd)
        k = (mm(h, lw["k.w"], "bsd,de->bse") + lw["k.b"]).reshape(b, s, hkv, hd)
        v = (mm(h, lw["v.w"], "bsd,de->bse") + lw["v.b"]).reshape(b, s, hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = hq // hkv                  # q head i reads kv head i // rep
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = mm(q, k, "bqhd,bkhd->bhqk") / np.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -1e30)
        att = mm(jax.nn.softmax(sc, -1), v, "bhqk,bkhd->bqhd")
        x = x + mm(att.reshape(b, s, hq * hd), lw["o.w"], "bse,ed->bsd")
        h = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(mm(h, lw["gate.w"], "bsd,df->bsf"))
        u = mm(h, lw["up.w"], "bsd,df->bsf")
        return x + mm(g * u, lw["down.w"], "bsf,fd->bsd"), None

    stacked = {n[len("layers."):]: a for n, a in w.items()
               if n.startswith("layers.")}
    # recompute each layer in the backward pass: the same numbers, and the
    # reference then fits beside nothing else on one 16 GB chip
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    x = _rms(x, w["final_norm"], eps)
    logits = mm(x, w["embed"], "bsd,vd->bsv")
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def leaf_norms(tree: Params) -> Dict[str, jax.Array]:
    """L2 norm of every leaf; a stacked per-layer weight gives one norm per
    layer (each layer's matrix is a leaf of its own)."""
    out = {}
    for n, a in tree.items():
        a = a.astype(jnp.float32)
        out[n] = (jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
                  if n.startswith("layers.")
                  else jnp.sqrt(jnp.sum(a * a)))
    return out


def _adamw_step(w, m, v, tokens, labels, step, p, quant):
    opt = p["optimizer"]
    b1, b2 = float(opt["beta1"]), float(opt["beta2"])
    eps, wd, lr = float(opt["eps"]), float(opt["weight_decay"]), \
        float(opt["lr"])
    loss, g = jax.value_and_grad(loss_fn)(w, tokens, labels, p, quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    gscale = jnp.minimum(1.0, float(opt["clip_global_norm"])
                         / jnp.maximum(gnorm, 1e-12))
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    nw, nm, nv = {}, {}, {}
    for n in w:
        gs = g[n] * gscale
        nm[n] = b1 * m[n] + (1 - b1) * gs
        nv[n] = b2 * v[n] + (1 - b2) * gs * gs
        u = (nm[n] / bc1) / (jnp.sqrt(nv[n] / bc2) + eps) + wd * w[n]
        nw[n] = w[n] - lr * u
    gn = leaf_norms({n: g[n] * gscale for n in g})
    return nw, nm, nv, loss, gnorm, gn


def train_readings(w0: Params, batches: Sequence[Dict[str, np.ndarray]],
                   p: Dict[str, object], remake_w0,
                   quant: Optional[str] = None) -> Dict[str, object]:
    """Run ``len(batches)`` AdamW steps from ``w0`` (consumed) and read what
    the benchmark compares: each step's loss and global gradient norm, the
    per-leaf norms of the first clipped gradient, and the per-leaf norms of
    the parameters' change over all the steps. ``remake_w0()`` makes the
    initial weights again (from the seed), so they need not be kept."""
    step_fn = jax.jit(
        lambda w, m, v, t, l, st: _adamw_step(w, m, v, t, l, st, p, quant),
        donate_argnums=(0, 1, 2))
    m = {n: jnp.zeros_like(a) for n, a in w0.items()}
    v = {n: jnp.zeros_like(a) for n, a in w0.items()}
    w = w0
    losses: List[float] = []
    gnorms: List[float] = []
    grad1 = None
    with jax.default_matmul_precision("highest"):
        for i, bt in enumerate(batches):
            w, m, v, loss, gnorm, gn = step_fn(
                w, m, v, jnp.asarray(bt["tokens"]), jnp.asarray(bt["labels"]),
                jnp.float32(i + 1))
            losses.append(float(loss))
            gnorms.append(float(gnorm))
            if i == 0:
                grad1 = {n: np.asarray(x) for n, x in gn.items()}
        del m, v
        w0 = remake_w0()
        delta = jax.jit(lambda a, b: leaf_norms(
            {n: a[n] - b[n] for n in a}))(w, w0)
        delta = {n: np.asarray(x) for n, x in delta.items()}
    return {"loss": losses, "grad_norm": gnorms, "grad1": grad1,
            "delta": delta}
