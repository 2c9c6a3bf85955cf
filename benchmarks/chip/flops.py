"""Analytic operation counts for the task payloads, kept with the benchmark.

A dense decoder's train step does 6 * N * T FLOPs in its matrix products
(forward 2 N T, backward 4 N T) plus the attention scores and values,
12 * L * B * S^2 * H * Dh, halved for the causal mask. N counts the
parameters that enter a matrix product per token: the attention and MLP
projections of every layer, the norms, and the vocabulary table once (tied
embeddings: the table is the LM head; the lookup does no arithmetic).
Recomputed activations (remat) do not count.

The same arithmetic as ``repro.analysis.roofline.analytic_model_flops`` for
a dense decoder, copied so that no later change to the program can move the
yardstick.
"""
from __future__ import annotations

from typing import Dict


def dense_param_count(p: Dict[str, object]) -> int:
    """Parameters of a dense decoder as counted for 6 N T."""
    d, v = int(p["d_model"]), int(p["vocab_size"])
    hd = int(p["head_dim"]) or d // int(p["num_heads"])
    hq, hkv = int(p["num_heads"]), int(p["num_kv_heads"])
    n = v * d if p.get("tie_embeddings", False) else 2 * v * d
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * int(p["d_ff"])
    return n + int(p["num_layers"]) * (attn + mlp + 2 * d)


def dense_train_step_flops(p: Dict[str, object]) -> float:
    """FLOPs of one train step of ``batch_size`` x ``seq_len`` tokens."""
    b, s = int(p["batch_size"]), int(p["seq_len"])
    hd = int(p["head_dim"]) or int(p["d_model"]) // int(p["num_heads"])
    base = 6.0 * dense_param_count(p) * b * s
    attn = 12.0 * int(p["num_layers"]) * b * s * s * int(p["num_heads"]) \
        * hd * 0.5
    return base + attn
