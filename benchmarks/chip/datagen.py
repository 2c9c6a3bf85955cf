"""The benchmark's own copy of the task payload's input generator.

Each training task names a data shard (its ``in1`` column); the executor
regenerates that shard's batch from ``(seed, shard)``. The reference needs
the same tokens and may take nothing the program made, so the generator is
copied here from ``repro.data.pipeline.shard_batch`` (Zipf unigrams with a
bigram rule). ``check_inputs`` in the harness compares the two on the first
steps of every run, so a change to the program's generator shows as a
mismatch instead of a silent change of workload.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def shard_batch(seed: int, shard_id: int, batch_size: int, seq_len: int,
                vocab_size: int, zipf_a: float = 1.3
                ) -> Dict[str, np.ndarray]:
    """Tokens [B, S] and next-token labels [B, S] of one data shard."""
    rng = np.random.default_rng((int(seed) << 32) ^ int(shard_id))
    b, s, v = batch_size, seq_len, vocab_size
    base = rng.zipf(zipf_a, size=(b, s + 1)) % v
    follow = (base * 31 + 7) % v
    mask = rng.random((b, s + 1)) < 0.5
    stream = np.where(mask, np.roll(follow, 1, axis=1), base).astype(np.int32)
    return {"tokens": stream[:, :s], "labels": stream[:, 1:]}
