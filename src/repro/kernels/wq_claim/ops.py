"""Dispatcher for the work-queue claim kernel."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.wq_claim.kernel import wq_claim_fwd
from repro.kernels.wq_claim.ref import wq_claim_ref


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit,
                   static_argnames=("num_workers", "k", "interpret"))
def wq_claim(status, worker, *, num_workers: int, k: int = 1,
             interpret: bool = False):
    """status/worker: [N] int32 -> (new_status [N], claimed [N] int32),
    bit-identical to ``wq_claim_ref``."""
    n = status.shape[0]
    w = _round_up(num_workers, 16)             # bf16 sublane tile
    # keep each [W, RB] f32 block intermediate near 1 MiB of fast memory
    rows = _round_up(max(n, 1), 128)
    rb = min(512 if w <= 512 else 256, rows)
    pad = _round_up(rows, rb) - n
    # ids outside [0, W) match no one-hot row (rank 0, as in the oracle);
    # fold them to -1 so they cannot hit the padded worker rows either
    worker = jnp.where((worker >= 0) & (worker < num_workers), worker, -1)
    status = jnp.pad(status, (0, pad))[None, :]          # pads are EMPTY(0)
    worker = jnp.pad(worker, (0, pad), constant_values=-1)[None, :]
    new_status, claimed = wq_claim_fwd(
        status, worker, num_workers=w, k=k, row_block=rb,
        interpret=interpret)
    return new_status[0, :n], claimed[0, :n]


def wq_claim_columns(status: np.ndarray, worker: np.ndarray, *,
                     num_workers: int, k: int = 1, interpret: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-facing bridge for WorkQueue's device claim path.

    Takes the store's numpy status/worker columns, runs the Pallas claim op
    (compiled for the device; ``interpret=True`` only when the caller asks),
    and returns numpy ``(claim_mask [N] bool, new_status [N] int32)`` for the
    control plane to apply to the authoritative host store.
    """
    if status.size == 0:
        return (np.zeros(0, bool), np.zeros(0, np.int32))
    new_status, claimed = wq_claim(
        jnp.asarray(np.ascontiguousarray(status), jnp.int32),
        jnp.asarray(np.ascontiguousarray(worker), jnp.int32),
        num_workers=num_workers, k=k, interpret=interpret)
    return (np.asarray(claimed).astype(bool),
            np.asarray(new_status, np.int32))
