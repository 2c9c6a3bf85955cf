"""Work-queue claim kernel — the paper's measured hot spot, TPU-native.

The paper's Experiment 6 shows getREADYtasks (SELECT next READY tasks WHERE
worker_id = w) + the RUNNING-status update are >40% + ~53% of all DBMS time.
SchalaDB's insight is that partition-private access needs no locks; on TPU
that becomes: every worker's claim is computed in ONE data-parallel pass over
the store columns, and the status flip is a masked vector write — no
conflicts are possible because the (status, worker) masks are disjoint by
construction (hash partitioning by worker id).

Inputs (columns of the WQ relation, int32, laid out lane-dense as [1, N]):
  status, worker — plus static W (workers, padded to a sublane multiple) and
  K (claim budget). Grid = (num_row_blocks,), sequential; scratch carries the
  per-worker READY counts seen so far, saturated at K, as a [W, 1] column.
For each row block the per-worker one-hot is built transposed, [W, RB]
(workers on sublanes, rows on lanes). A row's exclusive rank within its
worker's READY sequence in the block is one MXU matmul of that one-hot with
the strictly-upper-triangular [RB, RB] 0/1 matrix (bf16 operands, f32
accumulation: exact for counts <= RB). rank = in-block rank + carried count;
claim = READY & rank < K. No atomics, no locks, no cumsum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

READY = 2
RUNNING = 3


def _claim_kernel(status_ref, worker_ref, out_status_ref, claimed_ref,
                  counts_ref, *, rb: int, w: int, k: int):
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    status = status_ref[...]                   # [1, RB]
    worker = worker_ref[...]                   # [1, RB]
    ready = status == READY
    oh = ((jax.lax.broadcasted_iota(jnp.int32, (w, rb), 0) == worker)
          & ready).astype(jnp.float32)         # [W, RB]
    before = (jax.lax.broadcasted_iota(jnp.int32, (rb, rb), 0)
              < jax.lax.broadcasted_iota(jnp.int32, (rb, rb), 1))
    within = jnp.dot(oh.astype(jnp.bfloat16), before.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)   # exclusive
    counts = counts_ref[...]                   # [W, 1]
    rank = jnp.sum((within + counts) * oh, axis=0, keepdims=True)  # [1, RB]
    claim = ready & (rank < k)
    out_status_ref[...] = jnp.where(claim, RUNNING, status)
    claimed_ref[...] = claim.astype(jnp.int32)
    counts_ref[...] = jnp.minimum(counts + jnp.sum(oh, axis=1, keepdims=True),
                                  float(k))


def wq_claim_fwd(status: jax.Array, worker: jax.Array, *, num_workers: int,
                 k: int, row_block: int, interpret: bool = False):
    """status/worker: [1, N] int32, N a multiple of ``row_block`` (itself a
    multiple of 128), every worker id in [0, num_workers) or -1, and
    ``num_workers`` a multiple of 16. Returns (new_status [1, N],
    claimed [1, N] int32).

    claimed[i] == 1 iff row i was claimed this round (its worker's rank
    budget k not yet exhausted). One pass, no locks — the TPU analogue of
    the partition-private SELECT ... FOR UPDATE.
    """
    n = status.shape[1]
    rb = row_block
    kernel = functools.partial(_claim_kernel, rb=rb, w=num_workers, k=k)
    block = pl.BlockSpec((1, rb), lambda i: (0, i))
    new_status, claimed = pl.pallas_call(
        kernel,
        grid=(n // rb,),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((num_workers, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(status, worker)
    return new_status, claimed
