"""Process-wide lowering flags.

``scan_unroll()`` — when True, every model-level ``lax.scan`` fully unrolls.
Used ONLY by the roofline depth probe: XLA's ``cost_analysis`` counts a
while-loop body ONCE regardless of trip count, so faithful FLOP/byte counts
require unrolled lowering of shallow (1-2 layer) probe configs; the roofline
module then scales per-layer deltas to the real depth (see analysis/roofline).

``wq_device_claim()`` — when True, WorkQueues CONSTRUCTED while it holds run
claim_all's primary phase through the wq_claim Pallas op on the accelerator
instead of the host numpy fast-path (the queue samples the flag once in
__init__; flip ``wq.device_claim`` to switch an existing queue). Defaults
from the REPRO_WQ_DEVICE_CLAIM env var (off unless set to 1/true/yes);
``device_claims()`` scopes the construction-time default. The kernel is
compiled for the accelerator; ``device_claims("interpret")`` runs it in
Pallas interpret mode instead (CPU tests) — nothing switches implicitly.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Union

_SCAN_UNROLL = contextvars.ContextVar("repro_scan_unroll", default=False)

_WQ_DEVICE_CLAIM = contextvars.ContextVar(
    "repro_wq_device_claim",
    default=os.environ.get("REPRO_WQ_DEVICE_CLAIM", "").lower()
    in ("1", "true", "yes"))


def wq_device_claim() -> Union[bool, str]:
    return _WQ_DEVICE_CLAIM.get()


@contextlib.contextmanager
def device_claims(on: Union[bool, str] = True):
    """Construction-time default for WorkQueue(device_claim=None) within the
    scope; queues built earlier keep whatever they sampled."""
    tok = _WQ_DEVICE_CLAIM.set(on)
    try:
        yield
    finally:
        _WQ_DEVICE_CLAIM.reset(tok)


def scan_unroll() -> bool:
    return _SCAN_UNROLL.get()


@contextlib.contextmanager
def unrolled_scans(on: bool = True):
    tok = _SCAN_UNROLL.set(on)
    try:
        yield
    finally:
        _SCAN_UNROLL.reset(tok)


def scan(body, init, xs, **kw):
    """lax.scan wrapper honoring the unroll flag (model code uses this)."""
    import jax
    if scan_unroll():
        kw = dict(kw, unroll=True)
    return jax.lax.scan(body, init, xs, **kw)
