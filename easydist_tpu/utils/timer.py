"""Benchmark timing (reference: easydist/utils/timer.py:24-56 — cuda-event
timing there).

One timing discipline for every measurement in the package: warm the
function, dispatch `iters` calls, and wait for the last result with
`jax.block_until_ready` inside the timed region (dispatch is asynchronous;
a timing that does not wait measures the enqueue).  chip_smoke.py's clock
phase checks on the device that this wait is real: a chained bf16 matmul
timed this way must land under the chip's datasheet peak.
"""

from __future__ import annotations

import time
from typing import Callable

import jax


def time_per_call(fn: Callable, args=(), iters: int = 12,
                  warmup: int = 2) -> float:
    """Seconds per call of `fn(*args)` over `iters` back-to-back calls,
    after `warmup` untimed ones (compile caches, allocator)."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters
