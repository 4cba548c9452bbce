"""The one place the battery turns on 64-bit tracing.

Only the generators (``rng/generators.py``) need 64-bit integers: their
states, counters and jump-ahead ladders are uint64. ``x64()`` scopes
64-bit tracing to exactly that code. Everything else — the test kernels,
the Pallas kernels and the float statistics — traces in JAX's default 32
bits, so no float64 op and no 64-bit Pallas index reaches the device.
The scope may open and close inside a jitted trace: a program traced in
32 bits keeps the uint64 ops traced under it (``pool._job_fn``).
"""
from __future__ import annotations

import jax


def x64():
    """Context manager: trace and run the enclosed code with 64-bit
    dtypes enabled (``jax.enable_x64(True)``)."""
    return jax.enable_x64(True)
