"""jit'd public wrapper: padding for the histogram kernel.

The index vector is padded to whole chunks with -1 (a value no bin
counts) and the bin axis to whole bin tiles; the padded bins are sliced
off. ``interpret="auto"`` (the default) compiles the Pallas kernel on
TPU and runs the interpreter elsewhere (``repro.kernels``).
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.histogram.kernel import BIN_TILE, CHUNK, histogram


def bincount(idx: jax.Array, k: int,
             interpret: Union[str, bool] = "auto") -> jax.Array:
    """(N,) int32 bin indices in [0, k) -> (k,) float32 counts."""
    n = idx.shape[0]
    idx = jnp.pad(idx.astype(jnp.int32), (0, (-n) % CHUNK),
                  constant_values=-1)
    out = histogram(idx, k + (-k) % BIN_TILE,
                    interpret=resolve_interpret(interpret))
    return out[:k]
