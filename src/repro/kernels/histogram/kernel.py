"""Pallas TPU kernel: fused bin-count (the gap/poker/weight/serial hot loop).

Scatter-free TPU strategy: a chunk of pre-computed bin indices, laid
along sublanes, is compared against a tile of bin ids laid along lanes —
a (CHUNK, BIN_TILE) compare matrix reduced over CHUNK — so the
accumulation is pure VPU work on 128-lane tiles. The grid is (bin tiles,
chunks): the bin axis is tiled so the compare tile stays a fixed
CHUNK x BIN_TILE x 4 bytes (2 MiB) of VMEM whatever the bin count, and
the chunk axis is the reduction, revisiting one (1, BIN_TILE) output
block per bin tile. Work grows as N x K, so the bin counts routed here
are capped by cost (``stats.backends.HIST_MAX_BINS``), not by VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 2048      # indices per grid step (sublane axis of the compare tile)
BIN_TILE = 256    # bins per grid step (lane axis of the compare tile)


def _hist_kernel(idx_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                                     # (CHUNK, 1) int32
    bins = (pl.program_id(0) * BIN_TILE
            + jax.lax.broadcasted_iota(jnp.int32, (CHUNK, BIN_TILE), 1))
    hit = (idx == bins).astype(jnp.float32)
    out_ref[...] += jnp.sum(hit, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def histogram(idx: jax.Array, k: int, interpret: bool = True) -> jax.Array:
    """idx: (N,) int32 -> (k,) float32 counts of the values in [0, k);
    other values (the wrapper pads with -1) count nowhere.
    N % CHUNK == 0 and k % BIN_TILE == 0."""
    n = idx.shape[0]
    assert n % CHUNK == 0 and k % BIN_TILE == 0, (n, k)
    return pl.pallas_call(
        _hist_kernel,
        grid=(k // BIN_TILE, n // CHUNK),
        in_specs=[pl.BlockSpec((CHUNK, 1), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((1, BIN_TILE), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx.reshape(n, 1))[0]
