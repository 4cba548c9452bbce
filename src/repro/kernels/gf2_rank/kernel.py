"""Pallas TPU kernel: bit-packed GF(2) matrix rank (MatrixRank test hot spot).

TestU01 does word-level Gaussian elimination on CPU. TPU adaptation: a
32x32 bit-matrix is 32 int32 row words, and a VMEM tile of (32, TILE_M)
holds TILE_M matrices with row r of every matrix on sublane row r and
the matrices along lanes. Elimination walks the rows in order: row r
(a static slice, already reduced by the rows above it) is a pivot iff it
is non-zero, its pivot column is its lowest set bit ``p & -p``, and that
bit is cleared from every later row holding it. The rank is the number
of non-zero pivots. Every step is elementwise int32 work on the VPU —
no reductions, no argmax and no gathers, which is what Mosaic lowers.

Grid: one program per TILE_M matrices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_M = 256


def _rank_kernel(rows_ref, rank_ref):
    rows = rows_ref[...]                                   # (32, TILE_M) i32
    later = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    rank = jnp.zeros((1, rows.shape[1]), jnp.int32)
    for r in range(32):
        p = rows[r:r + 1, :]
        low = p & (-p)                          # pivot column bit (0 if p==0)
        hit = ((rows & low) != 0) & (later > r)
        rows = jnp.where(hit, rows ^ p, rows)
        rank = rank + (p != 0).astype(jnp.int32)
    rank_ref[...] = rank


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf2_rank(mats: jax.Array, interpret: bool = True) -> jax.Array:
    """mats: (M, 32) uint32 (rows of 32x32 bit matrices) -> (M,) int32 ranks.

    M must be a multiple of TILE_M (callers pad; the battery's matrix counts
    are powers of two).
    """
    m = mats.shape[0]
    assert m % TILE_M == 0, m
    rows = jax.lax.bitcast_convert_type(mats, jnp.int32).T     # (32, M)
    return pl.pallas_call(
        _rank_kernel,
        grid=(m // TILE_M,),
        in_specs=[pl.BlockSpec((32, TILE_M), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, TILE_M), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.int32),
        interpret=interpret,
    )(rows)[0]
