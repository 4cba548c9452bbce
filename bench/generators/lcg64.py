"""A 64-bit LCG, plain: ``x_{i+1} = A x_i + C mod 2^64`` with Knuth's MMIX
constants, ``x_0`` the ``(seed, stream)`` mix; word ``i`` is the high half
of ``x_i``."""
import numpy as np

from bench.generators._orbit import affine
from bench.reference import mix

A = 6364136223846793005
C = 1442695040888963407


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    x = affine(mix(seed, stream), n, A, C, 1 << 64)
    return (x >> np.uint64(32)).astype(np.uint32)
