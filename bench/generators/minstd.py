"""MINSTD (Park and Miller, 1988), plain: ``x_{i+1} = 16807 x_i mod
(2^31 - 1)``, ``x_0 = mix mod (2^31 - 2) + 1`` for the ``(seed, stream)``
mix; word ``i`` is ``x_{i+1}`` shifted to the top of 32 bits."""
import numpy as np

from bench.generators._orbit import affine
from bench.reference import mix

A = 16807
M = (1 << 31) - 1


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    x0 = mix(seed, stream) % (M - 1) + 1
    x = affine(A * x0 % M, n, A, 0, M)
    return (x << np.uint64(1)).astype(np.uint32)
