"""PCG32 (O'Neill, PCG-XSH-RR 64/32), plain: the 64-bit LCG of ``lcg64``
from the ``(seed, stream)`` mix, and word ``i`` the XSH-RR output of its
state ``x_i``: ``((x ^ x >> 18) >> 27)`` cut to 32 bits, rotated right by
``x >> 59``."""
import numpy as np

from bench.generators._orbit import affine
from bench.generators.lcg64 import A, C
from bench.reference import mix


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    x = affine(mix(seed, stream), n, A, C, 1 << 64)
    xs = (((x >> np.uint64(18)) ^ x) >> np.uint64(27)).astype(np.uint32)
    rot = (x >> np.uint64(59)).astype(np.uint32)
    return (xs >> rot) | (xs << ((np.uint32(32) - rot) & np.uint32(31)))
