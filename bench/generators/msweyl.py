"""Widynski's Middle-Square Weyl sequence in counter form, plain: three
rounds of ``x = x * x + w`` with a 32-bit rotation, where ``w = k * s``
(k = 1..n) and ``s`` is the ``(seed, stream)`` mix made odd."""
import numpy as np

from bench.reference import mix, u64


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    with np.errstate(over="ignore"):
        w = np.arange(1, n + 1, dtype=np.uint64) * u64(mix(seed, stream) | 1)
        x = w
        for _ in range(3):
            x = x * x + w
            x = (x >> np.uint64(32)) | (x << np.uint64(32))
    return (x >> np.uint64(32)).astype(np.uint32)
