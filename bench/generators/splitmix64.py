"""SplitMix64, plain: the high halves of its outputs at counters
``base + k * GOLDEN`` (k = 0..n-1), ``base`` an LCG mix of
``(seed, stream)``."""
import numpy as np

from bench.reference import GOLDEN, mix, u64


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    with np.errstate(over="ignore"):
        z = np.arange(n, dtype=np.uint64) * u64(GOLDEN) + u64(mix(seed,
                                                                  stream))
        z = z + u64(GOLDEN)
        z = (z ^ (z >> np.uint64(30))) * u64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * u64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z >> np.uint64(32)).astype(np.uint32)
