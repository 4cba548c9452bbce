"""xorshift64* (Vigna, 2016; Marsaglia's xorshift with shifts 12, 25, 27),
plain: ``x_0`` the ``(seed, stream)`` mix made odd, ``x_{i+1}`` one
shift-XOR step of ``x_i``, and word ``i`` the high half of
``x_{i+1} * 0x2545F4914F6CDD1D mod 2^64``. The states ``x_{1 + 64j}``
come from the GF(2) orbit of the 64-step map, then 64 vector steps fill
each run of 64 words."""
import numpy as np

from bench.generators._orbit import M64, gf2
from bench.reference import mix, u64

MULT = 0x2545F4914F6CDD1D
RUN = 64


def step(x: int) -> int:
    x ^= x >> 12
    x ^= (x << 25) & M64
    return x ^ (x >> 27)


def run_step(x: int) -> int:
    for _ in range(RUN):
        x = step(x)
    return x


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    x = gf2(step(mix(seed, stream) | 1), -(-n // RUN), run_step)
    states = np.empty((RUN, len(x)), np.uint64)
    for t in range(RUN):
        states[t] = x
        x = x ^ (x >> np.uint64(12))
        x ^= x << np.uint64(25)
        x ^= x >> np.uint64(27)
    with np.errstate(over="ignore"):
        out = (states.T.reshape(-1)[:n] * u64(MULT)) >> np.uint64(32)
    return out.astype(np.uint32)
