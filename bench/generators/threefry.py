"""Threefry-2x32 (Salmon et al., SC 2011; 20 rounds) in counter mode,
plain, with the keys ``jax.random`` derives under the program's settings
(the ``threefry2x32`` implementation, ``jax_threefry_partitionable`` on):

- ``PRNGKey(seed)`` is the pair ``(seed >> 32, seed mod 2^32)``;
- ``fold_in(key, d)`` is ``H(key, (0, d))``, a 32-bit ``d``, where
  ``H(key, (x0, x1))`` is Threefry-2x32 of the block ``(x0, x1)`` and
  its two output words are the new key;
- ``bits(key, (), uint32)`` is ``y0 ^ y1`` for ``(y0, y1) = H(key, (0,
  0))``.

Word ``c`` of stream ``s`` under ``seed`` is
``bits(fold_in(fold_in(fold_in(PRNGKey(seed), s), hi32(c)), lo32(c)))``.
Below 2^32 words ``hi32(c)`` is 0, so the first two folds are one key for
the whole block."""
import numpy as np

M32 = 0xFFFFFFFF
PARITY = 0x1BD11BDA
ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
CHUNK = 1 << 16             # words hashed at once


def hash2x32(key, x0, x1):
    """Threefry-2x32 of the blocks ``(x0, x1)`` (uint32 arrays of one
    shape) under ``key``, a pair of uint32 arrays of that shape or of
    shape (1,): the two output words."""
    k0, k1 = key
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(PARITY))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    t = np.empty_like(x1)
    for i in range(5):
        for r in ROTATIONS[i % 2]:
            x0 += x1
            np.left_shift(x1, np.uint32(r), out=t)
            x1 >>= np.uint32(32 - r)
            x1 |= t
            x1 ^= x0
        x0 += ks[(i + 1) % 3]
        x1 += ks[(i + 2) % 3]
        x1 += np.uint32(i + 1)
    return x0, x1


def _word(x: int) -> np.ndarray:
    return np.array([x & M32], np.uint32)


def fold_in(key, d):
    return hash2x32(key, np.zeros_like(d), d)


def block(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n]: words ``0 .. n-1`` of stream ``stream`` under ``seed``."""
    if n > 1 << 32:
        raise ValueError(f"{n} words: the counter's high half would move")
    seed = int(seed) & ((1 << 64) - 1)
    key = (_word(seed >> 32), _word(seed))
    key = fold_in(fold_in(key, _word(stream)), _word(0))
    out = np.empty(n, np.uint32)
    for lo in range(0, n, CHUNK):
        c = np.arange(lo, min(lo + CHUNK, n), dtype=np.uint32)
        y0, y1 = hash2x32(fold_in(key, c), np.zeros_like(c),
                          np.zeros_like(c))
        np.bitwise_xor(y0, y1, out=out[lo:lo + len(c)])
    return out
