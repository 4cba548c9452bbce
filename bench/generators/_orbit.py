"""Orbits of the classic recurrences, plain: ``x, f(x), f(f(x)), ...`` for
a step ``f`` that is affine modulo ``m`` or linear over GF(2)^64, built by
doubling. The first ``k`` states give the next ``k`` through the ``k``-step
map ``f^k``, which is again affine (or GF(2)-linear) and is composed with
Python integers; so an orbit of ``n`` states takes about ``log2(n)``
vector passes, with no per-word loop. Not a generator: the leading
underscore keeps it out of ``reference.generators()``."""
import numpy as np

M64 = (1 << 64) - 1


def affine(x0: int, n: int, a: int, c: int, modulus: int) -> np.ndarray:
    """uint64[n]: ``x_0 = x0``, ``x_{i+1} = (a x_i + c) mod modulus``, for
    ``modulus`` 2^64 or below 2^32 (where a product of two residues fits in
    64 bits)."""
    out = np.empty(n, np.uint64)
    if n == 0:
        return out
    wrap = modulus == 1 << 64
    if not wrap and modulus >= 1 << 32:
        raise ValueError(f"modulus {modulus} is neither 2^64 nor below 2^32")
    out[0] = x0 % modulus
    k, ak, ck = 1, a % modulus, c % modulus     # the k-step map x -> ak x + ck
    with np.errstate(over="ignore"):
        while k < n:
            m = min(k, n - k)
            seg = out[:m] * np.uint64(ak) + np.uint64(ck)
            out[k:k + m] = seg if wrap else seg % np.uint64(modulus)
            ak, ck = ak * ak % modulus, (ak * ck + ck) % modulus
            k *= 2
    return out


def _apply(cols, x: int) -> int:
    """The GF(2)-linear map with columns ``cols`` applied to ``x``."""
    y = 0
    for b in range(64):
        if x >> b & 1:
            y ^= cols[b]
    return y


def _tables(cols) -> np.ndarray:
    """uint64[8, 256]: entry ``[j, v]`` is the map applied to ``v << 8j``,
    so the map of ``x`` is the XOR over ``j`` of ``[j, byte j of x]``."""
    v = np.arange(256, dtype=np.uint64)
    out = np.zeros((8, 256), np.uint64)
    for j in range(8):
        for i in range(8):
            bit = (v >> np.uint64(i)) & np.uint64(1)
            out[j] ^= bit * np.uint64(cols[8 * j + i])
    return out


def gf2(x0: int, n: int, step) -> np.ndarray:
    """uint64[n]: ``x_0 = x0``, ``x_{i+1} = step(x_i)``, for a ``step`` on
    Python ints that is linear over GF(2)^64."""
    out = np.empty(n, np.uint64)
    if n == 0:
        return out
    out[0] = x0 & M64
    cols = [step(1 << b) & M64 for b in range(64)]     # the k-step map
    k = 1
    while k < n:
        m = min(k, n - k)
        tab = _tables(cols)
        seg = np.zeros(m, np.uint64)
        for j in range(8):
            byte = (out[:m] >> np.uint64(8 * j)) & np.uint64(0xFF)
            seg ^= tab[j][byte]
        out[k:k + m] = seg
        cols = [_apply(cols, c) for c in cols]
        k *= 2
    return out
