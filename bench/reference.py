"""Plain reference of the batteries the benchmark runs, in NumPy/SciPy.

It imports nothing of the program under test. From a request's
``(battery, scale, generator, seed)`` it builds the battery's test table,
generates each test's words, computes every statistic and p-value, and
takes the Bonferroni-sequential verdict:

- the battery grids are TestU01's SmallCrush/BigCrush structure as the
  program parameterises them (ten families, ``scale`` multiplies every
  sample size, Poisson-regime tests keep their rate);
- test ``i`` of a request reads words ``0 .. n_words-1`` of the
  generator's stream ``i`` under the lane's seed; each generator's words
  come from its own plain version, ``bench/generators/<name>.py``,
  found by name;
- counts are exact integers; everything in floating point is computed in
  ``ft`` (float64 for the reference, a lower precision for the control
  that ``bench/readings.py`` runs), with SciPy's special functions.
"""
from __future__ import annotations

import importlib.util
import math
import os
from typing import Callable, Dict, List, Tuple

import numpy as np

M64 = (1 << 64) - 1
GOLDEN = 0x9E3779B97F4A7C15


# ---------------------------------------------------------------------------
# generators, each in ``bench/generators/<name>.py``

GENERATOR_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "generators")
_GENERATORS: Dict[str, Callable] = {}


def u64(x) -> np.uint64:
    return np.uint64(int(x) & M64)


def mix(seed: int, stream: int) -> int:
    """The 64-bit LCG mix of ``(seed, stream)`` that seeds a stream."""
    return (int(seed) * 6364136223846793005 + int(stream) * GOLDEN
            + 1442695040888963407) & M64


def generators() -> List[str]:
    """Names of the generators that have a plain reference."""
    return sorted(f[:-3] for f in os.listdir(GENERATOR_DIR)
                  if f.endswith(".py") and not f.startswith("_"))


def generator(name: str) -> Callable:
    """``block(seed, stream, n) -> uint32[n]`` of
    ``bench/generators/<name>.py``, found by name."""
    if name not in _GENERATORS:
        if name not in generators():
            raise KeyError(f"no plain reference of generator {name!r}; "
                           f"known: {generators()}")
        spec = importlib.util.spec_from_file_location(
            "bench_generator_" + name,
            os.path.join(GENERATOR_DIR, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _GENERATORS[name] = mod.block
    return _GENERATORS[name]


# ---------------------------------------------------------------------------
# battery tables

_BASE = [
    ("birthday", dict(n=4096, tbits=30)), ("collision", dict(n=65536, kbits=26)),
    ("gap", dict(n=65536, beta=0.125)), ("poker", dict(n=32768)),
    ("coupon", dict(n=65536, d=8)), ("maxoft", dict(n=16384, t=8)),
    ("weight", dict(n=65536)), ("rank", dict(n_mats=1024)),
    ("hamcorr", dict(n=65536)), ("serial2d", dict(n=65536, d=64)),
]

_VARIANTS = {
    "birthday": [dict(n=1024, tbits=26), dict(n=2048, tbits=28),
                 dict(n=2048, tbits=30), dict(n=4096, tbits=30),
                 dict(n=8192, tbits=30), dict(n=4096, tbits=28),
                 dict(n=1024, tbits=24), dict(n=2048, tbits=26),
                 dict(n=2048, tbits=24)],
    "collision": [dict(n=n, kbits=k) for n in (32768, 65536, 131072)
                  for k in (24, 26, 28)],
    "gap": [dict(n=n, beta=b) for n in (32768, 65536, 131072)
            for b in (0.0625, 0.125, 0.25)],
    "poker": [dict(n=n) for n in (16384, 32768, 65536, 131072)],
    "coupon": [dict(n=n, d=d) for n in (32768, 65536) for d in (4, 8, 16)],
    "maxoft": [dict(n=n, t=t) for n in (8192, 16384, 32768)
               for t in (4, 8, 16)],
    "weight": [dict(n=n) for n in (32768, 65536, 131072, 262144)],
    "rank": [dict(n_mats=m) for m in (512, 1024, 2048, 4096)],
    "hamcorr": [dict(n=n) for n in (32768, 65536, 131072, 262144)],
    "serial2d": [dict(n=n, d=d) for n in (32768, 65536, 131072)
                 for d in (16, 64, 128)],
}

_SIZES = {"smallcrush": 10, "bigcrush": 106}


def _words(kname: str, p: dict) -> int:
    if kname == "poker":
        return p["n"] * 5
    if kname == "maxoft":
        return p["n"] * p["t"]
    if kname == "rank":
        return p["n_mats"] * 32
    if kname in ("serial2d",):
        return p["n"] * 2
    return p["n"]


def _scale(kname: str, kw: dict, scale: float) -> dict:
    """Sample sizes times ``scale`` (floor 256); birthday keeps its rate
    n^3/4k and collision n^2/2k by moving the bit widths."""
    kw = dict(kw)
    n0 = kw.get("n", 0)
    for key in ("n", "n_mats"):
        if key in kw:
            kw[key] = max(int(kw[key] * scale), 256)
    if kname == "birthday":
        lam0 = n0 ** 3 / (4.0 * (1 << kw["tbits"]))
        tb = kw["tbits"] + round(3 * math.log2(max(scale, 1e-9)))
        kw["tbits"] = min(max(tb, 16), 30)
        n = int(round((lam0 * 4 * (1 << kw["tbits"])) ** (1 / 3)))
        kw["n"] = max(min(n, int(math.sqrt(1 << kw["tbits"]) / 2)), 128)
    if kname == "collision":
        kb = kw["kbits"] + round(2 * math.log2(max(scale, 1e-9)))
        kw["kbits"] = min(max(kb, 14), 30)
    return kw


def battery(name: str, scale: float) -> List[Tuple[str, dict, int]]:
    """The battery's tests in order: ``(family, params, n_words)``.
    BigCrush takes the families' parameter grids in turn (67 tests).
    Past 1060 turns the grids hold their first points again, with ``n``
    doubled, and the turns go on until the battery holds 106 tests."""
    if name == "smallcrush":
        combos = [(k, _scale(k, kw, scale)) for k, kw in _BASE]
    elif name == "bigcrush":
        target = _SIZES[name]
        pools = {k: list(v) for k, v in _VARIANTS.items()}
        order = list(_VARIANTS)
        combos, i = [], 0
        while len(combos) < target:
            k = order[i % len(order)]
            if pools[k]:
                combos.append((k, _scale(k, pools[k].pop(0), scale)))
            i += 1
            if i > 10 * target:
                pools = {k2: [dict(kw, n=kw["n"] * 2) if "n" in kw else kw
                              for kw in v] for k2, v in _VARIANTS.items()}
    else:
        raise KeyError(f"the reference has no battery {name!r}")
    return [(k, p, _words(k, p)) for k, p in combos]


def test_name(kname: str, params: dict) -> str:
    """The name the program's report gives a test."""
    return kname + "_" + "_".join(f"{a}{v}" for a, v in sorted(params.items()))


# ---------------------------------------------------------------------------
# p-values (computed in float64, with inputs and outputs rounded to ``ft``)

def _f(ft, x):
    return float(np.asarray(x, ft))


def chi2_p(ft, stat, df):
    from scipy import special   # after the window: not part of set-up
    return _f(ft, special.gammaincc(df / 2.0, _f(ft, stat) / 2.0))


def chi2_stat(ft, counts, expected):
    e = np.maximum(np.asarray(expected, ft), np.asarray(1e-9, ft))
    c = np.asarray(counts, ft)
    return ft((np.square(c - e) / e).sum(dtype=ft))


def poisson_midp(ft, k, lam):
    """P[X > k] + P[X = k] / 2 for X ~ Poisson(lam)."""
    from scipy import stats
    k, lam = _f(ft, k), _f(ft, lam)
    p = stats.poisson.sf(k, lam) + 0.5 * stats.poisson.pmf(k, lam)
    return _f(ft, min(max(p, 1e-300), 1.0))


def normal_two_sided(ft, z):
    from scipy import stats
    return _f(ft, 2.0 * stats.norm.sf(abs(_f(ft, z))))


def unit(bits, ft):
    """uint32 -> [0, 1) from the top 24 bits."""
    return (bits >> np.uint32(8)).astype(ft) * ft(1.0 / (1 << 24))


# ---------------------------------------------------------------------------
# the ten test families: (bits, ft, **params) -> (stat, p)

def birthday(bits, ft, n, tbits):
    days = np.sort(bits[:n] >> np.uint32(32 - tbits))
    spacings = np.sort(np.diff(days))
    dup = int(np.count_nonzero(np.diff(spacings) == 0))
    lam = n ** 3 / (4.0 * (1 << tbits))
    return float(dup), poisson_midp(ft, dup, lam)


def collision(bits, ft, n, kbits):
    urns = np.sort(bits[:n] >> np.uint32(32 - kbits))
    coll = n - (1 + int(np.count_nonzero(np.diff(urns))))
    k = float(1 << kbits)
    mean = n + k * math.expm1(n * math.log1p(-1.0 / k))
    return float(coll), poisson_midp(ft, coll, max(mean, 1e-9))


def gap(bits, ft, n, beta, maxlen=20):
    u = unit(bits[:n], ft)
    hits = np.flatnonzero(u < ft(beta))
    gaps = np.diff(np.concatenate([[-1], hits])) - 1
    counts = np.bincount(np.minimum(gaps, maxlen), minlength=maxlen + 1)
    probs = np.array([beta * (1 - beta) ** i for i in range(maxlen)]
                     + [(1 - beta) ** maxlen])
    expected = np.asarray(len(hits), ft) * probs.astype(ft)
    stat = chi2_stat(ft, counts, expected)
    return float(stat), chi2_p(ft, stat, maxlen)


def _distinct_probs(d, hand):
    """P[r distinct values among ``hand`` draws from ``d``], r = 1..hand."""
    out = []
    for r in range(1, hand + 1):
        # Stirling number of the second kind S(hand, r), by inclusion-exclusion
        s2 = sum((-1) ** j * math.comb(r, j) * (r - j) ** hand
                 for j in range(r + 1)) // math.factorial(r)
        out.append(s2 * math.perm(d, r) / d ** hand)
    return np.array(out)


def poker(bits, ft, n, d=8, hand=5):
    digits = np.sort((bits[:n * hand] >> np.uint32(29)).reshape(n, hand), 1)
    distinct = 1 + np.count_nonzero(np.diff(digits, axis=1), axis=1)
    counts = np.bincount(np.maximum(distinct, 2) - 2, minlength=hand - 1)
    probs = _distinct_probs(d, hand)
    probs = np.concatenate([[probs[0] + probs[1]], probs[2:]])
    stat = chi2_stat(ft, counts, n * probs)
    return float(stat), chi2_p(ft, stat, hand - 2)


def coupon(bits, ft, n, d, maxlen=30):
    """Segments that collect all ``d`` values; lengths d .. d+maxlen-1+."""
    dbits = d.bit_length() - 1
    digits = (bits[:n] >> np.uint32(32 - dbits)).astype(np.int64)
    # complete[i]: the index at which a segment starting at i has seen
    # every value (n when it never does)
    complete = np.zeros(n, np.int64)
    pos = np.arange(n, dtype=np.int64)
    for v in range(d):
        nxt = np.where(digits == v, pos, n)
        np.maximum(complete, np.minimum.accumulate(nxt[::-1])[::-1],
                   out=complete)
    complete = complete.tolist()
    hist = np.zeros(maxlen, np.int64)
    s = 0
    while s < n and complete[s] < n:
        e = complete[s]
        hist[min(max(e - s + 1 - d, 0), maxlen - 1)] += 1
        s = e + 1

    def all_seen(length):
        return sum((-1) ** i * math.comb(d, i) * ((d - i) / d) ** length
                   for i in range(d + 1))
    probs = np.array([all_seen(d + j) - all_seen(d + j - 1)
                      for j in range(maxlen - 1)]
                     + [1.0 - all_seen(d + maxlen - 2)])
    expected = (np.asarray(hist.sum(), ft)
                * np.maximum(probs, 1e-12).astype(ft))
    stat = chi2_stat(ft, hist, expected)
    return float(stat), chi2_p(ft, stat, maxlen - 1)


def maxoft(bits, ft, n, t):
    u = unit(bits[:n * t], ft).reshape(n, t)
    m = np.sort(u.max(axis=1) ** t)
    i = np.arange(1, n + 1).astype(ft)
    nn = ft(n)
    d = max(ft((i / nn - m).max()), ft((m - (i - ft(1)) / nn).max()))
    lam = (math.sqrt(n) + 0.12 + 0.11 / math.sqrt(n)) * _f(ft, d)
    from scipy import special
    return float(m[-1]), _f(ft, special.kolmogorov(_f(ft, lam)))


def weight(bits, ft, n, lo=10, hi=22):
    w = np.bitwise_count(bits[:n]).astype(np.int64)
    counts = np.bincount(np.clip(w, lo, hi) - lo, minlength=hi - lo + 1)
    pmf = np.array([math.comb(32, k) for k in range(33)], np.float64)
    probs = np.concatenate([[pmf[:lo + 1].sum()], pmf[lo + 1:hi],
                            [pmf[hi:].sum()]]) / 2.0 ** 32
    stat = chi2_stat(ft, counts, n * probs)
    return float(stat), chi2_p(ft, stat, hi - lo)


def gf2_rank(mats: np.ndarray) -> np.ndarray:
    """Ranks over GF(2) of (M, 32) uint32 row-matrices, by Gaussian
    elimination column by column, vectorised over the matrices."""
    rows = mats.astype(np.uint32).copy()
    m = rows.shape[0]
    used = np.zeros((m, 32), bool)
    rank = np.zeros(m, np.int64)
    at = np.arange(m)
    for col in range(31, -1, -1):
        has_bit = ((rows >> np.uint32(col)) & np.uint32(1)).astype(bool)
        cand = has_bit & ~used
        found = cand.any(axis=1)
        piv = cand.argmax(axis=1)
        pivrow = np.where(found, rows[at, piv], np.uint32(0))
        clear = has_bit & (np.arange(32)[None, :] != piv[:, None])
        rows ^= np.where(clear, pivrow[:, None], np.uint32(0))
        used[at[found], piv[found]] = True
        rank += found
    return rank


def _rank_probs(dim=32):
    def p_rank(r):
        p = 2.0 ** (-(dim - r) ** 2)
        for i in range(r):
            p *= (1 - 2.0 ** (i - dim)) ** 2 / (1 - 2.0 ** (i - r))
        return p
    full, m1, m2 = p_rank(dim), p_rank(dim - 1), p_rank(dim - 2)
    return np.array([max(1 - full - m1 - m2, 1e-12), m2, m1, full])


def rank(bits, ft, n_mats):
    r = gf2_rank(bits[:n_mats * 32].reshape(n_mats, 32))
    counts = np.bincount(np.clip(r - 29, 0, 3), minlength=4)
    stat = chi2_stat(ft, counts, n_mats * _rank_probs())
    return float(stat), chi2_p(ft, stat, 3)


def hamcorr(bits, ft, n):
    w = np.bitwise_count(bits[:n]).astype(ft) - ft(16)
    z = ft((w[:-1] * w[1:]).sum(dtype=ft)) / ft(8.0 * math.sqrt(n - 1))
    return float(z), normal_two_sided(ft, z)


def serial2d(bits, ft, n, d):
    dbits = d.bit_length() - 1
    u = bits[:2 * n] >> np.uint32(32 - dbits)
    cell = u[0::2].astype(np.int64) * d + u[1::2]
    counts = np.bincount(cell, minlength=d * d)
    stat = chi2_stat(ft, counts, np.full(d * d, n / (d * d)))
    return float(stat), chi2_p(ft, stat, d * d - 1)


FAMILIES = {"birthday": birthday, "collision": collision, "gap": gap,
            "poker": poker, "coupon": coupon, "maxoft": maxoft,
            "weight": weight, "rank": rank, "hamcorr": hamcorr,
            "serial2d": serial2d}


# ---------------------------------------------------------------------------
# a request and its verdict

def run_request(table, generator_name: str, seed: int,
                ft=np.float64) -> Dict[int, Tuple[float, float]]:
    """``{test index: (stat, p)}`` of one request over ``table``."""
    gen = generator(generator_name)
    out = {}
    for i, (kname, params, n_words) in enumerate(table):
        bits = gen(seed, i, n_words)
        out[i] = FAMILIES[kname](bits, ft, **params)
    return out


def verdict(results: Dict[int, Tuple[float, float]], n_total: int,
            alpha: float) -> Tuple[str, Tuple[int, ...]]:
    """Bonferroni-sequential verdict: a test whose p lies outside
    ``[alpha/2n, 1 - alpha/2n]`` fails the battery; every test in range
    passes it. Returns ``(decision, failed test indices)``."""
    thr = alpha / (2.0 * n_total)
    valid = {i: p for i, (_, p) in results.items()
             if np.isfinite(p) and 0.0 <= p <= 1.0}
    failed = tuple(sorted(i for i, p in valid.items()
                          if p < thr or p > 1.0 - thr))
    if failed:
        return "FAIL", failed
    return ("PASS" if len(valid) >= n_total else "UNDECIDED"), ()
