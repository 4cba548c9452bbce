"""The battery's statistical test kernels (TestU01 SmallCrush analogues).

Every kernel has the uniform job signature ``kernel(bits: uint32[N]) ->
(stat: f32, p: f32)`` with its parameters STATICALLY bound (as in TestU01,
where each battery entry is a fixed parameterization). This uniformity is
what lets the pool dispatch heterogeneous tests through one ``lax.switch``
(DESIGN.md §2 — the paper's "one job = one test" on SPMD hardware).

Kernels (classic references in parentheses):
  birthday   — birthday spacings (Marsaglia), Poisson tail
  collision  — balls-in-urns collisions, normal approx
  gap        — gap lengths vs geometric, chi2
  poker      — distinct digits per 5-hand (simplified poker), chi2
  coupon     — coupon collector segment lengths, chi2
  maxoft     — max-of-t ^t uniformity, KS
  weight     — Hamming-weight histogram vs Binomial(32, 1/2), chi2
  rank       — 32x32 GF(2) matrix rank distribution, chi2
             (pure-jnp twin of kernels/gf2_rank)
  hamcorr    — lag-1 correlation of word Hamming weights, normal
  serial2d   — overlapping-free 2D serial pairs, chi2
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.rng.generators import to_unit
from repro.stats.special import (chi2_from_counts, chi2_sf, ks_pvalue,
                                 normal_p_two_sided, poisson_midp_upper)


# ---------------------------------------------------------------------------

def birthday(bits, n=4096, tbits=30):
    """Birthday spacings: n birthdays in 2^tbits days; duplicate spacings
    ~ Poisson(n^3 / 4k). Parameterized so lambda = n^3/4k stays in the
    Poisson regime (lambda << n)."""
    days = (bits[:n] >> (32 - tbits)).astype(jnp.uint32)
    s = jnp.sort(days)
    spacings = jnp.sort(jnp.diff(s))
    dup = jnp.sum((jnp.diff(spacings) == 0)).astype(jnp.float32)
    lam = n ** 3 / (4.0 * (1 << tbits))
    return dup, poisson_midp_upper(dup, lam)


def collision(bits, n=65536, kbits=24):
    """n balls into 2^kbits urns; collision count ~ Poisson(mean) in the
    sparse regime n << k (upper-tail sf; both tails are flagged by the
    suspect rule, matching TestU01's convention)."""
    urns = (bits[:n] >> (32 - kbits)).astype(jnp.uint32)
    s = jnp.sort(urns)
    distinct = 1.0 + jnp.sum(jnp.diff(s) != 0).astype(jnp.float32)
    coll = n - distinct
    k = float(1 << kbits)
    mean = n - k + k * (1.0 - 1.0 / k) ** n
    return coll, poisson_midp_upper(coll, max(mean, 1e-9))


def gap(bits, n=65536, beta=0.125, maxlen=20):
    """Gaps between visits to [0, beta); chi2 vs geometric."""
    u = to_unit(bits[:n])
    hit = u < beta
    idx = jnp.arange(n)
    last = jax.lax.cummax(jnp.where(hit, idx, -1))
    prev = jnp.concatenate([jnp.array([-1]), last[:-1]])
    gaps = jnp.where(hit, idx - prev - 1, -1)
    gapc = jnp.clip(gaps, -1, maxlen)
    counts = jnp.bincount(jnp.where(hit, gapc, maxlen + 1), length=maxlen + 2
                          )[:maxlen + 1].astype(jnp.float32)
    n_hits = jnp.sum(counts)
    probs = np.array([beta * (1 - beta) ** i for i in range(maxlen)]
                     + [(1 - beta) ** maxlen], np.float32)
    stat = chi2_from_counts(counts, n_hits * probs)
    return stat, chi2_sf(stat, maxlen)


def _stirling_probs(d=8, hand=5):
    """P[r distinct among `hand` draws from d values]."""
    # Stirling numbers of the second kind S(hand, r)
    S = np.zeros((hand + 1, hand + 1))
    S[0, 0] = 1
    for nn in range(1, hand + 1):
        for rr in range(1, nn + 1):
            S[nn, rr] = rr * S[nn - 1, rr] + S[nn - 1, rr - 1]
    probs = []
    for r in range(1, hand + 1):
        perm = 1.0
        for j in range(r):
            perm *= (d - j)
        probs.append(S[hand, r] * perm / d ** hand)
    return np.array(probs, np.float32)


def poker(bits, n=32768, d=8, hand=5):
    """Distinct values per hand of 5 3-bit digits; chi2."""
    digits = (bits[:n * hand] >> 29).astype(jnp.int32).reshape(n, hand)
    s = jnp.sort(digits, axis=1)
    distinct = 1 + jnp.sum(jnp.diff(s, axis=1) != 0, axis=1)
    # merge the rare r<=2 bins (expected count ~1e-4*n) for chi2 validity
    distinct = jnp.maximum(distinct, 2)
    counts = jnp.bincount(distinct - 2, length=hand - 1).astype(jnp.float32)
    probs = _stirling_probs(d, hand)
    probs = np.concatenate([[probs[0] + probs[1]], probs[2:]])
    stat = chi2_from_counts(counts, n * probs)
    return stat, chi2_sf(stat, hand - 2)


def coupon(bits, n=65536, d=8, maxlen=30):
    """Coupon-collector segment lengths; chi2 vs exact distribution."""
    dbits = int(d).bit_length() - 1
    assert (1 << dbits) == d, "d must be a power of two"
    digits = (bits[:n] >> (32 - dbits)).astype(jnp.int32)

    def body(st, dig):
        mask, ln, hist = st
        mask = mask | (1 << dig)
        ln = ln + 1
        done = mask == (1 << d) - 1
        binp = jnp.clip(ln - d, 0, maxlen - 1)
        hist = jnp.where(done, hist.at[binp].add(1.0), hist)
        mask = jnp.where(done, 0, mask)
        ln = jnp.where(done, 0, ln)
        return (mask, ln, hist), None

    (_, _, hist), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
               jnp.zeros((maxlen,), jnp.float32)), digits)
    # P[segment length = d+j]: exact via inclusion-exclusion on "all seen"
    def p_all_seen(ln):
        tot = 0.0
        for i in range(d + 1):
            tot += (-1) ** i * math.comb(d, i) * ((d - i) / d) ** ln
        return tot
    probs = np.array(
        [p_all_seen(d + j) - p_all_seen(d + j - 1) for j in range(maxlen - 1)]
        + [1.0 - p_all_seen(d + maxlen - 2)], np.float32)
    n_seg = jnp.sum(hist)
    stat = chi2_from_counts(hist, n_seg * np.maximum(probs, 1e-12))
    return stat, chi2_sf(stat, maxlen - 1)


def maxoft(bits, n=16384, t=8):
    """x = max(u_1..u_t)^... : F(x) = x^t, so x^t ~ U(0,1); KS."""
    u = to_unit(bits[:n * t]).reshape(n, t)
    m = jnp.max(u, axis=1) ** t
    return jnp.max(m), ks_pvalue(jnp.sort(m))


def weight(bits, n=65536):
    """Hamming weights of words vs Binomial(32, 1/2); chi2 (10..22 + tails)."""
    w = jax.lax.population_count(bits[:n]).astype(jnp.int32)
    lo, hi = 10, 22
    b = jnp.clip(w, lo, hi) - lo
    counts = jnp.bincount(b, length=hi - lo + 1).astype(jnp.float32)
    probs = []
    for k in range(lo, hi + 1):
        if k == lo:
            probs.append(sum(math.comb(32, j) for j in range(0, lo + 1)) / 2 ** 32)
        elif k == hi:
            probs.append(sum(math.comb(32, j) for j in range(hi, 33)) / 2 ** 32)
        else:
            probs.append(math.comb(32, k) / 2 ** 32)
    probs = np.array(probs, np.float32)
    stat = chi2_from_counts(counts, n * probs)
    return stat, chi2_sf(stat, hi - lo)


def gf2_rank32(mats):
    """Bit-packed GF(2) rank of (M, 32) uint32 row-matrices (pure-jnp ref
    for kernels/gf2_rank)."""
    m = mats.shape[0]
    rows0 = mats
    used0 = jnp.zeros((m, 32), bool)
    rank0 = jnp.zeros((m,), jnp.int32)
    ridx = jnp.arange(32)

    def body(i, st):
        rows, used, rank = st
        col = ((rows >> (31 - i)) & 1) == 1               # (M, 32)
        cand = col & ~used
        has = cand.any(axis=1)
        piv = jnp.argmax(cand, axis=1)                    # first candidate
        pivrow = jnp.take_along_axis(rows, piv[:, None], 1)[:, 0]
        pivrow = jnp.where(has, pivrow, 0)
        apply = col & (ridx[None, :] != piv[:, None])
        rows = jnp.where(apply, rows ^ pivrow[:, None], rows)
        used = used | (jax.nn.one_hot(piv, 32, dtype=bool) & has[:, None])
        rank = rank + has.astype(jnp.int32)
        return rows, used, rank

    _, _, rank = jax.lax.fori_loop(0, 32, body, (rows0, used0, rank0))
    return rank


def _rank_probs(dim=32):
    """P[rank = dim - j] for random GF(2) dim x dim; bins j=0,1,2,>=3."""
    def p_rank(r):
        # prod_{i=0}^{r-1} (1-2^{i-dim})^2 / (1-2^{i-r}) ... standard formula
        p = 2.0 ** (-(dim - r) * (dim - r))
        for i in range(r):
            p *= (1 - 2.0 ** (i - dim)) ** 2 / (1 - 2.0 ** (i - r))
        return p
    full, m1, m2 = p_rank(dim), p_rank(dim - 1), p_rank(dim - 2)
    return np.array([max(1 - full - m1 - m2, 1e-12), m2, m1, full],
                    np.float32)


def rank(bits, n_mats=1024):
    """32x32 GF(2) matrix rank distribution; chi2 over {<=29, 30, 31, 32}."""
    mats = bits[:n_mats * 32].reshape(n_mats, 32)
    r = gf2_rank32(mats)
    b = jnp.clip(r - 29, 0, 3)
    counts = jnp.bincount(b, length=4).astype(jnp.float32)
    stat = chi2_from_counts(counts, n_mats * _rank_probs(32))
    return stat, chi2_sf(stat, 3)


def hamcorr(bits, n=65536):
    """Lag-1 correlation of word Hamming weights; normal."""
    w = jax.lax.population_count(bits[:n]).astype(jnp.float32) - 16.0
    z = jnp.sum(w[:-1] * w[1:]) / (8.0 * math.sqrt(n - 1))
    return z, normal_p_two_sided(z)


def serial2d(bits, n=65536, d=64):
    """Non-overlapping pairs into d x d cells; chi2."""
    dbits = int(d).bit_length() - 1
    assert (1 << dbits) == d, "d must be a power of two"
    u = bits[:2 * n]
    x = (u[0::2] >> (32 - dbits)).astype(jnp.int32)
    y = (u[1::2] >> (32 - dbits)).astype(jnp.int32)
    cell = x * d + y
    counts = jnp.bincount(cell, length=d * d).astype(jnp.float32)
    stat = chi2_from_counts(counts, np.full((d * d,), n / (d * d),
                                            np.float32))
    return stat, chi2_sf(stat, d * d - 1)


def pairstream(bits, n=32768, mode="corr"):
    """Inter-stream disjointness/correlation at a sub-stream seam.

    The block is TWO adjacent sub-streams of one generator laid end to
    end: ``bits[:n]`` is the tail of stream s, ``bits[n:2n]`` the head of
    stream s+1 (the campaign dispatches this kernel at the seam offsets
    from ``rng.generators.seam_offsets``). Under the null the halves are
    independent; a broken jump-ahead offset (overlapping or correlated
    sub-streams) is exactly what each mode is sensitive to:

      ``corr``     Pearson cross-correlation of the unit floats,
                   z ~ N(0,1) two-sided
      ``hamcorr``  cross-correlation of word Hamming weights (catches
                   bit-level coupling the float map would wash out)
      ``match``    same-index word equality count ~ Poisson(n / 2^32) —
                   any match at all is a near-certain duplication
      ``shift``    equality between h1's last k and h2's first k words,
                   k = 1..8 — a seam that is off by k (stream s+1
                   starting k words early) duplicates exactly that
                   window
    """
    a, b = bits[:n], bits[n:2 * n]
    if mode == "corr":
        ua = to_unit(a) - 0.5
        ub = to_unit(b) - 0.5
        z = jnp.sum(ua * ub) * 12.0 / math.sqrt(n)   # var(U(-.5,.5)) = 1/12
        return z, normal_p_two_sided(z)
    if mode == "hamcorr":
        wa = jax.lax.population_count(a).astype(jnp.float32) - 16.0
        wb = jax.lax.population_count(b).astype(jnp.float32) - 16.0
        z = jnp.sum(wa * wb) / (8.0 * math.sqrt(n))  # var(weight) = 8
        return z, normal_p_two_sided(z)
    if mode == "match":
        m = jnp.sum(a == b).astype(jnp.float32)
        return m, poisson_midp_upper(m, n / 2.0 ** 32)
    if mode == "shift":
        maxk = 8
        m = jnp.float32(0.0)
        for k in range(1, maxk + 1):
            m = m + jnp.sum(a[n - k:] == b[:k]).astype(jnp.float32)
        lam = sum(range(1, maxk + 1)) / 2.0 ** 32
        return m, poisson_midp_upper(m, lam)
    raise KeyError(f"unknown pairstream mode {mode!r}; "
                   "known: corr, hamcorr, match, shift")


KERNELS: Dict[str, Callable] = {
    "birthday": birthday, "collision": collision, "gap": gap,
    "poker": poker, "coupon": coupon, "maxoft": maxoft, "weight": weight,
    "rank": rank, "hamcorr": hamcorr, "serial2d": serial2d,
    "pairstream": pairstream,
}
