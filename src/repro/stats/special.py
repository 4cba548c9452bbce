"""p-value machinery in JAX: chi-square, normal, Poisson, Kolmogorov."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def chi2_sf(x, k):
    """P[Chi2_k >= x] (regularized upper incomplete gamma)."""
    return jax.scipy.special.gammaincc(k / 2.0, x / 2.0)


def normal_sf(z):
    return jax.scipy.special.ndtr(-z)


def normal_p_two_sided(z):
    return 2.0 * jax.scipy.special.ndtr(-jnp.abs(z))


def poisson_sf(k, lam):
    """P[Poisson(lam) >= k] = gammainc(k, lam) (regularized lower)."""
    return jnp.where(k <= 0, 1.0, jax.scipy.special.gammainc(
        jnp.maximum(k, 1e-9), lam))


def poisson_midp_upper(k, lam):
    """Mid-p upper tail: P[X > k] + 0.5 P[X = k] — approximately uniform
    under H0 for discrete Poisson statistics (both tails then flag via the
    suspect rule)."""
    p_ge = poisson_sf(k, lam)
    p_ge1 = poisson_sf(k + 1.0, lam)
    return jnp.clip(p_ge - 0.5 * (p_ge - p_ge1), 1e-300, 1.0)


def kolmogorov_sf(lam):
    """Q(lam) = 2 sum_{j>=1} (-1)^{j-1} exp(-2 j^2 lam^2)."""
    j = jnp.arange(1, 101, dtype=jnp.float32)
    terms = jnp.power(-1.0, j - 1) * jnp.exp(-2.0 * j ** 2 * lam ** 2)
    return jnp.clip(2.0 * jnp.sum(terms), 0.0, 1.0)


def ks_pvalue(sorted_u):
    """One-sample KS against U(0,1). sorted_u: ascending float32[n]."""
    n = sorted_u.shape[0]
    i = jnp.arange(1, n + 1, dtype=jnp.float32)
    d_plus = jnp.max(i / n - sorted_u)
    d_minus = jnp.max(sorted_u - (i - 1) / n)
    d = jnp.maximum(d_plus, d_minus)
    lam = (jnp.sqrt(float(n)) + 0.12 + 0.11 / jnp.sqrt(float(n))) * d
    return kolmogorov_sf(lam)


def chi2_from_counts(counts, expected):
    """Pearson chi-square statistic, with TestU01-style clamping of tiny
    expected bins.

    ``expected`` is either a numpy array (counts known on the host) or a
    traced array (counts that depend on the data, as in gap and coupon,
    which scale by the observed number of hits or segments). A numpy
    array is inverted on the host, and each compiled program multiplies
    by the same reciprocal: a device division by a constant is rewritten
    by XLA into a reciprocal multiply in some programs and not in others
    (a constant hoisted out of a branch stays a division), which made
    the statistic depend on the pool width in its last bit. A traced
    divisor is no constant, so no program rewrites it, and it is divided
    on the device."""
    if isinstance(expected, np.ndarray):
        expected = expected.clip(min=1e-9).astype(np.float32)
        inv = (1.0 / expected).astype(np.float32)
        return jnp.sum(jnp.square(counts - expected) * inv)
    expected = jnp.maximum(expected, 1e-9)
    return jnp.sum(jnp.square(counts - expected) / expected)
