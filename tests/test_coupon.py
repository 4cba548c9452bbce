"""Coupon's blocked lock-step scan against the serial walk it replaces.

``_coupon_hist`` cuts the digits into blocks, scans the blocks in
lock-step and corrects the block entry states to a fixed point. Its
histogram must be the serial walk's on every input, random or not, and
``coupon``'s ``(stat, p)`` must be bitwise those of the per-digit scan
it replaced, kept here as the oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.compat import x64
from repro.core.battery import build_battery
from repro.rng import generators as G
from repro.stats import tests as T
from repro.stats.special import chi2_from_counts, chi2_sf

MAXLEN = 30


def serial_walk(digits, d, maxlen=MAXLEN):
    """The per-digit walk: collect values until all ``d`` are seen, bin
    the segment's length, start again."""
    hist = np.zeros(maxlen, np.int64)
    mask = ln = 0
    for v in np.asarray(digits).tolist():
        mask |= 1 << v
        ln += 1
        if mask == (1 << d) - 1:
            hist[min(max(ln - d, 0), maxlen - 1)] += 1
            mask = ln = 0
    return hist


def scan_coupon(bits, n=65536, d=8, maxlen=MAXLEN):
    """The per-digit device scan ``coupon`` ran before its blocked scan,
    operation for operation."""
    dbits = int(d).bit_length() - 1
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


def _random(d, n, seed=0):
    return np.random.default_rng(seed * 1000 + d).integers(0, d, n)


def _periodic(d, n, period):
    return np.arange(n) % period % d


def _first_d_all(d, n):
    digits = _random(d, n, seed=5)
    digits[:d] = np.random.default_rng(d).permutation(d)
    return digits


# (id, d, digits, the passes they may take at most: two where every
# block's chains meet, as on random digits and on a stuck digit, whose
# saturated lengths make every exit state equal; else one a block)
CASES = (
    [(f"random-d{d}-n{n}", d, _random(d, n), 2)
     for d in (4, 8, 16) for n in (256, 4096 + 37, 65536)]
    + [("random-d16-n1048576", 16, _random(16, 1 << 20), 2)]
    + [(f"period{p}-d{d}", d, _periodic(d, 4096 + 37, p), None)
       for d in (4, 8, 16) for p in (d, d + 1, 2 * d - 1)]
    + [("stuck-d8", 8, np.full(4096 + 37, 3), 2),
       ("short-n300-d16", 16, _random(16, 300), None),
       ("short-n100-d16", 16, _random(16, 100), None),
       ("first-d-all-d8", 8, _first_d_all(8, 4096 + 37), None)])


@pytest.mark.parametrize("d,digits,most", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_blocked_histogram_is_the_serial_walk(d, digits, most):
    n = len(digits)
    L = T._coupon_block(n, d)
    n_blocks = -(-n // L)
    hist, passes = jax.jit(T._coupon_hist, static_argnums=(1, 2, 3))(
        jnp.asarray(digits, jnp.int32), d, MAXLEN, L)
    np.testing.assert_array_equal(np.asarray(hist),
                                  serial_walk(digits, d).astype(np.float32))
    passes = int(passes)
    assert 1 <= passes <= n_blocks + 1
    if n_blocks == 1:
        assert passes == 1
    if most is not None:
        assert passes <= most


@pytest.mark.parametrize("n,d,L", [
    (65536, 8, 512),                 # SmallCrush: 128 blocks
    (524288, 4, 512), (524288, 8, 512), (524288, 16, 1024),
    (1048576, 4, 1024), (1048576, 8, 1024), (1048576, 16, 1024),
    (65536, 16, 1024),               # Crush: 16 mean segments first
    (256, 16, 256), (300, 16, 256), (100, 16, 64)])
def test_block_length_comes_from_the_shape(n, d, L):
    assert T._coupon_block(n, d) == L


def _battery_coupon(name, scale, d):
    return next(dict(e.params) for e in build_battery(name, scale)
                if e.kname == "coupon" and dict(e.params)["d"] == d)


@pytest.mark.parametrize("params", [
    _battery_coupon("smallcrush", 1.0, 8),
    _battery_coupon("bigcrush", 2.0, 16),
    dict(n=4096, d=8)], ids=lambda p: f"n{p['n']}-d{p['d']}")
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_stat_and_p_are_bitwise_the_serial_scan(params, seed):
    with x64():
        bits = G.splitmix64_block(seed, 1, params["n"])
    new = jax.jit(lambda b: T.coupon(b, **params))(bits)
    old = jax.jit(lambda b: scan_coupon(b, **params))(bits)
    for a, b in zip(new, old):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_each_pass_is_scoped():
    """A pass's ops carry ``repro.test.coupon.pass`` in their HLO
    metadata, so a device trace shows the passes inside the kernel."""
    with x64():
        bits = G.splitmix64_block(1, 1, 4096)
    text = jax.jit(lambda b: T.coupon(b, n=4096, d=8)).lower(
        bits).compile().as_text()
    assert "/repro.test.coupon.pass/" in text
