"""Kernel backend registry: every statistical test family behind one
``bits -> (stat, p)`` signature, with a ``reference`` (pure-jnp,
``stats/tests.py``) and — where a hand-written Pallas kernel covers the
hot loop — an ``accelerated`` implementation (DESIGN.md §7).

The accelerated paths route the counting hot loops through the Pallas
kernels:

  gap / poker / weight / serial2d / collision
      -> ``kernels/histogram`` (scatter-free bin-count, bin axis tiled)
  rank
      -> ``kernels/gf2_rank``  (bit-packed GF(2) elimination)

``route`` decides, per battery entry, which implementation runs and
says why when it is not the one asked for. Under ``accelerated`` an
entry runs the reference when

  * its family has no Pallas kernel (birthday, coupon, maxoft, hamcorr,
    pairstream), or
  * its histogram would have more than ``HIST_MAX_BINS`` bins: the
    bin-count kernel compares every index with every bin, so its work
    grows as N x K. Collision entries at paper scale (2^24..2^28 urns)
    keep the sort-based reference for that reason.

The reason is recorded on the ``TestEntry`` (``fallback``) and printed
by the launchers — a fallback is never silent. Both implementations of
a family share the same probability model and p-value machinery;
parity to float32 tolerance is asserted in ``tests/test_backends.py``
for every registered family.

Backend names:

  ``reference``    the pure-jnp kernels — the oracle
  ``accelerated``  Pallas kernels (``interpret="auto"``: compiled on
                   TPU, interpreted elsewhere so CI runs the same code)
  ``auto``         resolves to ``accelerated`` on a TPU backend and
                   ``reference`` everywhere else
"""
from __future__ import annotations

import inspect
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gf2_rank.ops import rank32
from repro.kernels.histogram.ops import bincount
from repro.rng.generators import to_unit
from repro.stats import tests as T
from repro.stats.special import (chi2_from_counts, chi2_sf,
                                 poisson_midp_upper)

BACKENDS = ("auto", "reference", "accelerated")

# Most bins ``route`` sends to the bin-count kernel. VMEM does not bound
# K (the kernel tiles the bin axis); its N x K compare work does.
HIST_MAX_BINS = 1 << 16

# bins of the histogram an accelerated entry builds, by its params (a
# param the entry leaves unset takes the accelerated function's default)
_HIST_BINS = {
    "collision": lambda p: 1 << p["kbits"],
    "serial2d": lambda p: p["d"] ** 2,
}

_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register(kname: str, backend: str, fn: Callable) -> None:
    """Register ``fn`` as the ``backend`` implementation of test family
    ``kname``. Signature contract: ``fn(bits, **params) -> (stat, p)``."""
    if backend not in ("reference", "accelerated"):
        raise KeyError(f"backend must be reference|accelerated, "
                       f"got {backend!r}")
    _REGISTRY.setdefault(kname, {})[backend] = fn


def families() -> list:
    """Every registered test family name."""
    return sorted(_REGISTRY)


def accelerated_families() -> list:
    """Families with a real accelerated implementation (no fallback)."""
    return sorted(k for k, d in _REGISTRY.items() if "accelerated" in d)


def default_backend() -> str:
    """What ``auto`` means here: accelerated on real TPU hardware,
    reference under interpret/CPU (the Pallas interpreter would only
    slow a CPU battery down; parity tests opt in explicitly)."""
    return "accelerated" if jax.default_backend() == "tpu" else "reference"


def resolve(backend: str) -> str:
    """Map a user-facing backend name to a concrete one."""
    if backend not in BACKENDS:
        raise KeyError(f"unknown backend {backend!r}; known: {BACKENDS}")
    return default_backend() if backend == "auto" else backend


def route(kname: str, backend: str = "reference",
          **params) -> Tuple[Callable, str]:
    """``(fn, fallback)``: the implementation an entry of family ``kname``
    with ``params`` runs under ``backend`` (resolved), and why it is the
    reference although ``accelerated`` was asked for ("" when it is what
    was asked). A battery-wide backend choice always yields a full job
    table; the fallback reason is what makes the choice visible."""
    impls = _REGISTRY[kname]
    if resolve(backend) == "reference":
        return impls["reference"], ""
    if "accelerated" not in impls:
        return impls["reference"], f"{kname} has no accelerated kernel"
    fn = impls["accelerated"]
    if kname in _HIST_BINS:
        defaults = {k: v.default for k, v in
                    inspect.signature(fn).parameters.items()}
        bins = _HIST_BINS[kname]({**defaults, **params})
        if bins > HIST_MAX_BINS:
            return impls["reference"], (f"{bins} bins > HIST_MAX_BINS="
                                        f"{HIST_MAX_BINS}")
    return fn, ""


# ---------------------------------------------------------------------------
# accelerated implementations (counting hot loops on the Pallas kernels;
# probability models shared with the reference in stats/tests.py)


def gap_accel(bits, n=65536, beta=0.125, maxlen=20):
    """`gap` with the gap-length histogram on the fused bin-count."""
    u = to_unit(bits[:n])
    hit = u < beta
    idx = jnp.arange(n)
    last = jax.lax.cummax(jnp.where(hit, idx, -1))
    prev = jnp.concatenate([jnp.array([-1]), last[:-1]])
    gaps = jnp.where(hit, idx - prev - 1, -1)
    gapc = jnp.clip(gaps, -1, maxlen)
    bins = jnp.where(hit, gapc, maxlen + 1).astype(jnp.int32)
    counts = bincount(bins, maxlen + 2)[:maxlen + 1]
    n_hits = jnp.sum(counts)
    probs = np.array([beta * (1 - beta) ** i for i in range(maxlen)]
                     + [(1 - beta) ** maxlen], np.float32)
    stat = chi2_from_counts(counts, n_hits * probs)
    return stat, chi2_sf(stat, maxlen)


def poker_accel(bits, n=32768, d=8, hand=5):
    """`poker` with the distinct-count histogram on the fused bin-count."""
    digits = (bits[:n * hand] >> 29).astype(jnp.int32).reshape(n, hand)
    s = jnp.sort(digits, axis=1)
    distinct = 1 + jnp.sum(jnp.diff(s, axis=1) != 0, axis=1)
    distinct = jnp.maximum(distinct, 2)
    counts = bincount((distinct - 2).astype(jnp.int32), hand - 1)
    probs = T._stirling_probs(d, hand)
    probs = np.concatenate([[probs[0] + probs[1]], probs[2:]])
    stat = chi2_from_counts(counts, n * probs)
    return stat, chi2_sf(stat, hand - 2)


def weight_accel(bits, n=65536):
    """`weight` with the Hamming-weight histogram on the fused bin-count."""
    w = jax.lax.population_count(bits[:n]).astype(jnp.int32)
    lo, hi = 10, 22
    b = (jnp.clip(w, lo, hi) - lo).astype(jnp.int32)
    counts = bincount(b, hi - lo + 1)
    probs = []
    for k in range(lo, hi + 1):
        if k == lo:
            probs.append(sum(math.comb(32, j)
                             for j in range(0, lo + 1)) / 2 ** 32)
        elif k == hi:
            probs.append(sum(math.comb(32, j)
                             for j in range(hi, 33)) / 2 ** 32)
        else:
            probs.append(math.comb(32, k) / 2 ** 32)
    probs = np.array(probs, np.float32)
    stat = chi2_from_counts(counts, n * probs)
    return stat, chi2_sf(stat, hi - lo)


def serial2d_accel(bits, n=65536, d=64):
    """`serial2d` with the cell histogram on the fused bin-count."""
    dbits = int(d).bit_length() - 1
    assert (1 << dbits) == d, "d must be a power of two"
    u = bits[:2 * n]
    x = (u[0::2] >> (32 - dbits)).astype(jnp.int32)
    y = (u[1::2] >> (32 - dbits)).astype(jnp.int32)
    cell = (x * d + y).astype(jnp.int32)
    counts = bincount(cell, d * d)
    stat = chi2_from_counts(counts, np.full((d * d,), n / (d * d),
                                            np.float32))
    return stat, chi2_sf(stat, d * d - 1)


def collision_accel(bits, n=65536, kbits=24):
    """`collision` with urn occupancy on the fused bin-count: distinct
    urns = occupied bins, so the collision count needs no sort. ``route``
    sends urn spaces above ``HIST_MAX_BINS`` to the reference."""
    k = 1 << kbits
    assert k <= HIST_MAX_BINS, (k, HIST_MAX_BINS)
    urns = (bits[:n] >> (32 - kbits)).astype(jnp.int32)
    occ = bincount(urns, k)
    distinct = jnp.sum(occ > 0).astype(jnp.float32)
    coll = n - distinct
    kf = float(k)
    mean = n - kf + kf * (1.0 - 1.0 / kf) ** n
    return coll, poisson_midp_upper(coll, max(mean, 1e-9))


def rank_accel(bits, n_mats=1024):
    """`rank` on the bit-packed Pallas GF(2) elimination kernel, with the
    4-bin rank histogram on the fused bin-count."""
    mats = bits[:n_mats * 32].reshape(n_mats, 32)
    r = rank32(mats)
    b = jnp.clip(r - 29, 0, 3).astype(jnp.int32)
    counts = bincount(b, 4)
    stat = chi2_from_counts(counts, n_mats * T._rank_probs(32))
    return stat, chi2_sf(stat, 3)


# ---------------------------------------------------------------------------
# registration: every family gets a reference; six get accelerated paths

for _k, _fn in T.KERNELS.items():
    register(_k, "reference", _fn)

for _k, _fn in {"gap": gap_accel, "poker": poker_accel,
                "weight": weight_accel, "serial2d": serial2d_accel,
                "collision": collision_accel, "rank": rank_accel}.items():
    register(_k, "accelerated", _fn)
