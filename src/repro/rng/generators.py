"""Generators under test, in JAX.

Every generator exposes ``block(seed, stream, n) -> uint32[n]`` — a fresh,
order-independent stream per (seed, stream) pair. This is the TestU01-
parallel "individual test re-instantiates the generator" semantics (paper
§4.1/§11) made deterministic: job results are bitwise independent of which
worker/round executes them, which is what makes the pool's hold/release and
speculative re-execution free to reconcile.

Counter-based generators (splitmix64, threefry, pcg32/lcg64 via LCG
jump-ahead, middle-square-weyl) evaluate lanes fully in parallel. The
classic recurrences xorshift64*, RANDU and MINSTD are ALSO evaluated in
parallel via jump-ahead cycle splitting: their step maps are linear
(an affine map mod 2^64 / a multiplicative map mod 2^31 or 2^31-1 / a
GF(2)-linear map on 64 bits), so lane i computes step^i(s0) directly
with a square-and-multiply ladder of log-depth — bit-exact with the
sequential recurrence (the ``*_block_scan`` twins kept for tests and
benchmarks). Only MWC still runs as ``lax.scan``: its lag-1 carry chain
has no cheap jump. RANDU is deliberately included as a known-bad
generator the battery must flag.

64-bit integer ops require tracing under x64 (``with x64():``, the one
scoped switch in ``common/compat.py``); constants here are Python ints so
nothing truncates at import time. All public entry points are safe to
trace inside the battery's jitted programs, which open the scope around
generation only.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = 0x9E3779B97F4A7C15
MASK32 = 0xFFFFFFFF


def _u64(x):
    return jnp.asarray(x, jnp.uint64)


def _mix_seed(seed, stream):
    return (_u64(seed) * _u64(6364136223846793005)
            + _u64(stream) * _u64(GOLDEN) + _u64(1442695040888963407))


def _hi32(x):
    return (x >> 32).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# counter-based

def _splitmix_hash(z):
    z = (z + _u64(GOLDEN))
    z = (z ^ (z >> 30)) * _u64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> 27)) * _u64(0x94D049BB133111EB)
    return z ^ (z >> 31)


def splitmix64_block(seed, stream, n, offset=0):
    base = _mix_seed(seed, stream)
    ctr = (jnp.arange(n, dtype=jnp.uint64) + _u64(offset)) * _u64(GOLDEN) + base
    return _hi32(_splitmix_hash(ctr))


def msweyl_block(seed, stream, n, offset=0):
    """Middle-Square Weyl sequence (Widynski) — counter form."""
    s = _mix_seed(seed, stream) | _u64(1)
    w = (jnp.arange(1, n + 1, dtype=jnp.uint64) + _u64(offset)) * s
    x = w
    for _ in range(3):
        x = x * x + w
        x = (x >> 32) | (x << 32)
    return _hi32(x)


def threefry_block(seed, stream, n, offset=0):
    """Threefry in explicit counter mode: word i is
    ``bits(fold_in(fold_in(key, hi32(c)), lo32(c)))`` for the 64-bit
    counter ``c = offset + i``, one key-hash chain per element, vmapped.
    jax.random.bits over a whole shape is NOT continuation-stable (its
    threefry2x32 pairs the iota's halves, so the pairing depends on the
    block length) — hashing each counter independently is, at a small
    constant factor in hashing cost. The counter is folded as two
    32-bit halves because ``fold_in`` takes 32-bit data: a single
    truncated fold would silently wrap past 2^32 words and alias
    distant campaign sub-streams (exactly the overlap the pairstream
    check exists to rule out)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
    ctr = jnp.arange(n, dtype=jnp.uint64) + _u64(offset)
    hi = (ctr >> 32).astype(jnp.uint32)
    lo = (ctr & _u64(MASK32)).astype(jnp.uint32)
    return jax.vmap(lambda h, l: jax.random.bits(
        jax.random.fold_in(jax.random.fold_in(key, h), l), (),
        jnp.uint32))(hi, lo)


LCG_A = 6364136223846793005
LCG_C = 1442695040888963407


def _lcg_jump(s0, idx, nbits=64):
    """state_i = A^i s0 + C (A^i-1)/(A-1), per lane in ``nbits`` ladder
    steps (enough to cover every lane index, see ``_jump_bits``)."""
    def step(bit, st):
        a_acc, c_acc, a_pow, c_pow = st
        take = ((idx >> bit.astype(jnp.uint64)) & 1) == 1
        c_acc = jnp.where(take, c_acc * a_pow + c_pow, c_acc)
        a_acc = jnp.where(take, a_acc * a_pow, a_acc)
        return a_acc, c_acc, a_pow * a_pow, c_pow * (a_pow + 1)
    init = (jnp.ones_like(idx), jnp.zeros_like(idx),
            jnp.broadcast_to(_u64(LCG_A), idx.shape),
            jnp.broadcast_to(_u64(LCG_C), idx.shape))
    a_acc, c_acc, _, _ = jax.lax.fori_loop(0, nbits, step, init)
    return a_acc * s0 + c_acc


def pcg32_block(seed, stream, n, offset=0):
    """PCG-XSH-RR 64/32 with per-lane LCG jump-ahead."""
    st = _lcg_jump(_mix_seed(seed, stream),
                   jnp.arange(n, dtype=jnp.uint64) + _u64(offset),
                   _jump_bits(n, offset))
    xorshifted = (((st >> 18) ^ st) >> 27).astype(jnp.uint32)
    rot = (st >> 59).astype(jnp.uint32)
    return (xorshifted >> rot) | (xorshifted << ((-rot) & jnp.uint32(31)))


def lcg64_block(seed, stream, n, offset=0):
    st = _lcg_jump(_mix_seed(seed, stream),
                   jnp.arange(n, dtype=jnp.uint64) + _u64(offset),
                   _jump_bits(n, offset))
    return _hi32(st)


# ---------------------------------------------------------------------------
# jump-ahead cycle splitting (log-depth twins of the classic recurrences)

def _jump_bits(n, offset):
    """Ladder length: enough exponent bits to cover every lane index
    ``0..n+offset``. Static when offset is a Python int (the battery hot
    path); a traced offset falls back to the full 64-bit ladder. The
    ladders are rolled loops (``lax.fori_loop``), so their program size
    does not grow with the length."""
    if isinstance(offset, (int, np.integer)):
        return max(int(int(n) + int(offset)).bit_length(), 1)
    return 64


def _pow_jump(idx, mult, nbits, mulmod):
    """``mult^idx`` per lane by square-and-multiply — the ``_lcg_jump``
    ladder generalized to any associative product ``mulmod``."""
    def step(bit, st):
        acc, apow = st
        take = ((idx >> bit.astype(jnp.uint64)) & 1) == 1
        return jnp.where(take, mulmod(acc, apow), acc), mulmod(apow, apow)
    acc, _ = jax.lax.fori_loop(
        0, nbits, step,
        (jnp.ones_like(idx), jnp.broadcast_to(_u64(mult), idx.shape)))
    return acc


@functools.lru_cache(maxsize=1)
def _xs_jump_cols():
    """Columns of M^(2^k), k = 0..63, for the xorshift64 step matrix M
    (the 12/25/27 shift-XOR map is linear over GF(2)^64). Host-side
    precompute: column b of M is step(e_b); squaring applies the current
    power to each of its own columns (matvec = XOR of selected columns)."""
    mask = (1 << 64) - 1
    cols = []
    for b in range(64):
        s = 1 << b
        s ^= s >> 12
        s ^= (s << 25) & mask
        s ^= s >> 27
        cols.append(s)
    cols = np.array(cols, np.uint64)
    powers = np.empty((64, 64), np.uint64)
    for k in range(64):
        powers[k] = cols
        nxt = np.zeros(64, np.uint64)
        for j in range(64):
            bit = ((cols >> np.uint64(j)) & np.uint64(1)).astype(bool)
            nxt = np.where(bit, nxt ^ cols[j], nxt)
        cols = nxt
    return powers


def _xs_jump(s0, idx, nbits):
    """``M^idx s0`` per lane: GF(2) square-and-multiply over the
    precomputed matrix powers, O(64 log idx) depth instead of an O(idx)
    scan. The matvec is an XOR-reduce of the state-selected columns
    (one (lanes, 64) reduce per ladder step keeps the trace small; XOR
    is exact, so bit-exactness vs the scan twin is preserved)."""
    pows = jnp.asarray(_xs_jump_cols())
    bitpos = jnp.arange(64, dtype=jnp.uint64)

    def step(k, s):
        take = ((idx >> k.astype(jnp.uint64)) & 1) == 1
        sel = jnp.where(((s[:, None] >> bitpos[None, :]) & 1) == 1,
                        pows[k][None, :], _u64(0))
        y = jax.lax.reduce(sel, _u64(0), jax.lax.bitwise_xor, (1,))
        return jnp.where(take, y, s)
    return jax.lax.fori_loop(0, nbits, step,
                             jnp.broadcast_to(s0, idx.shape))


# xorshift cycle-split chunk: each lane jump-starts its segment with the
# GF(2) ladder, then steps XS_CHUNK times — the ladder (the expensive 64-
# column matvec) runs once per CHUNK outputs instead of once per output,
# and the residual sequential depth is a constant 64, not O(n)
XS_CHUNK = 64


def xorshift64s_block(seed, stream, n, offset=0):
    """xorshift64* via jump-ahead cycle splitting: lane l jumps directly
    to state M^(l*CHUNK+offset) s0 (log-depth GF(2) ladder), then a
    vmapped constant-length micro-scan emits its segment. Bit-exact with
    the sequential recurrence (``xorshift64s_block_scan``)."""
    s0 = _mix_seed(seed, stream) | _u64(1)
    lanes = -(-n // XS_CHUNK)
    starts = (jnp.arange(lanes, dtype=jnp.uint64) * XS_CHUNK
              + _u64(offset))
    lane0 = _xs_jump(s0, starts, _jump_bits(n, offset))

    def step(s, _):
        s = s ^ (s >> 12)
        s = s ^ (s << 25)
        s = s ^ (s >> 27)
        return s, s

    def segment(st):
        _, outs = jax.lax.scan(step, st, None, length=XS_CHUNK)
        return outs

    states = jax.vmap(segment)(lane0).reshape(-1)[:n]
    return _hi32(states * _u64(0x2545F4914F6CDD1D))


def randu_block(seed, stream, n, offset=0):
    """RANDU: x <- 65539 x mod 2^31, via multiplicative jump-ahead
    (x_i = 65539^i x_0 — the modulus is a power of two, so the ring
    product is a masked multiply). Famously defective — the battery's
    canary (must FAIL spectral-sensitive tests)."""
    s0 = (_mix_seed(seed, stream) & _u64(0x7FFFFFFF)) | _u64(1)
    idx = jnp.arange(1, n + 1, dtype=jnp.uint64) + _u64(offset)

    def mm(a, b):
        return (a * b) & _u64(0x7FFFFFFF)
    st = mm(jnp.broadcast_to(s0, idx.shape),
            _pow_jump(idx, 65539, _jump_bits(n, offset), mm))
    return (st << 1).astype(jnp.uint32)


M31 = 2147483647                # 2^31 - 1, MINSTD's (Mersenne) modulus


def _mod_m31(x):
    """``x mod (2^31 - 1)`` for uint64 ``x`` by Mersenne folding:
    2^31 = 1 (mod 2^31 - 1), so adding the 31-bit digits keeps the
    residue. Shifts and adds only — a 64-bit remainder is a long
    emulated sequence on accelerators without 64-bit integer units."""
    m = _u64(M31)
    x = (x & m) + ((x >> 31) & m) + (x >> 62)       # < 2^32 + 2
    x = (x & m) + (x >> 31)                         # <= 2^31 + 1
    return jnp.where(x >= m, x - m, x)


def _minstd_seed(seed, stream):
    """``mix mod (2^31 - 2) + 1``, a state in [1, 2^31 - 2]. The modulus
    is 2 (2^30 - 1): the residue mod the Mersenne number 2^30 - 1 comes
    from folding 30-bit digits, and of its two lifts below 2 (2^30 - 1)
    the one with the parity of ``mix`` is the residue (Chinese
    remainders; 2^30 - 1 is odd)."""
    x = _mix_seed(seed, stream)
    m = _u64((1 << 30) - 1)
    q = (x & m) + ((x >> 30) & m) + (x >> 60)       # < 2^32
    q = (q & m) + (q >> 30)                         # < 2^30 + 4
    q = jnp.where(q >= m, q - m, q)
    q = jnp.where((q & _u64(1)) == (x & _u64(1)), q, q + m)
    return q + _u64(1)


def minstd_block(seed, stream, n, offset=0):
    """MINSTD: x <- 16807 x mod (2^31 - 1), via multiplicative jump-ahead
    (prime modulus; 62-bit products fit uint64)."""
    s0 = _minstd_seed(seed, stream)
    idx = jnp.arange(1, n + 1, dtype=jnp.uint64) + _u64(offset)

    def mm(a, b):
        return _mod_m31(a * b)
    st = mm(jnp.broadcast_to(s0, idx.shape),
            _pow_jump(idx, 16807, _jump_bits(n, offset), mm))
    return (st << 1).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# sequential recurrences

def _scan_block(step, state0, n):
    def body(st, _):
        return step(st)
    _, outs = jax.lax.scan(body, state0, None, length=n)
    return outs


def xorshift64s_block_scan(seed, stream, n):
    """The O(n)-sequential twin of ``xorshift64s_block`` (tests assert the
    jump path is bit-exact against this)."""
    def step(s):
        s = s ^ (s >> 12)
        s = s ^ (s << 25)
        s = s ^ (s >> 27)
        return s, _hi32(s * _u64(0x2545F4914F6CDD1D))
    return _scan_block(step, _mix_seed(seed, stream) | _u64(1), n)


def mwc_block(seed, stream, n):
    """Multiply-with-carry (Marsaglia), 32-bit lag-1. The ONLY generator
    still evaluated as a sequential ``lax.scan`` — the carry chain is not
    linear in any cheap ring, so there is no O(1) jump-ahead; it is the
    lone member of ``COUNTER_BASED``'s complement and does not accept an
    ``offset``."""
    s = _mix_seed(seed, stream)
    x0 = (s >> 32) | _u64(1)
    c0 = (s & _u64(MASK32)) | _u64(1)

    def step(st):
        x, c = st
        t = _u64(4294957665) * (x & _u64(MASK32)) + c
        return (t & _u64(MASK32), t >> 32), (t & _u64(MASK32)).astype(jnp.uint32)
    return _scan_block(step, (x0, c0), n)


def randu_block_scan(seed, stream, n):
    """Sequential twin of ``randu_block`` (bit-exactness reference)."""
    s0 = (_mix_seed(seed, stream) & _u64(0x7FFFFFFF)) | _u64(1)

    def step(s):
        s = (s * _u64(65539)) & _u64(0x7FFFFFFF)
        return s, (s << 1).astype(jnp.uint32)
    return _scan_block(step, s0, n)


def minstd_block_scan(seed, stream, n):
    """Sequential twin of ``minstd_block`` (bit-exactness reference)."""
    def step(s):
        s = (s * _u64(16807)) % _u64(2147483647)
        return s, (s << 1).astype(jnp.uint32)
    s0 = (_mix_seed(seed, stream) % _u64(2147483646)) + _u64(1)
    return _scan_block(step, s0, n)


# sequential references for the jump-ahead generators, keyed by name —
# what tests/test_backends.py asserts bit-exactness against
SCAN_REFERENCE: Dict[str, Callable] = {
    "xorshift64s": xorshift64s_block_scan,
    "randu": randu_block_scan,
    "minstd": minstd_block_scan,
}


# ---------------------------------------------------------------------------
# the plugin registry (rng/sources.py): built-ins register here, in the
# historical dict order so their stable gen_ids match every checkpoint,
# ledger and cache digest minted before the BitSource layer existed.
# GENERATORS / GEN_IDS are re-exported LIVE views (the same dict objects
# sources.py mutates on register/unregister); gen_block_by_id is the
# registry-backed switch. Counter-based: block(seed, stream, n, offset)
# supports exact continuation — block(n=2k) == block(n=k) ++
# block(n=k, offset=k) — the property that makes sequential-reuse mode
# and over-decomposition exact. xorshift64s/randu/minstd joined via
# jump-ahead cycle splitting; mwc's lag-1 carry chain has no cheap jump,
# stays a sequential lax.scan, and takes no offset.

from repro.rng.sources import (  # noqa: E402
    GENERATORS,
    GEN_IDS,
    register_generator,
    switch_block as gen_block_by_id,
)

register_generator("splitmix64", splitmix64_block, counter_based=True)
register_generator("msweyl", msweyl_block, counter_based=True)
register_generator("threefry", threefry_block, counter_based=True)
register_generator("pcg32", pcg32_block, counter_based=True)
register_generator("lcg64", lcg64_block, counter_based=True)
register_generator("xorshift64s", xorshift64s_block, counter_based=True)
register_generator("mwc", mwc_block, counter_based=False)
register_generator("randu", randu_block, counter_based=True)
register_generator("minstd", minstd_block, counter_based=True)


def __getattr__(name):
    """``COUNTER_BASED`` is DERIVED from the live registry (PEP 562):
    the static tuple is retired so a runtime-registered generator's
    declared capability is visible everywhere the old constant was
    consulted, with no second source of truth to fall stale."""
    if name == "COUNTER_BASED":
        from repro.rng.sources import counter_based_names
        return counter_based_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# campaign stream grids (cycle splitting at the block level)

def stream_offsets(n_streams: int, span: int) -> np.ndarray:
    """Word offsets of ``n_streams`` disjoint parallel sub-streams spaced
    ``span`` words apart: stream s owns ``[s * span, (s + 1) * span)`` of
    every (seed, stream-id) sequence. With ``span >= `` the widest block
    any battery job reads, cells of a campaign grid consume disjoint
    words by construction — the modern analogue of the paper's "one
    generator per idle machine" is "one sub-stream per grid cell"."""
    if n_streams < 1:
        raise ValueError(f"n_streams must be >= 1, got {n_streams}")
    if span < 1:
        raise ValueError(
            f"span must be >= 1, got {span}: a zero or negative span "
            f"would hand every stream overlapping (or wrapped) words")
    last = (n_streams - 1) * span            # exact Python-int arithmetic
    if last > np.iinfo(np.int64).max:
        raise ValueError(
            f"stream {n_streams - 1} offset {last} overflows int64 "
            f"words; shrink span ({span}) or n_streams ({n_streams})")
    return np.arange(n_streams, dtype=np.int64) * np.int64(span)


def seam_offsets(n_streams: int, span: int, n_words: int) -> np.ndarray:
    """Block offsets straddling each adjacent-stream SEAM: pair s reads
    ``[(s+1)*span - n_words, (s+1)*span + n_words)`` — the last
    ``n_words`` words of stream s followed by the first ``n_words`` of
    stream s+1. A ``pairstream`` kernel splits that block in half and
    checks the halves are uncorrelated and disjoint, which is exactly
    where an off-by-one in the jump-ahead offset arithmetic would show
    up (overlapping or correlated words across the seam)."""
    if n_streams < 2:
        return np.zeros((0,), np.int64)
    if span < 1:
        raise ValueError(
            f"span must be >= 1, got {span}: a zero or negative span "
            f"would place stream 1's seam at or before word 0 and wrap")
    if n_words < 1:
        raise ValueError(f"n_words must be >= 1, got {n_words}")
    if n_words > span:
        raise ValueError(
            f"seam block of {n_words} words needs span >= n_words, "
            f"got span={span}")
    hi = (n_streams - 1) * span + n_words    # exact Python-int arithmetic
    if hi > np.iinfo(np.int64).max:
        raise ValueError(
            f"seam {n_streams - 2} (streams {n_streams - 2}|"
            f"{n_streams - 1}) reads up to word {hi}, which overflows "
            f"int64; shrink span ({span}) or n_streams ({n_streams})")
    seams = np.arange(1, n_streams, dtype=np.int64) * np.int64(span)
    return seams - np.int64(n_words)


def to_unit(bits):
    """uint32 -> float32 in [0, 1)."""
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
