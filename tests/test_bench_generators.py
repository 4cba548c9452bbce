"""The benchmark's plain generators (``bench/generators/<name>.py``, NumPy
and Python integers, nothing of ``src/``) give bitwise the words of the
program's generators (``rng/generators.py``), on a grid of seeds, streams
and lengths, and of the sequential twins where the program keeps one."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402
from repro.common.compat import x64  # noqa: E402
from repro.rng import generators as G  # noqa: E402

REFERENCES = ("splitmix64", "msweyl", "threefry", "pcg32", "lcg64",
              "xorshift64s", "randu", "minstd")
SEEDS = (0, 1, 2**31 - 1, 0x5DEECE66D1234567)
STREAMS = (0, 105)
LENGTHS = (1, 63, 64, 65, 4133, 65536)


def _program(fn, seed, stream, n):
    with x64():
        return np.asarray(jax.jit(fn, static_argnums=2)(
            np.int64(seed), np.int64(stream), n))


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("name", REFERENCES)
def test_reference_matches_the_program(name, n):
    block = reference.generator(name)
    fn = getattr(G, name + "_block")
    for seed in SEEDS:
        for stream in STREAMS:
            want = _program(fn, seed, stream, n)
            got = block(seed, stream, n)
            assert got.dtype == np.uint32 and got.shape == (n,)
            assert np.array_equal(got, want), (seed, stream)


@pytest.mark.parametrize("name", sorted(G.SCAN_REFERENCE))
def test_reference_matches_the_sequential_twin(name):
    block = reference.generator(name)
    for seed in SEEDS:
        for stream in STREAMS:
            want = _program(G.SCAN_REFERENCE[name], seed, stream, 4133)
            assert np.array_equal(block(seed, stream, 4133), want), (
                seed, stream)
