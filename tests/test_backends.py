"""Backend-registry parity suite (ISSUE 4): for every registered kernel
family the accelerated (Pallas, interpret mode on CPU) implementation
must agree with the pure-jnp reference on (stat, p); the jump-ahead
generator blocks must be bit-identical to their sequential scan twins
(including mid-stream ``offset`` continuation); and a fixed-seed battery
must stitch the same verdict under ``backend=reference`` and
``backend=accelerated``."""
import numpy as np
import pytest

from repro.common.compat import x64
from repro.core import pool
from repro.core.api import PoolSession, RunSpec
from repro.core.battery import build_battery, routing_report, split_entry
from repro.rng import generators as G
from repro.stats import backends as B

# family -> small/large parameterizations (the "2 scales" of the parity
# contract; sizes chosen so every code path engages at CI speed)
PARITY_CASES = {
    "gap": [dict(n=4096), dict(n=16384)],
    "poker": [dict(n=1024), dict(n=4096)],
    "weight": [dict(n=4096), dict(n=16384)],
    "serial2d": [dict(n=2048, d=16), dict(n=8192, d=32)],
    "collision": [dict(n=2048, kbits=14), dict(n=8192, kbits=16)],
    "rank": [dict(n_mats=256), dict(n_mats=512)],
    # no accelerated impl — the registry must fall back to reference
    "birthday": [dict(n=1024, tbits=24)],
    "coupon": [dict(n=4096, d=8)],
    "maxoft": [dict(n=2048, t=8)],
    "hamcorr": [dict(n=4096)],
    "pairstream": [dict(n=1024, mode="corr"), dict(n=1024, mode="match")],
}


def _bits(seed, n=262144):
    with x64():
        return G.splitmix64_block(seed, 1, n)


# ------------------------------------------------------------- registry

def test_registry_covers_every_family():
    assert B.families() == sorted(PARITY_CASES)
    assert B.accelerated_families() == sorted(
        ["gap", "poker", "weight", "serial2d", "collision", "rank"])


def test_resolve_and_auto():
    assert B.resolve("reference") == "reference"
    assert B.resolve("accelerated") == "accelerated"
    assert B.resolve("auto") in ("reference", "accelerated")
    with pytest.raises(KeyError):
        B.resolve("vectorized")


def test_fallback_for_unaccelerated_family():
    """A family without an accelerated impl resolves to its reference —
    a battery-wide backend choice always yields a full job table — and
    the entry says why."""
    assert B.route("birthday", "accelerated")[0] is B.route(
        "birthday", "reference")[0]
    assert B.route("birthday", "accelerated")[1] == (
        "birthday has no accelerated kernel")
    assert B.route("birthday", "reference")[1] == ""


def test_routing_report_names_every_fallback():
    """The launchers' per-family routing lines: counts per implementation
    and the reason for each reference fallback under accelerated."""
    lines = routing_report(build_battery("bigcrush", 1.0,
                                         backend="accelerated"))
    by = {line.split(":")[0]: line for line in lines}
    assert by["rank"] == "rank: 8 accelerated"
    assert by["birthday"] == ("birthday: 12 reference "
                              "(birthday has no accelerated kernel)")
    assert by["collision"].startswith("collision: 13 reference (")
    assert "HIST_MAX_BINS" in by["collision"]


def test_resolve_interpret_never_hides_the_chip(monkeypatch):
    """"auto" interprets exactly off a TPU; an explicit interpreter on a
    TPU is refused outside a test run."""
    import jax

    from repro.kernels import resolve_interpret
    assert resolve_interpret("auto") is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret("auto") is False
    assert resolve_interpret(True) is True          # inside a test: allowed
    monkeypatch.delenv("PYTEST_CURRENT_TEST")
    with pytest.raises(ValueError, match="interpreter"):
        resolve_interpret(True)


# ------------------------------------------------- (stat, p) parity

@pytest.mark.parametrize("family", sorted(PARITY_CASES))
@pytest.mark.parametrize("seed", [1, 7, 31])
def test_accelerated_matches_reference(family, seed):
    bits = _bits(seed)
    for kw in PARITY_CASES[family]:
        s1, p1 = B.route(family, "reference", **kw)[0](bits, **kw)
        s2, p2 = B.route(family, "accelerated", **kw)[0](bits, **kw)
        np.testing.assert_allclose(float(s1), float(s2), rtol=1e-5,
                                   err_msg=f"{family} stat {kw}")
        np.testing.assert_allclose(float(p1), float(p2), rtol=1e-5,
                                   atol=1e-7, err_msg=f"{family} p {kw}")


def test_collision_large_urn_space_falls_back():
    """Above HIST_MAX_BINS the accelerated backend routes collision to the
    sort-based reference (the bin-count's N x K compare work), says so,
    and the battery entry records the reason."""
    kw = dict(n=4096, kbits=24)          # 2^24 urns > HIST_MAX_BINS
    fn, why = B.route("collision", "accelerated", **kw)
    assert fn is B.route("collision", "reference")[0]
    assert "HIST_MAX_BINS" in why
    fn, why = B.route("collision", "accelerated", n=4096, kbits=16)
    assert fn is B.collision_accel and why == ""
    bits = _bits(3)
    s1, p1 = B.route("collision", "reference")[0](bits, **kw)
    s2, p2 = B.route("collision", "accelerated", **kw)[0](bits, **kw)
    assert float(s1) == float(s2) and float(p1) == float(p2)
    acc = build_battery("bigcrush", 1.0, backend="accelerated")
    coll = [e for e in acc if e.kname == "collision"]
    assert coll and all(e.impl == "reference" and e.fallback for e in coll)
    assert all(e.impl == "accelerated" for e in acc
               if e.kname in ("gap", "poker", "weight", "serial2d", "rank"))


# --------------------------------------- jump-ahead generator bit-exactness

@pytest.mark.parametrize("gen", sorted(G.SCAN_REFERENCE))
@pytest.mark.parametrize("seed", [0, 9, 123])
def test_jump_matches_scan(gen, seed):
    jump, scan = G.GENERATORS[gen], G.SCAN_REFERENCE[gen]
    with x64():
        for n in (37, 1024):
            a = np.asarray(jump(seed, 5, n))
            b = np.asarray(scan(seed, 5, n))
            assert (a == b).all(), (gen, n)


@pytest.mark.parametrize("gen", sorted(G.SCAN_REFERENCE))
def test_jump_offset_continuation(gen):
    """Mid-stream continuation: block(n)[k:] == block(n-k, offset=k) —
    the property that lets the former scan generators join
    COUNTER_BASED."""
    jump = G.GENERATORS[gen]
    with x64():
        full = np.asarray(jump(11, 2, 300))
        for k in (1, 128, 299):
            tail = np.asarray(jump(11, 2, 300 - k, offset=k))
            assert (full[k:] == tail).all(), (gen, k)


def test_counter_based_complement_is_mwc():
    """Every generator except mwc is counter-based now (jump-ahead gave
    the linear recurrences exact offset continuation)."""
    assert set(G.GENERATORS) - set(G.COUNTER_BASED) == {"mwc"}


# ------------------------------------------------- battery-level threading

def test_build_battery_binds_backend():
    ref = build_battery("smallcrush", 0.125, backend="reference")
    acc = build_battery("smallcrush", 0.125, backend="accelerated")
    assert all(e.backend == "reference" for e in ref)
    assert all(e.backend == "accelerated" for e in acc)
    # identical table geometry: same names, words, costs — only kernels
    assert [(e.name, e.n_words, e.cost) for e in ref] == \
           [(e.name, e.n_words, e.cost) for e in acc]
    sub = split_entry(acc[4], 2, start_index=0)
    assert all(s.backend == "accelerated" for s in sub)


def test_runspec_backend_validation():
    with pytest.raises(KeyError):
        RunSpec("smallcrush", backend="gpu")
    assert RunSpec("smallcrush").backend == "auto"


def test_bucketed_blocks_bound_waste():
    """Power-of-two bucketing keeps generated/read <= 1.25 on smallcrush
    (the acceptance bound) and < the old battery-wide-max ratio."""
    for scale in (0.125, 1.0):
        entries = build_battery("smallcrush", scale)
        ratio = pool.block_ratio(entries)
        legacy = (len(entries) * max(e.n_words for e in entries)
                  / pool.read_words(entries))
        assert 1.0 <= ratio <= 1.25, (scale, ratio)
        assert ratio < legacy, (scale, ratio, legacy)
    assert pool.word_bucket(0) == 0
    assert pool.word_bucket(1) == 1
    assert pool.word_bucket(4096) == 4096
    assert pool.word_bucket(4097) == 8192


def test_smallcrush_verdict_identical_across_backends():
    """Acceptance: a fixed-seed smallcrush run stitches the same p-values
    and the same verdict under backend=reference and
    backend=accelerated, from one session (distinct cache slots)."""
    session = PoolSession()
    res = {}
    for backend in ("reference", "accelerated"):
        res[backend] = session.submit(
            RunSpec("smallcrush", "pcg32", seeds=17, scale=0.0625,
                    backend=backend)).result()
    ref, acc = res["reference"], res["accelerated"]
    assert ref.verdict.decision == acc.verdict.decision
    assert sorted(ref.results) == sorted(acc.results)
    for i in ref.results:
        np.testing.assert_allclose(ref.results[i][1], acc.results[i][1],
                                   rtol=1e-5, atol=1e-7)
    # the two backends compiled as separate cache slots, not one
    keys = {k[-1] for k in session.trace_counts}
    assert keys == {"reference", "accelerated"}
