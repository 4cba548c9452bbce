"""Calibration + property suite for the e-value verdict engine
(core/evidence.py, DESIGN.md §13).

Four layers, cheapest first:

  calibrator math      the κp^(κ-1) family and the mixture calibrator
                       are exactly what the paper trail promises: unit
                       mean under the null (numerically integrated),
                       closed form == numeric κ-integration, correct
                       limits at both ends of [0, 1].
  engine semantics     ``evidence_verdict`` decision logic: Ville
                       crossing, completion PASS, the borderline band,
                       validation, trajectory bookkeeping.
  calibration          the anytime false-FAIL rate on synthetic null
                       batteries stays within the binomial CI of alpha
                       (the PR 2 harness machinery: Wilson intervals),
                       including under adversarial interim looks; the
                       power gate has randu FAIL crush within 12 rounds.
  end to end           real batteries/campaigns/serve under
                       ``verdict_engine="evalue"``: wealth trajectories,
                       checkpoint v5 wealth leaves, engine-mismatch
                       refusal, borderline continuation, cache engine
                       isolation.

Property tests use ``hypothesis``.
"""
import math
import os

import numpy as np
import pytest

_trapz = getattr(np, "trapezoid", np.trapz)

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.ckpt import io as ckpt_io
from repro.core import evidence, stitch
from repro.core.api import (CampaignSpec, Checkpoint, PoolSession,
                            RunSpec)
from repro.core.campaign import Campaign
from repro.core.evidence import (CALIBRATORS, EvidenceVerdict,
                                 VerdictEngineMismatch, combine_log_wealth,
                                 evidence_verdict, kappa_calibrator,
                                 log_evalue, log_kappa_evalue,
                                 log_mixture_evalue, mixture_calibrator,
                                 two_sided_p, wealth_from_log)
from repro.core.stitch import FAIL, PASS, UNDECIDED

SCALE = 0.0625
KAPPAS = (0.05, 0.1, 0.25, 0.4, 0.5, 0.6, 0.75, 0.9, 0.95)
P_GRID = (1e-12, 1e-8, 1e-4, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.999)


@pytest.fixture(scope="module")
def session():
    return PoolSession()


def wilson_ci(k: int, n: int, z: float = 2.576):
    """99% Wilson score interval for a binomial proportion."""
    p = k / n
    denom = 1 + z ** 2 / n
    center = (p + z ** 2 / (2 * n)) / denom
    half = z * np.sqrt(p * (1 - p) / n + z ** 2 / (4 * n ** 2)) / denom
    return center - half, center + half


# ------------------------------------------------- calibrator family

@pytest.mark.parametrize("kappa", KAPPAS)
def test_kappa_calibrator_has_unit_mean(kappa):
    """E[e(P)] = ∫₀¹ κp^(κ-1) dp = [p^κ]₀¹ = 1 exactly. The
    antiderivative pins the full mass; a fine trapezoid on the
    singularity-free subinterval [0.1, 0.9] must agree with the
    antiderivative there (the implementation IS the density it
    claims)."""
    assert 1.0 ** kappa - 0.0 ** kappa == pytest.approx(1.0)
    p = np.linspace(0.1, 0.9, 200001)
    numeric = _trapz([kappa_calibrator(float(x), kappa) for x in p], p)
    assert numeric == pytest.approx(0.9 ** kappa - 0.1 ** kappa,
                                    abs=1e-6)


@pytest.mark.parametrize("kappa", KAPPAS)
def test_kappa_calibrator_is_decreasing_in_p(kappa):
    vals = [kappa_calibrator(p, kappa) for p in P_GRID]
    assert vals == sorted(vals, reverse=True)


@pytest.mark.parametrize("p", P_GRID)
def test_kappa_calibrator_matches_formula(p):
    for kappa in (0.25, 0.5, 0.75):
        assert kappa_calibrator(p, kappa) == pytest.approx(
            kappa * p ** (kappa - 1.0), rel=1e-12)


@pytest.mark.parametrize("kappa", (-0.5, 0.0, 1.0, 1.5))
def test_kappa_outside_open_unit_interval_raises(kappa):
    with pytest.raises(ValueError, match="kappa"):
        kappa_calibrator(0.5, kappa)
    with pytest.raises(ValueError, match="kappa"):
        log_kappa_evalue(0.5, kappa)


@pytest.mark.parametrize("p", P_GRID)
def test_mixture_matches_numeric_kappa_integration(p):
    """The closed form F(p) = (1 - p + p·ln p)/(p·(ln p)²) must equal
    ∫₀¹ κp^(κ-1) dκ (the uniform mixture over the family)."""
    kappas = np.linspace(1e-6, 1.0 - 1e-6, 200001)
    numeric = _trapz(kappas * p ** (kappas - 1.0), kappas)
    assert mixture_calibrator(p) == pytest.approx(numeric, rel=1e-3)


def test_mixture_has_unit_mean():
    """By Fubini, ∫ₐᵇ F(p) dp = ∫₀¹ (b^κ - a^κ) dκ = (b-1)/ln b -
    (a-1)/ln a; at (a, b) → (0, 1) that is 1 - 0 — unit mean. Check the
    implementation against the closed form on a singularity-free
    subinterval."""
    def mass(x):
        return (x - 1.0) / math.log(x)
    a, b = 0.1, 0.9
    p = np.linspace(a, b, 200001)
    numeric = _trapz([mixture_calibrator(float(x)) for x in p], p)
    assert numeric == pytest.approx(mass(b) - mass(a), abs=1e-6)
    # the endpoints' limits: lim_{a→0} (a-1)/ln a = 0, lim_{b→1} = 1
    assert mass(1e-12) == pytest.approx(0.0, abs=0.04)
    assert mass(1.0 - 1e-12) == pytest.approx(1.0, abs=1e-9)


def test_mixture_limit_at_one_is_half():
    """lim_{p→1} F(p) = 1/2 (l'Hôpital twice); the implementation must
    not 0/0 at the boundary."""
    assert mixture_calibrator(1.0) == pytest.approx(0.5)
    assert mixture_calibrator(1.0 - 1e-9) == pytest.approx(0.5, abs=1e-3)


def test_mixture_is_huge_at_tiny_p():
    assert mixture_calibrator(1e-12) > 1e9
    assert mixture_calibrator(1e-300) > 1e290


@pytest.mark.parametrize("p", P_GRID)
@pytest.mark.parametrize("cal", CALIBRATORS)
def test_log_evalue_consistent_with_linear_calibrator(p, cal):
    lin = (kappa_calibrator(p) if cal == "kappa"
           else mixture_calibrator(p))
    assert math.exp(log_evalue(p, calibrator=cal)) == pytest.approx(
        lin, rel=1e-10)


def test_log_evalue_rejects_unknown_calibrator():
    with pytest.raises(KeyError, match="calibrator"):
        log_evalue(0.5, calibrator="fisher")


@pytest.mark.parametrize("p", (0.0, 1e-12, 0.01, 0.3, 0.5))
def test_two_sided_p_is_symmetric(p):
    assert two_sided_p(p) == pytest.approx(two_sided_p(1.0 - p))
    assert two_sided_p(p) == pytest.approx(min(1.0, 2.0 * p))


def test_two_sided_p_validates_domain():
    assert two_sided_p(0.5) == 1.0
    for bad in (-0.1, 1.1, float("nan")):
        with pytest.raises(ValueError):
            two_sided_p(bad)


def test_two_sided_fold_preserves_uniformity():
    """p₂ = 2·min(p, 1-p) of a Uniform(0,1) is Uniform(0,1) — the fold
    that lets one-sided calibrators spend on BOTH suspect tails without
    breaking the unit-mean guarantee."""
    rng = np.random.default_rng(3)
    u = rng.uniform(size=200000)
    folded = np.array([two_sided_p(p) for p in u])
    hist, _ = np.histogram(folded, bins=20, range=(0.0, 1.0))
    assert hist.min() > 0.8 * len(u) / 20
    assert hist.max() < 1.2 * len(u) / 20


def test_evidence_constants_match_stitch():
    """evidence.py keeps local PASS/FAIL/UNDECIDED copies (stitch
    imports evidence, not the reverse) — they must never drift."""
    assert evidence.PASS == stitch.PASS == "PASS"
    assert evidence.FAIL == stitch.FAIL == "FAIL"
    assert evidence.UNDECIDED == stitch.UNDECIDED == "UNDECIDED"


def test_verdict_engine_registry():
    assert set(stitch.VERDICT_ENGINES) == {"bonferroni", "evalue"}
    assert stitch.verdict_for("bonferroni") is stitch.sequential_verdict
    assert stitch.verdict_for("evalue") is evidence_verdict
    with pytest.raises(KeyError, match="bonferroni"):
        stitch.verdict_for("fisher")


# --------------------------------------------------- engine semantics

def test_empty_results_are_undecided():
    v = evidence_verdict({}, 10, 0.01)
    assert v.decision == UNDECIDED and not v.decided
    assert v.n_checked == 0 and v.log_wealth == 0.0 and v.wealth == 1.0


def test_null_battery_passes_at_completion():
    v = evidence_verdict({i: (0.0, 0.5) for i in range(10)}, 10, 0.01)
    assert v.decision == PASS and v.decided
    assert v.wealth < 1.0                       # e(0.5-ish p) < 1


def test_catastrophic_p_fails_immediately():
    v = evidence_verdict({3: (9.0, 1e-12)}, 10, 0.01)
    assert v.decision == FAIL and v.decided
    assert v.failed_tests == (3,)
    assert v.wealth >= 1.0 / 0.01


def test_high_tail_p_fails_too():
    """TestU01's two-sided suspect convention: p ≈ 1 is as damning as
    p ≈ 0 — the two-sided fold must route it into the calibrator."""
    v = evidence_verdict({2: (9.0, 1.0 - 1e-12)}, 10, 0.01)
    assert v.decision == FAIL and v.failed_tests == (2,)


def test_accumulated_moderate_evidence_fails():
    """No single test is damning but the product crosses 1/alpha —
    the martingale composition the Bonferroni engine cannot express."""
    results = {i: (0.0, 1e-3) for i in range(6)}
    v = evidence_verdict(results, 10, 0.01)
    assert v.decision == FAIL
    single = evidence_verdict({0: (0.0, 1e-3)}, 10, 0.01)
    assert single.decision == UNDECIDED        # one alone is not enough
    assert single.failed_tests == ()


def test_invalid_p_values_are_skipped():
    v = evidence_verdict({0: (1.0, float("nan")), 1: (1.0, -0.5),
                          2: (1.0, 2.0), 3: (0.0, 0.5)}, 10, 0.01)
    assert v.n_checked == 1
    assert v.decision == UNDECIDED


@pytest.mark.parametrize("n_total", (0, -3))
def test_engine_rejects_bad_n_total(n_total):
    with pytest.raises(ValueError, match="n_total"):
        evidence_verdict({}, n_total, 0.01)


@pytest.mark.parametrize("alpha", (0.0, 1.0, -0.2, 1.5))
def test_engine_rejects_bad_alpha(alpha):
    with pytest.raises(ValueError, match="alpha"):
        evidence_verdict({}, 10, alpha)


@pytest.mark.parametrize("band", (-0.1, 1.0, 2.0))
def test_engine_rejects_bad_band(band):
    with pytest.raises(ValueError, match="band"):
        evidence_verdict({}, 10, 0.01, band=band)


def test_band_holds_borderline_cells_open():
    """At completion, wealth inside [band/alpha, 1/alpha) is UNDECIDED
    (borderline) when a band is configured, PASS when it is not."""
    results = {i: (0.0, 0.01) for i in range(4)}       # some evidence
    full = {**results, **{i: (0.0, 0.5) for i in range(4, 10)}}
    closed = evidence_verdict(full, 10, 0.01, band=0.0)
    assert closed.decision == PASS and not closed.borderline
    open_ = evidence_verdict(full, 10, 0.01, band=0.01)
    assert 0.01 / 0.01 <= open_.wealth < 1.0 / 0.01
    assert open_.decision == UNDECIDED and open_.borderline


def test_band_does_not_touch_clear_pass():
    v = evidence_verdict({i: (0.0, 0.5) for i in range(10)}, 10, 0.01,
                         band=0.5)
    assert v.decision == PASS and not v.borderline


def test_trajectory_is_cumulative_in_test_order():
    results = {5: (0.0, 0.2), 1: (0.0, 0.01), 3: (0.0, 0.4)}
    v = evidence_verdict(results, 10, 0.01)
    traj = v.trajectory
    assert len(traj) == 3
    expect = []
    acc = 0.0
    for i in (1, 3, 5):                        # ascending test index
        acc += log_evalue(two_sided_p(results[i][1]))
        expect.append(wealth_from_log(acc))
    assert traj == pytest.approx(tuple(expect))
    assert traj[-1] == pytest.approx(v.wealth)


def test_verdict_str_names_engine_quantities():
    s = str(evidence_verdict({0: (0.0, 1e-12)}, 10, 0.01))
    assert "FAIL" in s and "wealth" in s and "alpha=0.01" in s


def test_log_wealth_never_overflows():
    results = {i: (0.0, 1e-300) for i in range(50)}
    v = evidence_verdict(results, 50, 0.01)
    assert v.decision == FAIL
    assert math.isfinite(v.wealth)              # capped, not inf
    assert all(math.isfinite(w) for w in v.trajectory)


@pytest.mark.parametrize("kappa", (0.2, 0.5, 0.8))
def test_engine_kappa_calibrator_option(kappa):
    v = evidence_verdict({0: (0.0, 1e-14)}, 10, 0.01,
                         calibrator="kappa", kappa=kappa)
    assert v.decision == FAIL
    assert v.log_wealth == pytest.approx(
        log_kappa_evalue(two_sided_p(1e-14), kappa))


# ------------------------------------------------- calibration gates

def test_null_false_fail_rate_within_binomial_ci_of_alpha():
    """Calibration headline: m synthetic null batteries through the
    e-value engine. Ville guarantees P(FAIL) <= alpha; the Wilson CI of
    the observed rate must be consistent with that (lower bound below
    alpha) — the engine is allowed to be conservative, never
    anti-conservative."""
    rng = np.random.default_rng(42)
    n, alpha, m = 10, 0.05, 4000
    fails = 0
    for _ in range(m):
        ps = rng.uniform(size=n)
        v = evidence_verdict({i: (0.0, p) for i, p in enumerate(ps)},
                             n, alpha)
        assert v.decision in (PASS, FAIL)
        fails += v.decision == FAIL
    lo, hi = wilson_ci(fails, m)
    assert lo <= alpha, (fails, m, lo, hi)
    assert fails / m <= alpha, (fails, m)


def test_anytime_false_fail_rate_under_interim_looks():
    """The point of an e-process: look after EVERY result and FAIL the
    moment wealth crosses — the sup over all interim looks must still
    respect alpha (a fixed-sample test abused this way would not)."""
    rng = np.random.default_rng(7)
    n, alpha, m = 10, 0.05, 4000
    crossed = 0
    for _ in range(m):
        ps = rng.uniform(size=n)
        for k in range(1, n + 1):
            v = evidence_verdict(
                {i: (0.0, ps[i]) for i in range(k)}, n, alpha)
            if v.decision == FAIL:
                crossed += 1
                break
    lo, hi = wilson_ci(crossed, m)
    assert lo <= alpha, (crossed, m, lo, hi)


def test_power_moderate_alternative_beats_single_look():
    """Under a diffuse alternative (p ~ Beta(0.3, 1): small but not
    catastrophic) the mixture-martingale engine must actually reject
    most of the time — conservativeness under the null must not mean
    uselessness under the alternative."""
    rng = np.random.default_rng(11)
    n, alpha, m = 10, 0.05, 500
    fails = sum(
        evidence_verdict(
            {i: (0.0, p) for i, p in
             enumerate(rng.beta(0.3, 1.0, size=n) * 0.1)},
            n, alpha).decision == FAIL
        for _ in range(m))
    assert fails / m > 0.8, fails


@pytest.mark.slow
def test_power_gate_randu_fails_crush_within_12_rounds(session):
    """ISSUE gate: randu must FAIL crush under the e-value engine in at
    most 12 of its ~96 rounds — early stopping has to actually engage
    on a catastrophically bad generator."""
    spec = RunSpec("crush", "randu", 9, scale=SCALE, policy="adaptive",
                   stop_on_verdict=True, verdict_engine="evalue")
    res = session.submit(spec).result()
    assert res.verdict.decision == FAIL
    assert res.rounds_run <= 12, res.rounds_run


def test_engines_agree_on_decided_smallcrush_verdicts(session):
    """Fast agreement gate: a complete smallcrush screen decided by both
    engines must decide the same way (PASS the good generator, FAIL
    randu) — the engines differ in WHEN they decide, never on WHAT."""
    spec = RunSpec("smallcrush", ("splitmix64", "randu"), seeds=(7, 7),
                   scale=SCALE)
    res = session.submit(spec).result()
    n = len(session.entries(spec))
    for gen, run in res.runs.items():
        b = stitch.sequential_verdict(run.results, n, 0.01)
        e = evidence_verdict(run.results, n, 0.01)
        assert b.decided and e.decided
        assert b.decision == e.decision, (gen, b.decision, e.decision)


@pytest.mark.slow
def test_engines_agree_on_every_decided_crush_verdict(session):
    """ISSUE gate, benchmarks/early_stop.py's sweep: every generator in
    the registry, complete crush results, both engines.  All decided
    verdicts must match outside the razor-thin margin; inside it the
    documented (DESIGN.md §13) conservatism of the product e-process is
    the ONLY divergence allowed — Bonferroni rejects on a single test's
    p a small factor under its ``alpha/2n`` line, while the product of
    96 e-values stays diluted below ``1/alpha``.  The divergence must
    therefore (a) run in the conservative direction only (never an
    e-value FAIL that Bonferroni calls PASS), and (b) rest on a lone
    marginal test: exactly one Bonferroni-failed test whose p is within
    32x of the per-tail threshold and whose single e-value cannot carry
    the 96-test family on its own (below ``n/alpha``, the e-Bonferroni
    line)."""
    from repro.rng.generators import GENERATORS
    gens = tuple(sorted(GENERATORS))
    spec = RunSpec("crush", gens, seeds=(9,) * len(gens), scale=SCALE)
    res = session.submit(spec).result()
    n = len(session.entries(spec))
    decided_both = agreed = 0
    for gen, run in res.runs.items():
        b = stitch.sequential_verdict(run.results, n, 0.01)
        e = evidence_verdict(run.results, n, 0.01)
        assert b.decided and e.decided, gen
        decided_both += 1
        if b.decision == e.decision:
            agreed += 1
            continue
        # conservative direction only, and only on a razor-thin margin
        assert (b.decision, e.decision) == (FAIL, PASS), (
            gen, b.decision, e.decision)
        assert len(b.failed_tests) == 1, (gen, b.failed_tests)
        minp = min(p for _, p in run.results.values())
        per_tail = 0.01 / (2 * n)
        assert per_tail / 32 < minp < per_tail, (gen, minp, per_tail)
        assert max(le for _, le in e.log_evalues) < math.log(n / 0.01), gen
    assert decided_both == len(gens)
    # the canaries are crisp cases — engines must agree on them, and
    # agreement must hold on all but at most one marginal generator
    for gen in ("randu", "minstd"):
        assert evidence_verdict(res.runs[gen].results, n,
                                0.01).decision == FAIL, gen
    assert agreed >= len(gens) - 1, f"{agreed}/{len(gens)} agreed"


# ---------------------------------------------------- property tests

@settings(max_examples=40, deadline=None)
@given(ps=st.lists(st.floats(1e-9, 1.0 - 1e-9), min_size=1,
                   max_size=12),
       seed=st.integers(0, 2 ** 16))
def test_wealth_is_order_invariant(ps, seed):
    """E-value products commute: any data-independent ordering of the
    same results accumulates the same wealth (within float tolerance) —
    merging partial batteries in any order is sound."""
    import random as _random
    results = {i: (0.0, p) for i, p in enumerate(ps)}
    base = evidence_verdict(results, len(ps), 0.01)
    idx = list(results)
    _random.Random(seed).shuffle(idx)
    shuffled = {i: results[i] for i in idx}
    again = evidence_verdict(shuffled, len(ps), 0.01)
    assert again.log_wealth == pytest.approx(base.log_wealth, abs=1e-9)
    assert again.decision == base.decision


@settings(max_examples=40, deadline=None)
@given(a=st.lists(st.floats(-30.0, 30.0), max_size=8),
       b=st.lists(st.floats(-30.0, 30.0), max_size=8),
       c=st.lists(st.floats(-30.0, 30.0), max_size=8))
def test_combine_log_wealth_commutes_and_associates(a, b, c):
    """Product composition to battery/campaign level: merge is a plain
    sum in log space, so it must commute and associate."""
    assert combine_log_wealth(a + b) == pytest.approx(
        combine_log_wealth(b + a), abs=1e-9)
    left = combine_log_wealth([combine_log_wealth(a + b)] + c)
    right = combine_log_wealth(a + [combine_log_wealth(b + c)])
    assert left == pytest.approx(right, abs=1e-9)
    assert combine_log_wealth([]) == 0.0


@settings(max_examples=40, deadline=None)
@given(ps=st.lists(st.floats(1e-9, 1.0 - 1e-9), min_size=2,
                   max_size=12),
       k=st.integers(1, 12))
def test_wealth_invariant_to_data_independent_stopping(ps, k):
    """Stopping after k results (k chosen before seeing data) yields
    exactly the wealth of the first k e-values — no stopping rule can
    manufacture or destroy evidence (Ville validity's bookkeeping
    half)."""
    k = min(k, len(ps))
    n = len(ps)
    prefix = evidence_verdict({i: (0.0, ps[i]) for i in range(k)},
                              n, 0.01)
    expect = combine_log_wealth(
        [log_evalue(two_sided_p(p)) for p in ps[:k]])
    assert prefix.log_wealth == pytest.approx(expect, abs=1e-9)
    # a FAIL at the stop is a FAIL of every continuation (products of
    # later e-values can shrink wealth, but the CROSSING already bound
    # the error budget — the engine must keep it)
    if prefix.decision == FAIL:
        assert prefix.wealth >= 1.0 / 0.01


@settings(max_examples=25, deadline=None)
@given(codes=st.lists(st.sampled_from([0, 1, 2]), min_size=2,
                      max_size=12))
def test_ledger_roundtrip_preserves_wealth_and_decisions(
        tmp_path_factory, codes):
    """v3 ledger property: save/load is the identity on (decisions,
    log_wealth, engine, continuations) for arbitrary decision states —
    what makes continuation resume-safe. (``tmp_path_factory`` — a
    session-scoped fixture — keeps the real hypothesis's health check
    quiet.)"""
    from repro.core.api import CampaignLedger
    spec = CampaignSpec("smallcrush", ("splitmix64",),
                        n_streams=len(codes), seed=3,
                        waves=(SCALE,), verdict_engine="evalue")
    led = CampaignLedger.fresh(spec)
    led.decisions = np.asarray(codes, np.int8)
    led.log_wealth = np.linspace(-2.0, 5.0, len(codes))
    led.continuations = 1
    path = str(tmp_path_factory.mktemp("evledger") / "prop.ledger")
    led.save(path)
    back = CampaignLedger.load(path)
    assert back.version == 3 and back.engine == "evalue"
    assert back.continuations == 1
    np.testing.assert_array_equal(back.decisions, led.decisions)
    np.testing.assert_allclose(back.log_wealth, led.log_wealth)
    assert back.matches(spec)


# ------------------------------------------------- battery end to end

def test_evalue_battery_pass_and_wealth_history(session):
    spec = RunSpec("smallcrush", "splitmix64", 3, scale=SCALE,
                   verdict_engine="evalue")
    handle = session.submit(spec)
    res = handle.result()
    v = res.verdict
    assert isinstance(v, EvidenceVerdict)
    assert v.decision == PASS
    assert v.wealth < 1.0 / spec.alpha
    # one wealth sample per dispatched round, ending at the final wealth
    assert len(handle.wealth_history[0]) == res.rounds_run > 0
    assert handle.wealth_history[0][-1] == pytest.approx(v.wealth)


def test_evalue_adaptive_randu_stops_early(session):
    spec = RunSpec("smallcrush", "randu", 7, scale=SCALE,
                   policy="adaptive", stop_on_verdict=True,
                   verdict_engine="evalue")
    res = session.submit(spec).result()
    assert res.verdict.decision == FAIL
    assert res.rounds_run < res.plan_rounds     # pending rounds cancelled
    assert res.verdict.wealth >= 1.0 / spec.alpha


def test_evalue_checkpoint_v5_records_wealth(session, tmp_path):
    ck = str(tmp_path / "wealth.ck")
    spec = RunSpec("smallcrush", "splitmix64", 3, scale=SCALE,
                   checkpoint_path=ck, verdict_engine="evalue")
    res = session.submit(spec).result()
    saved = Checkpoint.load(ck)
    assert saved.version == 5 and saved.engine == "evalue"
    assert saved.log_wealth is not None and saved.log_wealth.shape == (1,)
    assert float(saved.log_wealth[0]) == pytest.approx(
        res.verdict.log_wealth)
    # resume with the same spec: nothing re-executes, verdict identical
    res2 = session.submit(spec).result()
    assert res2.rounds_run == 0
    assert res2.verdict.log_wealth == pytest.approx(
        res.verdict.log_wealth)


def test_resume_refuses_cross_engine_checkpoint(session, tmp_path):
    """Satellite gate: a Bonferroni stop_on_verdict checkpoint resumed
    under ``verdict_engine="evalue"`` is a typed refusal naming both
    engines and alphas — their decisions are not comparable."""
    ck = str(tmp_path / "engine.ck")
    spec = RunSpec("smallcrush", "splitmix64", 3, scale=SCALE,
                   policy="adaptive", stop_on_verdict=True,
                   checkpoint_path=ck)
    session.submit(spec).result()
    import dataclasses
    cross = dataclasses.replace(spec, verdict_engine="evalue")
    with pytest.raises(VerdictEngineMismatch) as exc:
        session.submit(cross)
    msg = str(exc.value)
    assert "'bonferroni'" in msg and "'evalue'" in msg
    assert "alpha=0.01" in msg
    assert issubclass(VerdictEngineMismatch, ValueError)
    # the same checkpoint under its own engine resumes cleanly: no jobs
    # re-execute (plan_rounds == 0), and the stop_on_verdict bookkeeping
    # adopts the checkpoint's sequential-look round count unchanged
    res = session.submit(spec).result()
    assert res.plan_rounds == 0
    assert res.rounds_run == 10
    assert res.verdict.decision == PASS


def test_tampered_checkpoint_error_names_engine_and_alphas(session,
                                                           tmp_path):
    """Satellite 4: the verdict cross-check's error must carry the
    engine name and BOTH alphas (checkpoint's and spec's) so a
    different-spec resume is diagnosable from the message alone."""
    ck = str(tmp_path / "tamper.ck")
    spec = RunSpec("smallcrush", "splitmix64", 3, scale=SCALE,
                   policy="adaptive", stop_on_verdict=True,
                   checkpoint_path=ck)
    session.submit(spec).result()
    leaves = ckpt_io.load_flat(ck)
    dec = np.asarray(leaves[4], np.int8).copy()
    dec[0] = 2                                  # flip PASS -> FAIL code
    ckpt_io.save(ck, leaves[:4] + [dec] + leaves[5:])
    with pytest.raises(ValueError) as exc:
        session.submit(spec)
    msg = str(exc.value)
    assert "engine 'bonferroni'" in msg
    assert "checkpoint alpha=0.01" in msg and "at alpha=0.01" in msg


# ------------------------------------------------ campaign continuation

def _continuation_spec(tmp_path, name="cont"):
    return CampaignSpec(
        "smallcrush", ("splitmix64", "pcg32"), n_streams=2, seed=11,
        waves=(SCALE,), stream_check=False, verdict_engine="evalue",
        continue_band=1e-4, max_continuations=1,
        ledger_path=str(tmp_path / f"{name}.ledger"))


def test_campaign_borderline_cells_reopen_next_wave(session, tmp_path):
    """ISSUE acceptance: a borderline cell (wealth within the band of
    1/alpha at the last wave) is re-opened in a ``continue1`` phase on
    fresh stream words instead of force-decided; the continuation
    budget then force-decides it."""
    camp = Campaign(session, _continuation_spec(tmp_path))
    assert [p.name for p in camp.phases()] == ["x0.0625"]
    res = camp.run()
    assert res.continuations == 1
    assert res.phase_names == ["x0.0625", "continue1"]
    assert "continue1" in res.phase_names
    assert len(res.survivors) + len(res.knockouts) == len(res.cells)
    assert res.log_wealth is not None and res.wealth is not None
    assert res.log_wealth.shape == (4,)


def test_campaign_continuation_never_flips_decided_cells(session,
                                                         tmp_path):
    """Satellite 2's campaign property, end to end: any cell decided
    BEFORE the continuation keeps its decision (and its decided_phase)
    after the continuation runs."""
    camp = Campaign(session, _continuation_spec(tmp_path, "flip"))
    assert camp.run_next_phase()                # wave completes
    pre = camp.ledger.decisions.copy()
    pre_phase = camp.ledger.decided_phase.copy()
    decided = pre != 0
    assert decided.any()                        # at least one decided cell
    while camp.run_next_phase():
        pass
    post = camp.ledger.decisions
    np.testing.assert_array_equal(post[decided], pre[decided])
    np.testing.assert_array_equal(
        camp.ledger.decided_phase[decided], pre_phase[decided])
    assert (post != 0).all()                    # and the rest got decided


def test_campaign_continuation_resume_is_bitwise(session, tmp_path):
    """ISSUE acceptance: a campaign stopped mid-continuation resumes
    from the v3 ledger bitwise — the resumed run replays 0 completed
    rounds and lands on identical decisions and wealth."""
    spec = _continuation_spec(tmp_path, "resume")
    camp = Campaign(session, spec)
    res1 = camp.run()
    assert res1.continuations == 1
    # a fresh Campaign over the finished ledger replays nothing
    again = Campaign(session, spec)
    assert again.ledger.continuations == 1
    assert [p.name for p in again.phases()] == res1.phase_names
    assert again.complete
    res2 = again.run()
    assert res2.rounds_run == 0
    np.testing.assert_array_equal(res2.decisions, res1.decisions)
    np.testing.assert_array_equal(res2.log_wealth, res1.log_wealth)
    assert res2.continuations == res1.continuations == 1


def test_campaign_mid_wave_continuation_resume(session, tmp_path):
    """Mid-wave variant: kill the campaign right AFTER the ledger
    records the continuation opening, resume — the continuation phase
    list is reconstructed from the ledger (phases() is a pure function
    of (spec, ledger)) and completed phases replay 0 rounds."""
    spec = _continuation_spec(tmp_path, "midwave")
    camp = Campaign(session, spec)
    assert camp.run_next_phase()                # wave 0
    first_rounds = camp.rounds_run
    assert first_rounds > 0
    assert camp.run_next_phase()                # opens + runs continue1
    assert camp.ledger.continuations == 1
    resumed = Campaign(session, spec)
    assert [p.name for p in resumed.phases()] == ["x0.0625", "continue1"]
    res = resumed.run()
    assert res.rounds_run == 0                  # everything came from disk
    np.testing.assert_array_equal(res.decisions, camp.ledger.decisions)


def test_campaign_spec_validates_continuation_knobs():
    with pytest.raises(ValueError, match="continue_band"):
        CampaignSpec("smallcrush", ("splitmix64",), waves=(SCALE,),
                     verdict_engine="evalue", continue_band=1.5)
    with pytest.raises(ValueError, match="max_continuations"):
        CampaignSpec("smallcrush", ("splitmix64",), waves=(SCALE,),
                     verdict_engine="evalue", max_continuations=-1)
    with pytest.raises(KeyError, match="verdict engine"):
        CampaignSpec("smallcrush", ("splitmix64",), waves=(SCALE,),
                     verdict_engine="fisher")


def test_bonferroni_campaign_has_no_wealth(session, tmp_path):
    spec = CampaignSpec("smallcrush", ("splitmix64",), n_streams=1,
                        seed=5, waves=(SCALE,), stream_check=False,
                        ledger_path=str(tmp_path / "bon.ledger"))
    res = Campaign(session, spec).run()
    assert res.log_wealth is None and res.wealth is None
    assert res.continuations == 0
    assert "continue1" not in res.phase_names


# ------------------------------------------------------- serve layer

def test_cell_digest_engine_fold_is_backward_compatible():
    """A Bonferroni digest must be byte-identical to the historical
    (pre-engine) digest — cached fleets keep their history — while an
    e-value digest differs, so cached Bonferroni results can never
    answer e-value submissions."""
    from repro.serve.cache import cell_digest
    base = ("smallcrush", 0.0625, "splitmix64", 7, 0, 0.01, "reference")
    assert cell_digest(*base) == cell_digest(*base, engine="bonferroni")
    assert cell_digest(*base, engine="evalue") != cell_digest(*base)
    assert cell_digest(*base, engine="evalue") == cell_digest(
        *base, engine="evalue")


def test_spec_cells_fold_the_spec_engine():
    from repro.serve.queue import admission_key, spec_cells
    bon = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE)
    ev = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                 verdict_engine="evalue")
    assert spec_cells(bon)[0].digest != spec_cells(ev)[0].digest
    assert admission_key(bon) != admission_key(ev)


def test_cache_entry_v2_roundtrip_and_v1_read(tmp_path):
    from repro.serve.cache import CACHE_VERSION, CacheEntry
    assert CACHE_VERSION == 2
    results = {i: (1.0, 0.4) for i in range(10)}
    entry = CacheEntry.from_results(results, 10, 0.01, engine="evalue")
    assert entry.engine == "evalue"
    assert isinstance(entry.verdict(), EvidenceVerdict)
    path = str(tmp_path / "v2.ck")
    entry.save(path)
    leaves = ckpt_io.load_flat(path)
    assert len(leaves) == 9 and int(leaves[0]) == CACHE_VERSION
    back = CacheEntry.load(path)
    assert back.engine == "evalue" and back.version == 2
    assert back.decision == entry.decision == PASS
    # v1 read path: strip the engine leaf, rewrite version 1
    v1 = str(tmp_path / "v1.ck")
    ckpt_io.save(v1, [np.int64(1)] + leaves[1:8])
    old = CacheEntry.load(v1)
    assert old.version == 1 and old.engine == "bonferroni"
    assert not isinstance(old.verdict(), EvidenceVerdict)
    # malformed layouts stay refused
    bad = str(tmp_path / "bad.ck")
    ckpt_io.save(bad, leaves[:5])
    with pytest.raises(ValueError, match="leaves"):
        CacheEntry.load(bad)


def test_cached_bonferroni_never_answers_evalue_submission(tmp_path):
    """The whole point of folding the engine into the digest: fill the
    cache under one engine, resubmit the identical cell under the
    other — guaranteed miss."""
    from repro.serve.cache import CacheEntry, ResultCache
    from repro.serve.queue import spec_cells
    cache = ResultCache(str(tmp_path / "cache"))
    bon = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE)
    ev = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                 verdict_engine="evalue")
    results = {i: (1.0, 0.4) for i in range(10)}
    cache.put(spec_cells(bon)[0].digest,
              CacheEntry.from_results(results, 10, 0.01))
    assert cache.get(spec_cells(bon)[0].digest) is not None
    assert cache.get(spec_cells(ev)[0].digest) is None
    assert cache.hits == 1 and cache.misses == 1


def test_serve_ticket_verdicts_use_spec_engine(session):
    from repro.serve import SubmissionQueue
    queue = SubmissionQueue(session=session)
    spec = RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                   verdict_engine="evalue")
    t = queue.submit(spec)
    queue.drain()
    res = t.result()
    assert isinstance(res.verdict, EvidenceVerdict)
    assert res.verdict.decision == PASS
    # a repeat submission under the SAME engine is the O(1) cache path
    t2 = queue.submit(spec)
    assert t2.done and t2.cache_hits == 1
    assert isinstance(t2.result().verdict, EvidenceVerdict)
