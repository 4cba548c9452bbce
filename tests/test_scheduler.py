"""Property tests for the pool scheduler (the paper's batch model)."""
import math

import numpy as np
from hypothesis import example, given, settings, strategies as st

from repro.core.scheduler import make_plan, replan


@given(k=st.integers(1, 300), w=st.integers(1, 128))
@settings(max_examples=60, deadline=None)
def test_roundrobin_matches_paper_batch_model(k, w):
    """ceil(K/W) batches — the paper's §11 performance model."""
    plan = make_plan([1.0] * k, w, "roundrobin")
    assert plan.rounds == math.ceil(k / w)


@given(k=st.integers(1, 200), w=st.integers(1, 64),
       seed=st.integers(0, 10))
@settings(max_examples=60, deadline=None)
def test_every_test_scheduled_exactly_once(k, w, seed):
    rng = np.random.default_rng(seed)
    costs = rng.uniform(0.1, 10.0, k)
    for mode in ("roundrobin", "lpt"):
        plan = make_plan(costs, w, mode)
        sched = sorted(int(i) for i in plan.assignment.ravel() if i >= 0)
        assert sched == list(range(k))


@given(k=st.integers(2, 150), w=st.integers(2, 48),
       seed=st.integers(0, 20))
@settings(max_examples=60, deadline=None)
def test_lpt_never_worse_than_roundrobin(k, w, seed):
    rng = np.random.default_rng(seed)
    costs = rng.lognormal(0, 1.5, k)        # skewed, like TestU01's tests
    rr = make_plan(costs, w, "roundrobin")
    lpt = make_plan(costs, w, "lpt")
    assert lpt.est_makespan <= rr.est_makespan + 1e-9
    # LPT's classic bound: makespan <= (4/3 - 1/3W) * OPT >= ideal
    assert lpt.est_makespan >= lpt.est_ideal - 1e-9


def test_paper_numbers_106_tests():
    """The paper's concrete claim: 106 tests on 40 cores -> 3 batches;
    70 -> 2; 90 -> still 2 (no improvement)."""
    for w, batches in ((40, 3), (70, 2), (90, 2)):
        plan = make_plan([1.0] * 106, w, "roundrobin")
        assert plan.rounds == batches


@given(w=st.integers(1, 32),
       missing=st.sets(st.integers(0, 49), min_size=1, max_size=20))
@settings(max_examples=40, deadline=None)
def test_replan_covers_exactly_missing(w, missing):
    plan = replan(sorted(missing), [1.0] * 50, w)
    covered = sorted(int(i) for i in plan.assignment.ravel() if i >= 0)
    assert covered == sorted(missing)


# ----------------------------------------------------------- policy registry

def test_make_plan_accepts_policy_instances():
    from repro.core.policies import LPTPolicy
    costs = [3.0, 1.0, 2.0]
    assert (make_plan(costs, 2, LPTPolicy()).assignment
            == make_plan(costs, 2, "lpt").assignment).all()


def test_make_plan_unknown_mode_raises():
    import pytest
    with pytest.raises(ValueError):
        make_plan([1.0], 1, "fifo")


@given(k=st.integers(2, 60), w=st.integers(2, 16), seed=st.integers(0, 10))
@example(k=4, w=2, seed=8)   # 256-word floor: 3 parts outgrew the test
@settings(max_examples=30, deadline=None)
def test_over_decompose_invariants(k, w, seed):
    """Decomposition shrinks the largest job, never adds cost, covers every
    test exactly once per part, and is independent of the worker count
    (checkpoint job indices must survive elastic re-meshing). Note the
    round-synchronous makespan estimate is NOT guaranteed monotone under
    splitting — LPT packing anomalies are real — so that is not asserted."""
    from repro.core.battery import TestEntry
    from repro.core.policies import OverDecomposePolicy

    rng = np.random.default_rng(seed)
    costs = rng.lognormal(0, 1.5, k)
    # synthetic entries: cost-only jobs the policy can split evenly
    entries = [TestEntry(i, f"t{i}", None, max(int(c * 1000), 8), float(c),
                         kname="weight",
                         params=(("n", max(int(c * 1000), 8)),))
               for i, c in enumerate(costs)]
    policy = OverDecomposePolicy(max_parts=8)
    jobs = policy.decompose(entries, w)
    if jobs is None:                     # nothing heavy enough to split
        return
    assert max(j.cost for j in jobs) <= max(e.cost for e in entries) + 1e-9
    assert sum(j.cost for j in jobs) <= sum(e.cost for e in entries) + 1e-6
    # every original test is covered by its group exactly once per part
    by_group = {}
    for j in jobs:
        by_group.setdefault(j.group, []).append(j.part)
    assert sorted(by_group) == list(range(k))
    for g, parts in by_group.items():
        assert sorted(parts) == list(range(len(parts)))
    # job table is a pure function of the battery, not the mesh width
    other = policy.decompose(entries, w + 7)
    assert [(j.index, j.group, j.part, j.cost) for j in jobs] == \
           [(j.index, j.group, j.part, j.cost) for j in other]
