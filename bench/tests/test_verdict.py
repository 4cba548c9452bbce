"""The verdict check: the program's verdict against the reference's rule
applied to the program's own stitched p-values (BigCrush's 106 tests at
alpha 0.01: a test fails outside [4.717e-5, 1 - 4.717e-5])."""
import pytest

from bench import run

N, ALPHA = 106, 0.01


def _lane(ps, decision, failed):
    results = {t: (0.0, p) for t, p in enumerate(ps)}
    return run.Lane("splitmix64", 1, results, decision, tuple(failed))


def _mismatch(ps, decision, failed):
    return run.verdict_mismatch(_lane(ps, decision, failed), N, ALPHA)


BASE = [0.5] * N


def test_pass_and_fail_by_the_rule_are_sound():
    assert _mismatch(BASE, "PASS", ()) == 0
    near = list(BASE)
    near[39] = 0.99996         # past 1 - 4.717e-5: the lane's own p decides
    assert _mismatch(near, "FAIL", (39,)) == 0


def test_a_fail_frozen_at_its_first_crossing_is_sound():
    ps = list(BASE)
    ps[39] = ps[75] = 1e-7
    assert _mismatch(ps, "FAIL", (39,)) == 0
    assert _mismatch(ps, "FAIL", (39, 75)) == 0


@pytest.mark.parametrize("decision,failed,ps_at", [
    ("PASS", (), {39: 1e-7}),              # a crossing not acted on
    ("FAIL", (3,), {}),                    # a FAIL with no crossing
    ("FAIL", (3,), {39: 1e-7}),            # names a test in range
    ("FAIL", (), {39: 1e-7}),              # a FAIL that names nothing
    ("UNDECIDED", (), {}),                 # every test is in
])
def test_a_verdict_that_breaks_the_rule_is_counted(decision, failed, ps_at):
    ps = list(BASE)
    for t, p in ps_at.items():
        ps[t] = p
    assert _mismatch(ps, decision, failed) == 1


def test_missing_results_leave_the_battery_undecided():
    lane = _lane(BASE[:N - 1], "PASS", ())
    assert run.verdict_mismatch(lane, N, ALPHA) == 1
    lane = _lane(BASE[:N - 1], "UNDECIDED", ())
    assert run.verdict_mismatch(lane, N, ALPHA) == 0
