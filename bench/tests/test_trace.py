"""The reduction from a trace to the per-layer metrics: on a hand-built
trace with known answers, and on a small trace recorded on the chip."""
import glob
import os

import pytest

from bench import run
from bench.trace import Trace, self_times, union

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"hbm_bytes_per_s": 819e9}


def _ctx(trace, n_workers):
    return run.Context(trace, {"trace": 1.0, "lower": 2.0, "compile": 3.0},
                       PEAKS, n_workers)


def _read(name, ctx):
    return run.load_reader(name)(ctx)


@pytest.fixture
def hand():
    host = [("traced", 0, 1000), ("poll", 0, 400), ("poll", 500, 900),
            ("stitch", 900, 950)]
    devices = {0: [("a", 10, 100, ""), ("b", 50, 150, ""),
                   ("c", 600, 700, "")],
               1: [("d", 0, 300, ""), ("e", 600, 800, ""),
                   ("late", 1200, 1300, "")]}
    return _ctx(Trace(host, devices), 2)


def test_busy_is_the_union_of_op_intervals(hand):
    assert hand.busy(0).total() == 240
    assert hand.busy(1).total() == 500          # clipped to the window
    assert hand.busy_s() == pytest.approx(370e-9)
    assert hand.window_s() == pytest.approx(1e-6)


def test_idle_share_and_per_round(hand):
    assert _read("device_idle_share", hand) == pytest.approx(63.0)
    assert _read("device_ms_per_round", hand) == pytest.approx(370e-9 * 1e3
                                                               / 2)
    # busy on any device: [0, 300] and [600, 800]
    assert _read("host_ms_per_round", hand) == pytest.approx(150e-6)
    assert _read("stitch_verdict_ms", hand) == pytest.approx(50e-6)


def test_straggler_is_slowest_minus_mean_per_round(hand):
    # round 1: 140 and 300 ns busy; round 2: 100 and 200 ns
    assert _read("straggler_ms_per_round", hand) == pytest.approx(
        ((300 - 220) + (200 - 150)) / 2 / 1e6)
    one = _ctx(Trace(hand.trace.host, {0: hand.trace.devices[0]}), 1)
    assert _read("straggler_ms_per_round", one) is None


def test_set_up_readers():
    ctx = _ctx(Trace([("traced", 0, 1)], {0: []}), 1)
    assert _read("setup_trace_lower_s", ctx) == 3.0
    assert _read("setup_compile_s", ctx) == 3.0


def test_coupon_rounds_are_timed_outside_the_block():
    trace = Trace([("traced", 0, 1)], {0: []})
    rounds = [(frozenset({"coupon", "gap"}), 0.9), (frozenset({"gap"}), 0.1),
              (frozenset({"coupon"}), 1.1), (frozenset(), 0.2)]
    ctx = run.Context(trace, {}, PEAKS, 1, rounds)
    assert _read("coupon_round_ms", ctx) == pytest.approx(1000.0)
    assert _read("coupon_round_ms", _ctx(trace, 1)) is None


def test_profiler_block_follows_the_serial_rounds():
    """A request of four rounds whose second holds 2^18 serial steps: the
    block starts after that round in the second request, and ends before
    the third serial round would take the steps it holds past 2^19."""
    prof = run.Profiler(None)
    try:
        prof.plan([0, 1 << 18, 0, 0])
        assert prof.block == [6, 17]
    finally:
        prof.load()


def test_union_covered_and_self_time():
    u = union([(0, 10), (5, 20), (30, 40)])
    assert u.total() == 30
    assert u.covered(8, 35) == 17
    assert u.gaps(0, 50) == [(20, 30), (40, 50)]
    st = self_times([("loop", 0, 100, ""), ("body", 10, 30, ""),
                     ("next", 150, 160, "")], 0, 200)
    assert st == {"loop": 80, "body": 20, "next": 10}


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "*.json.gz"))))
def test_recorded_trace(path):
    """A few rounds recorded on a TPU v5e through ``--trace 1``: every
    reader gives a number in range, and busy never exceeds the window."""
    tr = Trace.from_json(path)
    ctx = _ctx(tr, len(tr.devices))
    assert 0 < ctx.busy_s() <= ctx.window_s()
    idle = _read("device_idle_share", ctx)
    assert 0 <= idle < 100
    assert _read("device_ms_per_round", ctx) > 0
    assert _read("host_ms_per_round", ctx) >= 0
    straggler = _read("straggler_ms_per_round", ctx)
    assert (straggler is None) == (len(tr.devices) == 1)
    assert straggler is None or straggler >= 0
    for kernel in ("histogram_roofline", "gf2_rank_roofline"):
        share = _read(kernel, ctx)
        assert share is None or 0 < share <= 100
    br = ctx.breakdown()
    assert 0 < len(br["device_ops"]) <= 10
    assert sum(v for _, v in br["idle_gaps"]) == pytest.approx(
        ctx.window_s() - ctx.busy_s(), rel=1e-6)
