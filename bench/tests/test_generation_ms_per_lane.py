"""``generation_ms_per_lane`` (``bench/metrics/generation_ms_per_lane.py``):
one bucket conditional per lane per round on the recorded chip traces,
the device time of ``repro.gen`` on the trace recorded with scopes, a
hand-built fan-out round with a known answer, and nothing to read where
the block has no such conditional."""
import os

import pytest

from bench import run, spans
from bench.spans import Spans
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"hbm_bytes_per_s": 819e9}
METRIC = "generation_ms_per_lane"
LANES = run._load_module("metrics", METRIC, run.BENCH).lanes


def _ctx(trace):
    return run.Context(trace, {}, PEAKS, len(trace.devices))


def _cond(name, n, a, b):
    return (name, a, b, f"%{name} = (u32[{n}]{{0:T(1024)}}) conditional("
                        f"s32[] %clamp, (s32[], s32[]) %tuple)")


@pytest.mark.parametrize("name", ["bigcrush_s16_v5e.json.gz",
                                  "bigcrush_s16_w4_v5e.json.gz",
                                  "smallcrush_s1_v5e.json.gz"])
def test_one_bucket_conditional_per_job_per_poll(name):
    """Each recorded round runs one job per device and one lane, so each
    ``poll`` holds one outermost ``u32[N]`` conditional per device."""
    tr = Trace.from_json(os.path.join(DATA, name))
    polls = tr.spans("poll")
    for d in tr.devices:
        found = LANES(tr, d)
        assert [sum(1 for a, b, _ in found if pa <= a and b <= pb)
                for pa, pb in polls] == [1] * len(polls), d
        assert all(0 < ns <= b - a for a, b, ns in found)
    value = run.load_reader(METRIC)(_ctx(tr))
    assert 0 < value < 1.0


def test_agrees_with_the_generation_scope():
    """On the SmallCrush block recorded with the program's scopes, the
    lanes' total is the device time under ``repro.gen``."""
    sp = Spans.from_json(os.path.join(DATA, "smallcrush_s1_spans_v5e.json.gz"))
    ids = sorted(sp.trace.devices)
    found = LANES(sp.trace, ids[0])
    total_ms = sum(ns for _, _, ns in found) / 1e6
    scoped_ms = spans.generation_ms_per_round(sp, ids) * len(sp.rounds())
    assert total_ms == pytest.approx(scoped_ms, rel=0.05)
    assert run.load_reader(METRIC)(_ctx(sp.trace)) == pytest.approx(
        total_ms / len(found))


def test_a_fan_out_round_on_two_devices():
    """Two lanes a round on each device: the generator switch inside the
    bucket switch is not counted again, and a lane's time is the ops
    inside its conditional, not the conditional's own."""
    host = [("traced", 0, 1000), ("poll", 0, 900)]
    dev0 = [("while", 5, 800, "%while = (s32[]) while(...)"),
            _cond("conditional.10", 64, 10, 110),
            _cond("conditional.3", 32, 40, 90),
            ("fusion.1", 45, 85, "%fusion.1 = u32[32] fusion()"),
            ("pad.1", 90, 100, "%pad.1 = u32[64] pad()"),
            ("k", 120, 300, "%k = f32[] fusion()"),
            _cond("conditional.10", 64, 310, 400),
            _cond("conditional.5", 64, 330, 370),
            ("fusion.2", 335, 365, "%fusion.2 = u32[64] fusion()")]
    dev1 = [_cond("conditional.10", 64, 20, 80),
            ("fusion.3", 30, 50, "%fusion.3 = u32[64] fusion()"),
            _cond("conditional.10", 64, 500, 600),
            ("fusion.4", 510, 530, "%fusion.4 = u32[64] fusion()")]
    tr = Trace(host, {0: dev0, 1: dev1})
    assert LANES(tr, 0) == [(10, 110, 60), (310, 400, 40)]
    assert LANES(tr, 1) == [(20, 80, 20), (500, 600, 20)]
    assert run.load_reader(METRIC)(_ctx(tr)) == pytest.approx(140 / 4 / 1e6)


def test_nothing_to_read_without_a_bucket_conditional():
    host = [("traced", 0, 100), ("poll", 0, 90)]
    tr = Trace(host, {0: [("fusion", 10, 20, "%fusion = u32[64] fusion()"),
                          ("c", 30, 40, "%c = (f32[], f32[]) conditional()"),
                          ("x", 50, 60, "")]})
    assert LANES(tr, 0) == []
    assert run.load_reader(METRIC)(_ctx(tr)) is None
