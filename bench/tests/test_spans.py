"""The program's spans and device scopes in a trace (``bench/spans.py``):
the reductions on a hand-built trace with known answers, the reading of
op scopes from an ``.xplane.pb``, a small trace recorded on the chip,
the tool on the CPU, and the harness's readers, unchanged, on the traces
recorded before the program had spans."""
import gzip
import json
import os

import pytest

from bench import run, spans
from bench.spans import Spans
from bench.tests.helpers import tiny_tree
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "smallcrush_s1_spans_v5e.json.gz")
PEAKS = {"hbm_bytes_per_s": 819e9}
GEN, GAP, COUPON = "repro.gen", "repro.test.gap", "repro.test.coupon"


def _round(k, a, phases):
    out = [("repro.round", a, a + 380, {"run": 0, "round": k, "jobs": 1})]
    return out + [("repro.round." + p, a + s, a + e, {})
                  for p, s, e in phases]


@pytest.fixture
def hand():
    """Two rounds on two devices. Round 0 in [10, 390], its wait in [60,
    300]; round 1 in [510, 890], its wait in [560, 800], with a
    checkpoint. Device 0 runs a conditional holding a generation and a
    gap test, then a generation; device 1 a coupon test, then an op with
    no scope."""
    host = [("traced", 0, 1000), ("poll", 0, 400), ("poll", 500, 900),
            ("stitch", 900, 950)]
    program = (_round(0, 10, [("plan", 2, 10), ("plan", 10, 20),
                              ("launch", 20, 50), ("wait", 50, 290),
                              ("fold", 290, 310), ("verdict", 310, 330),
                              ("status", 330, 370)])
               + _round(1, 510, [("plan", 2, 10), ("plan", 10, 20),
                                 ("launch", 20, 50), ("wait", 50, 290),
                                 ("fold", 290, 320), ("verdict", 320, 340),
                                 ("checkpoint", 340, 350),
                                 ("status", 350, 370)])
               + [("repro.finalize", 900, 950, {"run": 0})])
    devices = {0: [("cond", 70, 250, ""), ("gen", 80, 120, ""),
                   ("k", 130, 240, ""), ("g2", 600, 700, "")],
               1: [("x", 65, 290, ""), ("y", 600, 780, "")]}
    scopes = {0: ["", GEN, GAP, GEN], 1: [COUPON, ""]}
    return Spans(Trace(host, devices), program, scopes)


def test_rounds_and_their_phases(hand):
    assert hand.rounds() == [(10, 390), (510, 890)]
    assert hand.children("wait") == [(60, 300), (560, 800)]
    assert hand.children("checkpoint") == [(850, 860)]


@pytest.mark.parametrize("name, ns", [
    ("generation_ms_per_round", (40 + 100) / 2 / 2),
    ("launch_ms_per_round", (30 + 30) / 2),
    ("fold_verdict_ms_per_round", (80 + 80) / 2),
    # idle in rounds less idle in wait: 480 - 200 and 355 - 75
    ("idle_host_ms_per_round", (280 + 280) / 2 / 2),
    # idle in wait: 60 + 140 on device 0, 15 + 60 on device 1
    ("idle_wait_ms_per_round", (200 + 75) / 2 / 2),
])
def test_quantities_on_a_hand_built_trace(hand, name, ns):
    assert spans.QUANTITIES[name](hand, [0, 1]) == pytest.approx(ns / 1e6)


def test_split_of_the_idle_time(hand):
    out = spans.split(hand, [0, 1])
    assert out["rounds"] == out["polls"] == 2
    assert out["idle_s"] == pytest.approx((720 + 595) / 2 * 1e-9)
    assert out["idle_host_s"] == pytest.approx(280e-9)
    assert out["idle_wait_s"] == pytest.approx(137.5e-9)
    assert out["idle_outside_rounds_s"] == pytest.approx(240e-9)
    assert out["idle_in_polls_s"] == pytest.approx(457.5e-9)
    assert out["polls_covered"] == pytest.approx(417.5 / 457.5)


def test_device_time_by_scope(hand):
    out = spans.layers(hand, [0, 1])
    assert out["by_scope"] == pytest.approx(
        {GEN: 70e-9, GAP: 55e-9, COUPON: 112.5e-9, "(none)": 105e-9})
    assert out["scoped_share"] == pytest.approx(1 - 105 / 342.5)
    assert out["unscoped_ops"] == [("y", pytest.approx(90e-9)),
                                   ("cond", pytest.approx(15e-9))]


def test_no_rounds_reads_nothing():
    sp = Spans(Trace([("traced", 0, 10)], {0: []}), [], {0: []})
    assert all(f(sp, [0]) is None for f in spans.QUANTITIES.values())


def test_head_keeps_the_first_polls(hand, tmp_path):
    head = hand.head(1)
    assert head.trace.window == (0, 400)
    assert head.rounds() == [(10, 390)]
    assert head.trace.devices == {0: hand.trace.devices[0][:3],
                                  1: hand.trace.devices[1][:1]}
    assert head.scopes == {0: ["", GEN, GAP], 1: [COUPON]}
    path = str(tmp_path / "head.json.gz")
    head.to_json(path)
    back = Spans.from_json(path)
    assert back.program == head.program and back.scopes == head.scopes
    assert Trace.from_json(path).host == [tuple(s) for s in head.trace.host]


@pytest.mark.parametrize("path, scope", [
    ("jit(round_fn)/cond/branch_1_fun/repro.gen/cond/shift_right_logical:",
     GEN),
    ("jit(round_fn)/repro.test.rank/jit(gf2_rank)/pallas_call:",
     "repro.test.rank"),
    ("jit(round_fn)/cond/branch_0_fun/dynamic_slice:", ""),
    ("", ""),
])
def test_scope_of_an_op_path(path, scope):
    assert spans.scope_of(path) == scope


def _varint(v):
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        if not v:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(field, v):
    return _varint(field << 3) + _varint(v)


def _msg(field, body):
    body = body.encode() if isinstance(body, str) else body
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def test_op_paths_read_the_event_metadata(tmp_path):
    """An ``XSpace`` written by hand: the ``tf_op`` stat of a device
    plane's event metadata is each op's path, and where it is missing
    the metadata plane's HLO proto gives the instruction's ``op_name``;
    a host plane, a stat of another name and the lines are passed
    over."""
    def event(i, name, stats):
        return _msg(4, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name)
                                         + b"".join(stats)))

    def stat_md(i, name):
        return _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name)))

    def stat(i, field, value):
        return _msg(5, _int(1, i) + _msg(field, value))

    gen = "%fusion.1 = u32[8] fusion()"
    loop = "%while.3 = (s32[]) while()"
    device = (_msg(2, "/device:TPU:0") + _msg(3, b"\x08\x01" * 50)
              + stat_md(7, "tf_op") + stat_md(8, "source")
              + event(1, gen, [stat(7, 5, "f/repro.gen/x:"),
                               stat(8, 5, "a.py:1")])
              + event(2, "%copy.2 = u32[8] copy()",
                      [stat(8, 5, "f/repro.gen/y:")])
              + event(3, loop, []))
    ins = _msg(1, "while.3") + _msg(7, _msg(2, "f/repro.test.coupon/while"))
    proto = _msg(1, _msg(1, "jit_f") + _msg(3, _msg(1, "main")
                                            + _msg(2, ins)))
    meta = (_msg(2, "/host:metadata") + stat_md(3, "Hlo Proto")
            + event(1, "jit_f(1)", [stat(3, 6, proto)]))
    host = (_msg(2, "/host:CPU") + stat_md(7, "tf_op")
            + event(1, "python", [stat(7, 5, "z")]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg(1, device) + _msg(1, meta) + _msg(1, host)
                     + _msg(4, "hostname"))
    assert spans.op_paths(str(path)) == {0: {
        gen: "f/repro.gen/x:", "%copy.2 = u32[8] copy()": "",
        loop: "f/repro.test.coupon/while"}}


def test_recorded_chip_trace():
    """Four SmallCrush rounds recorded on a TPU v5e by ``bench/spans.py
    --record``: every round has its phases, the device time is mostly
    scoped (the job switch's conditionals are not), and the idle split
    adds up."""
    sp = Spans.from_json(RECORDED)
    ids = sorted(sp.trace.devices)
    assert len(sp.rounds()) == len(sp.trace.spans("poll")) == 4
    assert [a["round"] for n, _, _, a in sp.program
            if n == "repro.round"] == sorted(
        a["round"] for n, _, _, a in sp.program if n == "repro.round")
    for phase in ("launch", "wait", "fold", "verdict", "status"):
        assert len(sp.children(phase)) == 4
    assert not sp.children("checkpoint")
    for name, f in spans.QUANTITIES.items():
        assert f(sp, ids) > 0, name
    out = spans.split(sp, ids)
    assert out["idle_host_s"] + out["idle_wait_s"] + out[
        "idle_outside_rounds_s"] == pytest.approx(out["idle_s"])
    assert 0.95 <= out["polls_covered"] <= 1
    scoped = spans.layers(sp, ids)
    assert scoped["scoped_share"] > 0.8     # the rest: the job switch
    assert {GEN, "repro.test.serial2d"} <= set(scoped["by_scope"])
    assert "conditional" in scoped["unscoped_ops"][0][0]
    ctx = run.Context(sp.trace, {}, PEAKS, len(ids))
    assert 0 < ctx.busy_s() <= ctx.window_s()


# The harness's readers on the three traces recorded before the program
# had spans, as the parent commit read them: nothing here may move them.
PARENT = {
    "bigcrush_s16_v5e.json.gz": {
        "device_idle_share": 55.119300291605036,
        "device_ms_per_round": 2.3318783333333335,
        "host_ms_per_round": 2.8521845,
        "histogram_roofline": 0.7210204199406313},
    "bigcrush_s16_w4_v5e.json.gz": {
        "device_idle_share": 61.711286573040766,
        "device_ms_per_round": 7.950480958333333,
        "host_ms_per_round": 4.862544,
        "straggler_ms_per_round": 7.938191875,
        "histogram_roofline": 0.1539204578608163,
        "gf2_rank_roofline": 15.719620253385376},
    "smallcrush_s1_v5e.json.gz": {
        "device_idle_share": 89.37540108073956,
        "device_ms_per_round": 0.30380633333333334,
        "host_ms_per_round": 2.5466235,
        "histogram_roofline": 0.3500808788608293},
}
READERS = ("device_idle_share", "device_ms_per_round", "host_ms_per_round",
           "stitch_verdict_ms", "straggler_ms_per_round",
           "histogram_roofline", "gf2_rank_roofline")


@pytest.mark.parametrize("name", sorted(PARENT))
def test_readers_read_the_old_traces_as_before(name):
    tr = Trace.from_json(os.path.join(DATA, name))
    ctx = run.Context(tr, {}, PEAKS, len(tr.devices))
    got = {r: run.load_reader(r)(ctx) for r in READERS}
    assert {r: v for r, v in got.items() if v is not None} == pytest.approx(
        PARENT[name], rel=1e-12)


def test_the_recorded_files_keep_the_trace_keys():
    for name in sorted(os.listdir(DATA)):
        with gzip.open(os.path.join(DATA, name), "rt") as f:
            assert {"host", "devices"} <= set(json.load(f)), name


def test_the_tool_runs_a_cell_on_the_cpu(tmp_path):
    """The command's path at a tiny size: SmallCrush at scale 1/16 with
    its traced block, the quantities read, the head recorded."""
    root, bench, cell = tiny_tree(tmp_path)
    path = str(tmp_path / "head.json.gz")
    out = spans.run_traced(cell, 5, 4.0, bench, root, need_accelerator=False,
                           record=path, record_rounds=3)
    assert "error" not in out, out
    assert out["split"]["rounds"] == out["split"]["polls"] > 0
    assert all(v is not None and v >= 0 for v in out["program"].values())
    cost = out["span_cost_us_per_round"]
    assert 0 < cost["off"] < 1000 and 0 < cost["on"] < 10000
    head = Spans.from_json(path)
    assert len(head.rounds()) == 3
