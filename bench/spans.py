"""The program's own spans and device scopes in a profiler trace.

The program under test marks its layers in the profiler's trace
(``src/repro/common/trace.py``):

- host spans: ``repro.round`` (one ``BatteryRun.poll`` that dispatches a
  round; args ``run``, ``round``, ``jobs``) and, nested in it,
  ``repro.round.plan``, ``.launch``, ``.wait``, ``.fold``, ``.verdict``,
  ``.checkpoint`` and ``.status``; ``repro.finalize`` around the stitch;
- device scopes: ``repro.gen`` (generation) and ``repro.test.<family>``
  (a test kernel), in each device op's HLO ``op_name`` metadata.

``Trace`` (``bench/trace.py``) keeps the harness's spans and the device
ops; ``Spans`` keeps it and adds what the program marks:

- ``program``: every ``repro.*`` host event as ``(name, start, end,
  args)``, on the profiler's one clock (nanoseconds);
- ``scopes``: per device, the ``repro.*`` scope of each op of
  ``trace.devices[d]``, in the same order ("" for an op with none).

A device op's scope is in its event's metadata, which
``jax.profiler.ProfileData`` does not show: on a TPU the ``tf_op`` stat
of the op's ``XEventMetadata`` holds its ``op_name`` path
(``jit(round_fn)/cond/branch_1_fun/repro.gen/shift_right_logical:``).
``op_paths`` reads those from the ``.xplane.pb`` itself (the protobuf
wire format of ``XSpace``, stdlib only). A CPU trace has no such stat,
so its ops have no scope.

The functions below reduce them to the idle split of a round and the
device time by layer, over the traced block and the pool's devices.

As a command it runs one cell's traced block as ``bench/run.py --trace
1`` does (same set-up, window, driver and ``Profiler``; no correctness
check), keeps the profile, and prints one JSON line: the cell's
per-layer metrics as the harness reads them, the quantities below, and
the cost of a round's spans with the profiler off and on:

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--record <path> --record-rounds <k>]

``--record`` writes the first ``k`` rounds of the block as a small
recorded trace (``Spans.to_json``) for the tests.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.trace import Trace, self_times  # noqa: E402

PREFIX = "repro."
ROUND = "repro.round"
FOLD_VERDICT = ("fold", "verdict", "checkpoint", "status")
ARGS = ("run", "round", "jobs")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_HLO_NAME = re.compile(r"^%([^\s=]+) = ")


def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message:
    an int for a varint, the bytes (a memoryview) otherwise."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire in (1, 2, 5):
            n = {1: 8, 5: 4}.get(wire)
            if n is None:
                n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {pos}")
        yield key >> 3, val


def _strings(buf, *path: int):
    """The string fields at ``path`` (field numbers, outermost first)
    inside a protobuf message."""
    for field, val in _fields(buf):
        if field == path[0]:
            if len(path) == 1:
                yield bytes(val).decode()
            else:
                yield from _strings(val, *path[1:])


def _hlo_op_names(proto) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over an ``HloProto``: module (1)
    -> computations (3) -> instructions (2) -> name (1) and metadata (7)
    -> op_name (2)."""
    out = {}
    for module in (v for f, v in _fields(proto) if f == 1):
        for comp in (v for f, v in _fields(module) if f == 3):
            for ins in (v for f, v in _fields(comp) if f == 2):
                name = next(_strings(ins, 1), "")
                out[name] = next(_strings(ins, 7, 2), "")
    return out


def op_paths(xplane_path: str) -> Dict[int, Dict[str, str]]:
    """Per accelerator, each op's event name (its HLO text) -> its
    ``op_name`` path. The ``tf_op`` stat of the op's event metadata
    gives it; for the ops it leaves out (loops, conditionals) the
    ``Hlo Proto`` stats of the metadata plane give the instruction's
    ``op_name``. In ``XSpace``: planes (1) -> ``XPlane`` name (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5) map entries ->
    ``XEventMetadata`` name (2) and stats (5) -> ``XStat`` metadata id
    (1) and string (5) or bytes (6) value. The planes' lines are skipped
    unread."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    planes = []                             # (name, events, stat names)
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, val in _fields(plane):
            if field == 2:
                name = bytes(val).decode()
            elif field == 4:
                meta = dict(_fields(val)).get(2, b"")
                text, stats = next(_strings(meta, 2), ""), {}
                for st in (v for f, v in _fields(meta) if f == 5):
                    st = dict(_fields(st))
                    stats[st.get(1)] = st.get(5, st.get(6))
                events.append((text, stats))
            elif field == 5:
                entry = dict(_fields(val))
                stat_names[entry.get(1, 0)] = next(
                    _strings(entry.get(2, b""), 2), "")
        planes.append((name, events, stat_names))
    hlo = {}
    for name, events, stat_names in planes:
        for _, stats in events:
            for k, v in stats.items():
                if stat_names.get(k) == "Hlo Proto" and v is not None:
                    hlo.update(_hlo_op_names(v))
    out = {}
    for name, events, stat_names in planes:
        m = _DEVICE_PLANE.match(name)
        if not m:
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        paths = out.setdefault(int(m.group(2)), {})
        for text, stats in events:
            path = next((bytes(v).decode() for k, v in stats.items()
                         if k in tf_op and v is not None), "")
            op = _HLO_NAME.match(text)
            paths[text] = path or hlo.get(op.group(1) if op else text, "")
    return out


def scope_of(text: str) -> str:
    """The innermost ``repro.*`` component of an op's name path
    (``jit(f)/repro.test.coupon/while/body/add`` -> ``repro.test.coupon``),
    or ""."""
    found = [c for c in str(text).split("/") if c.startswith(PREFIX)]
    return found[-1] if found else ""


@dataclasses.dataclass
class Spans:
    trace: Trace
    program: List[Tuple[str, int, int, dict]]
    scopes: Dict[int, List[str]]
    _busy: dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)

    # -- construction ------------------------------------------------------

    @classmethod
    def load(cls, xplane_path: str) -> "Spans":
        """Read a profiler ``.xplane.pb``: ``Trace.load`` for the
        harness's spans and the device ops, the ``repro.*`` events of the
        host planes, and each device op's scope from ``op_paths``."""
        from jax.profiler import ProfileData
        trace = Trace.load(xplane_path)
        program = []
        for plane in ProfileData.from_file(xplane_path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        program.append((ev.name, int(ev.start_ns),
                                        int(ev.end_ns),
                                        {k: int(v) for k, v in ev.stats
                                         if k in ARGS}))
        program.sort(key=lambda e: e[1])
        paths = op_paths(xplane_path)
        scopes = {d: [scope_of(paths.get(d, {}).get(hlo or name, ""))
                      for name, _, _, hlo in ops]
                  for d, ops in trace.devices.items()}
        return cls(trace, program, scopes)

    def to_json(self, path: str) -> None:
        """The recorded form: ``Trace.to_json``'s keys, which
        ``Trace.from_json`` reads, and ``program`` and ``scopes``."""
        with gzip.open(path, "wt") as f:
            json.dump({"host": self.trace.host,
                       "devices": {str(k): v
                                   for k, v in self.trace.devices.items()},
                       "program": self.program,
                       "scopes": {str(k): v
                                  for k, v in self.scopes.items()}}, f)

    @classmethod
    def from_json(cls, path: str) -> "Spans":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(Trace.from_json(path),
                   [(n, a, b, dict(args)) for n, a, b, args in d["program"]],
                   {int(k): list(v) for k, v in d["scopes"].items()})

    def head(self, n_polls: int) -> "Spans":
        """The first ``n_polls`` harness polls of the traced block as a
        block of their own: the ``traced`` span cut to them, and the
        host spans, program events and device ops within it."""
        lo, _ = self.trace.window
        polls = [s for s in self.trace.spans("poll") if s[0] >= lo]
        hi = polls[:n_polls][-1][1]
        host = [("traced", lo, hi)] + [
            s for s in self.trace.host
            if s[0] != "traced" and s[1] >= lo and s[2] <= hi]
        devices, scopes = {}, {}
        for d, ops in self.trace.devices.items():
            keep = [i for i, o in enumerate(ops) if o[2] > lo and o[1] < hi]
            devices[d] = [ops[i] for i in keep]
            scopes[d] = [self.scopes[d][i] for i in keep]
        program = [e for e in self.program if e[1] >= lo and e[2] <= hi]
        return Spans(Trace(host, devices), program, scopes)

    # -- reductions --------------------------------------------------------

    def rounds(self) -> List[Tuple[int, int]]:
        """The ``repro.round`` spans inside the traced window."""
        lo, hi = self.trace.window
        return [(a, b) for n, a, b, _ in self.program
                if n == ROUND and a >= lo and b <= hi]

    def children(self, phase: str) -> List[Tuple[int, int]]:
        """``repro.round.<phase>`` spans inside some round of the
        window."""
        rounds = self.rounds()
        name = f"{ROUND}.{phase}"
        return [(a, b) for n, a, b, _ in self.program
                if n == name and any(ra <= a and b <= rb
                                     for ra, rb in rounds)]

    def idle_ns(self, device: int, spans) -> int:
        """Nanoseconds of the spans in which ``device`` ran no op."""
        if device not in self._busy:
            self._busy[device] = self.trace.busy(device)
        busy = self._busy[device]
        return sum((b - a) - busy.covered(a, b) for a, b in spans)

    def self_ns(self, device: int) -> Dict[Tuple[str, str], int]:
        """Nanoseconds the device's ops ran in the window less the time
        of the ops nested in them (``trace.self_times``), by ``(scope, op
        text)``."""
        ops = self.trace.devices.get(device, [])
        scopes = self.scopes.get(device, [""] * len(ops))
        keyed = [(name, a, b, f"{scope}\n{hlo or name}")
                 for (name, a, b, hlo), scope in zip(ops, scopes)]
        return {tuple(k.split("\n", 1)): ns
                for k, ns in self_times(keyed, *self.trace.window).items()}


def _per_round_ms(total_ns: float, sp: Spans):
    n = len(sp.rounds())
    return total_ns / n / 1e6 if n else None


def generation_ms_per_round(sp: Spans, ids: List[int]):
    """Device self time of the ops under ``repro.gen``, mean over the
    pool's devices, per round."""
    ns = sum(t for d in ids for (s, _), t in sp.self_ns(d).items()
             if s == PREFIX + "gen")
    return _per_round_ms(ns / len(ids), sp)


def launch_ms_per_round(sp: Spans, ids: List[int]):
    """Host time in ``repro.round.launch`` per round."""
    return _per_round_ms(sum(b - a for a, b in sp.children("launch")), sp)


def fold_verdict_ms_per_round(sp: Spans, ids: List[int]):
    """Host time in ``repro.round.fold``, ``.verdict``, ``.checkpoint``
    and ``.status`` per round."""
    ns = sum(b - a for p in FOLD_VERDICT for a, b in sp.children(p))
    return _per_round_ms(ns, sp)


def idle_host_ms_per_round(sp: Spans, ids: List[int]):
    """Device idle time (mean over the pool's devices) inside
    ``repro.round`` but outside its ``wait``, per round: idle that the
    program's host work causes."""
    rounds, waits = sp.rounds(), sp.children("wait")
    ns = sum(sp.idle_ns(d, rounds) - sp.idle_ns(d, waits) for d in ids)
    return _per_round_ms(ns / len(ids), sp)


def idle_wait_ms_per_round(sp: Spans, ids: List[int]):
    """Device idle time (mean over the pool's devices) inside
    ``repro.round.wait``, per round: the device has the round and still
    sits idle."""
    waits = sp.children("wait")
    return _per_round_ms(sum(sp.idle_ns(d, waits) for d in ids) / len(ids),
                         sp)


QUANTITIES = {f.__name__: f for f in (
    generation_ms_per_round, launch_ms_per_round, fold_verdict_ms_per_round,
    idle_host_ms_per_round, idle_wait_ms_per_round)}


def split(sp: Spans, ids: List[int]) -> dict:
    """Where the block's device idle time lies, in seconds (mean over the
    pool's devices): in rounds outside ``wait``, in ``wait``, outside
    every round; the idle in the harness's ``poll`` spans, and the share
    of it that the first two cover."""
    lo, hi = sp.trace.window
    rounds, waits = sp.rounds(), sp.children("wait")
    polls = [(a, b) for a, b in sp.trace.spans("poll") if a >= lo and b <= hi]
    n = len(ids)
    idle = sum(sp.idle_ns(d, [(lo, hi)]) for d in ids) / n
    in_rounds = sum(sp.idle_ns(d, rounds) for d in ids) / n
    wait = sum(sp.idle_ns(d, waits) for d in ids) / n
    in_polls = sum(sp.idle_ns(d, polls) for d in ids) / n
    return {"rounds": len(rounds), "polls": len(polls),
            "idle_s": idle / 1e9, "idle_host_s": (in_rounds - wait) / 1e9,
            "idle_wait_s": wait / 1e9,
            "idle_outside_rounds_s": (idle - in_rounds) / 1e9,
            "idle_in_polls_s": in_polls / 1e9,
            "polls_covered": in_rounds / in_polls if in_polls else None}


def layers(sp: Spans, ids: List[int], top: int = 8) -> dict:
    """Device self time by scope in seconds (mean over the pool's
    devices), the scoped share of it, and the ops without a scope that
    took most."""
    by_scope, unscoped = collections.Counter(), collections.Counter()
    for d in ids:
        for (s, text), ns in sp.self_ns(d).items():
            by_scope[s or "(none)"] += ns / 1e9 / len(ids)
            if not s:
                unscoped[text[:120]] += ns / 1e9 / len(ids)
    total = sum(by_scope.values())
    return {"by_scope": dict(by_scope.most_common()),
            "scoped_share": 1 - by_scope["(none)"] / total if total else None,
            "unscoped_ops": unscoped.most_common(top)}


def span_cost_us(profiling: bool = False, n: int = 2000,
                 reps: int = 5) -> float:
    """Median microseconds of one round's spans (the round, two plan
    spans, launch, wait, fold, verdict, status and the ``jobs`` arg),
    with no profiler session running or, ``profiling``, under one."""
    import jax

    from repro.common.trace import span

    def one_round():
        with span("round", run=0, round=0) as r:
            with span("round.plan"):
                pass
            r.set_metadata(jobs=1)
            for phase in ("plan", "launch", "wait", "fold", "verdict",
                          "status"):
                with span("round." + phase):
                    pass

    def timed():
        times = []
        for _ in range(reps):
            t = time.perf_counter()
            for _ in range(n):
                one_round()
            times.append((time.perf_counter() - t) / n * 1e6)
        return statistics.median(times)

    if not profiling:
        return timed()
    where = tempfile.mkdtemp(prefix="span-cost-")
    try:
        with jax.profiler.trace(where):
            return timed()
    finally:
        shutil.rmtree(where, ignore_errors=True)


# ---------------------------------------------------------------------------
# the command


def run_traced(workload: str, seed: int, seconds: float,
               bench_dir: str = BENCH, root: str = ROOT,
               need_accelerator: bool = True, record: str = "",
               record_rounds: int = 4) -> dict:
    """One cell's window with its traced block, as ``bench/run.py
    --trace 1`` runs it, and what the block's trace says."""
    from bench import roofline, run
    cell = run.load_cell(workload, bench_dir, root)
    pool = run.open_pool(cell, seed, root, need_accelerator)
    prof = run.Profiler(pool.jax)
    w = run.Window(cell, pool, seed, seconds, prof)
    cell.driver.drive(w)
    prof.close()
    found = glob.glob(os.path.join(prof.dir, "**", "*.xplane.pb"),
                      recursive=True)
    try:
        sp = Spans.load(found[0]) if len(found) == 1 else None
    finally:
        shutil.rmtree(prof.dir, ignore_errors=True)
    out = {"workload": workload, "seed": seed, "polls": w.polls,
           "span_cost_us_per_round": {"off": span_cost_us(),
                                      "on": span_cost_us(profiling=True)}}
    if sp is None:
        out["error"] = "the window closed before the traced block began"
        return out
    kind = pool.devices[0].device_kind
    ctx = run.Context(sp.trace, pool.setup_clock,
                      roofline.peaks_for(kind) if need_accelerator else None,
                      cell.config["n_workers"], w.round_times())
    ids = ctx.device_ids()
    out["device"] = {"kind": kind, "count": len(pool.devices),
                     "busy_s": ctx.busy_s(), "window_s": ctx.window_s()}
    if need_accelerator:     # the rooflines need the device's peaks
        out["metrics"] = {m["name"]: run.load_reader(m["name"], bench_dir)(
            ctx) for m in cell.per_layer}
    out["program"] = {name: f(sp, ids) for name, f in QUANTITIES.items()}
    out["split"] = split(sp, ids)
    out["layers"] = layers(sp, ids)
    if record:
        sp.head(record_rounds).to_json(record)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default="")
    ap.add_argument("--record-rounds", type=int, default=4)
    args = ap.parse_args(argv)
    out = run_traced(args.workload, args.seed, args.seconds,
                     record=args.record, record_rounds=args.record_rounds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
