"""Faults planted under the timed path, for ``test_faults.py``: each
patches the program (or puts the control in its place) and returns a
function that undoes it. Also runs as a script: for the four-worker
faults, which need a process whose CPU backend has four devices,

    python3 -m bench.tests.faults <root> <bench> <cell> <fault>

and, on the chip, to read every fault a cell can have at the cell's own
size, one short window each, in one process:

    python3 -m bench.tests.faults --chip <cell> <seed> <seconds> <fault>...
"""
import json
import sys

import numpy as np


def _wrap_runner(alter):
    from repro.core.api import PoolSession
    orig = PoolSession._runner

    def patched(self, spec, *a, **k):
        fn = orig(self, spec, *a, **k)

        def wrapped(row, *args):
            st, ps = fn(row, *args)
            return alter(np.asarray(row), np.array(st), np.array(ps))
        return wrapped
    PoolSession._runner = patched
    return lambda: setattr(PoolSession, "_runner", orig)


def altered_answer():
    """Test 3's p-value is altered where the round produces it."""
    def alter(row, st, ps):
        ps[..., row == 3] = (ps[..., row == 3] + 0.25) % 1.0
        return st, ps
    return _wrap_runner(alter)


def altered_lane():
    """Test 3's p-value is altered in the last lane only, where the
    round produces it."""
    def alter(row, st, ps):
        ps[-1, ..., row == 3] = (ps[-1, ..., row == 3] + 0.25) % 1.0
        return st, ps
    return _wrap_runner(alter)


def exchange_left_out():
    """Every worker's slot carries worker 0's result: the results of the
    other chips never reach the host."""
    def alter(row, st, ps):
        st[..., 1:] = st[..., :1]
        ps[..., 1:] = ps[..., :1]
        return st, ps
    return _wrap_runner(alter)


def half_left_out():
    """Half of a request's tests are left out of the stitched result, and
    the verdict is taken over the rest."""
    from repro.core.api import BatteryRun
    orig = BatteryRun.results_by_position

    def half(self):
        return [{t: v for t, v in r.items() if t % 2 == 0}
                for r in orig(self)]
    BatteryRun.results_by_position = half
    return lambda: setattr(BatteryRun, "results_by_position", orig)


def stale_answer():
    """Every request returns the first request's result: the state the
    answer comes from is never moved on."""
    from repro.core.api import BatteryRun
    orig = BatteryRun.result
    first = []

    def result(self):
        res = orig(self)
        if not first:
            first.append(res)
        return first[0]
    BatteryRun.result = result
    return lambda: setattr(BatteryRun, "result", orig)


def altered_verdict():
    """The verdict is turned over after stitching: a PASS becomes a FAIL
    of test 0, a FAIL a PASS; the stitched results stay as they are."""
    import types

    from repro.core.api import BatteryRun
    orig = BatteryRun.result

    def result(self):
        res = orig(self)
        runs = getattr(res, "runs", None) or {self.spec.generators[0]: res}
        turned = {}
        for gen, r in runs.items():
            fail = r.verdict.decision != "FAIL"
            turned[gen] = types.SimpleNamespace(
                results=r.results, verdict=types.SimpleNamespace(
                    decision="FAIL" if fail else "PASS",
                    failed_tests=(0,) if fail else ()))
        return types.SimpleNamespace(runs=turned, rounds_run=res.rounds_run,
                                     retries=res.retries)
    BatteryRun.result = result
    return lambda: setattr(BatteryRun, "result", orig)


def control():
    """The control in the program's place: the plain reference computed
    in bfloat16."""
    import types

    import ml_dtypes

    from bench import reference
    from repro.core.api import BatteryRun
    orig = BatteryRun.result

    def result(self):
        orig(self)
        spec = self.spec
        table = reference.battery(spec.battery, spec.scale)
        runs = {}
        for gen, seed in zip(spec.generators, spec.seeds):
            res = reference.run_request(table, gen, seed, ml_dtypes.bfloat16)
            dec, failed = reference.verdict(res, len(table), spec.alpha)
            runs[gen] = types.SimpleNamespace(
                results=res, verdict=types.SimpleNamespace(
                    decision=dec, failed_tests=failed))
        return types.SimpleNamespace(runs=runs, rounds_run=self.rounds_run,
                                     retries=self.retries)
    BatteryRun.result = result
    return lambda: setattr(BatteryRun, "result", orig)


FAULTS = {"altered_answer": altered_answer, "altered_lane": altered_lane,
          "exchange_left_out": exchange_left_out,
          "half_left_out": half_left_out, "stale_answer": stale_answer,
          "altered_verdict": altered_verdict, "control": control}


def run_with(fault, root, bench, cell, seed=11, seconds=1.0):
    """One run of ``cell`` with ``fault`` planted (``None``: sound)."""
    from bench import run
    sys.path.insert(0, root + "/src")
    undo = FAULTS[fault]() if fault else (lambda: None)
    try:
        return run.run_cell(cell, seed, seconds, False, bench, root,
                            need_accelerator=False, log=lambda m: None)
    finally:
        undo()


def on_chip(cell, seed, seconds, faults):
    """Each of ``faults`` (``none``: a sound run) planted in turn under a
    short window of ``cell`` at its own size on the chip, one result
    line each."""
    from bench import run
    sys.path.insert(0, run.ROOT + "/src")
    for k, fault in enumerate(faults):
        undo = FAULTS[fault]() if fault != "none" else (lambda: None)
        try:
            out = run.run_cell(cell, seed + k, seconds, False,
                               log=lambda m: None)
        finally:
            undo()
        print(json.dumps({"fault": fault, "seed": seed + k,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "--chip":
        on_chip(sys.argv[2], int(sys.argv[3]), float(sys.argv[4]),
                sys.argv[5:])
    else:
        root, bench, cell, fault = sys.argv[1:5]
        out = run_with(None if fault == "none" else fault, root, bench,
                       cell)
        print(json.dumps({"correct": out["correct"],
                          "checks": out["checks"]}))
