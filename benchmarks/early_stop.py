"""Adaptive early stopping: rounds-executed and wall-clock, bad vs good
generators, at smallcrush and crush scales.

Ryabko's observation (arXiv:2001.11838) applied to the paper's pool:
ordering cheap, historically-discriminating tests first and stopping at
the first definitive verdict means a bad generator costs a handful of
rounds instead of a whole battery. Rows report rounds-to-verdict for the
adaptive early-stopping run vs the rounds a full battery executes, plus
the wall-clock of each. A final row sweeps EVERY registered generator at
crush scale (one multi-generator fan-out dispatch per round, failed
generators dropping out of the mapped axis) and checks the early-stopped
verdict agrees with the full-battery verdict for each.
"""
from __future__ import annotations

import time


def _one(session, RunSpec, battery, scale, gen, stop):
    spec = RunSpec(battery, gen, 9, scale=scale, policy="adaptive",
                   stop_on_verdict=stop)
    t0 = time.time()
    res = session.submit(spec).result()
    return res, time.time() - t0


def run(rows):
    from repro.core.api import PoolSession, RunSpec
    from repro.rng.generators import GENERATORS

    session = PoolSession()
    for battery, scale in (("smallcrush", 0.125), ("crush", 0.0625)):
        for gen in ("randu", "minstd", "splitmix64"):
            full, t_full = _one(session, RunSpec, battery, scale, gen, False)
            earl, t_earl = _one(session, RunSpec, battery, scale, gen, True)
            assert earl.verdict.decision == full.verdict.decision, \
                (gen, earl.verdict, full.verdict)
            if gen in ("randu", "minstd"):
                assert earl.verdict.decision == "FAIL", earl.verdict
                assert earl.rounds_run <= full.rounds_run // 2, \
                    (gen, earl.rounds_run, full.rounds_run)
            rows.append((
                f"early_stop_{battery}_{gen}", t_earl * 1e6,
                f"rounds={earl.rounds_run}/{full.rounds_run}_"
                f"verdict={earl.verdict.decision}_"
                f"full_wall={t_full:.2f}s"))

    # every generator, one fan-out: early-stopped == full-battery verdict
    gens = tuple(GENERATORS)
    full, t_full = _one(session, RunSpec, "crush", 0.0625, gens, False)
    earl, t_earl = _one(session, RunSpec, "crush", 0.0625, gens, True)
    match = sum(earl.verdicts[g].decision == full.verdicts[g].decision
                for g in gens)
    assert match == len(gens), {
        g: (earl.verdicts[g].decision, full.verdicts[g].decision)
        for g in gens}
    fails = sorted(g for g in gens if earl.verdicts[g].decision == "FAIL")
    rows.append((
        "early_stop_crush_all_gens_fanout", t_earl * 1e6,
        f"verdict_match={match}/{len(gens)}_fails={'+'.join(fails)}_"
        f"full_wall={t_full:.2f}s"))
