#!/usr/bin/env python3
"""On-chip smoke test of the battery path, at the paper's job: BigCrush
(106 tests, 9,961,472 words per generator) at ``scale=1.0``.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # the pool's workers axis over 4 chips

One chip runs, through the entry points a user calls:

  1. ``PoolSession.submit`` of bigcrush over (splitmix64, randu) with
     ``backend="auto"``: auto must resolve to ``accelerated``, the round
     program must hold the Pallas kernels (``tpu_custom_call``),
     splitmix64 must PASS, randu must FAIL, every p-value must be finite
     and in [0, 1];
  2. the same spec under ``backend="reference"``: every entry that ran a
     Pallas kernel must match its reference p-value (rtol 1e-5, atol 1e-7,
     as ``tests/test_backends.py``);
  3. three tickets through ``SubmissionQueue`` plus one resubmission,
     which must be served from the result cache.

``--chips 4`` runs only bigcrush over splitmix64 at ``workers=4`` and at
``workers=1``: the 4-wide mesh must span 4 distinct chips and the stitched
p-values of both widths must be bitwise equal.

Timings printed here are one-off smoke timings, not benchmark metrics.
The script refuses to run without a TPU (exit 1) or outside a checkout
(exit 2). The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SEED = 42
SCALE = 1.0
HERE = os.path.dirname(os.path.abspath(__file__))


def check(cond, msg):
    """Fail the smoke run (a raised error, so no result line is printed)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class CompileClock:
    """Sums JAX's own compile-phase durations and compilation-cache hits
    (``jax.monitoring``) between ``reset`` calls."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self, jax):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.secs = {v: 0.0 for v in self.EVENTS.values()}
        self.cache_hits = 0

    def _dur(self, event, duration, **_):
        if event in self.EVENTS:
            self.secs[self.EVENTS[event]] += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self):
        return (" ".join(f"{k}={v:.1f}s" for k, v in self.secs.items())
                + f" persistent_cache_hits={self.cache_hits}")


def run_timed(session, spec, clock, label):
    """Submit ``spec``, wait for the verdict, print the smoke timings."""
    clock.reset()
    t0 = time.perf_counter()
    res = session.submit(spec).result()
    wall = time.perf_counter() - t0
    print(f"[smoke timing, one-off] {label}: time_to_verdict={wall:.1f}s "
          f"{clock.line()}", flush=True)
    return res


def runs_of(res, spec):
    """``{generator: RunResult}`` for a single- or multi-generator result."""
    return getattr(res, "runs", None) or {spec.generators[0]: res}


def check_pvalues(runs, n_tests, label):
    import numpy as np
    for gen, run in runs.items():
        ps = np.array([run.results[i][1] for i in range(n_tests)])
        check(len(run.results) == n_tests, f"{label} {gen}: "
              f"{len(run.results)} of {n_tests} tests have results")
        check(np.isfinite(ps).all() and ((ps >= 0) & (ps <= 1)).all(),
              f"{label} {gen}: p-values not finite in [0, 1]: {ps}")


def one_chip(jax, clock):
    import numpy as np
    from repro.core.api import PoolSession, RunSpec
    from repro.core.battery import routing_report
    from repro.serve import SubmissionQueue
    from repro.stats import backends as B

    check(B.resolve("auto") == "accelerated",
          f"backend auto resolved to {B.resolve('auto')!r}")
    session = PoolSession(n_workers=1)
    gens = ("splitmix64", "randu")
    spec = RunSpec("bigcrush", gens, SEED, scale=SCALE, backend="auto")
    entries = session.entries(spec)
    check(len(entries) == 106, f"bigcrush has {len(entries)} tests")
    print(f"bigcrush scale={SCALE}: {len(entries)} tests, "
          f"{sum(e.n_words for e in entries)} words per generator")
    for line in routing_report(entries):
        print(f"  kernels {line}")

    # the compiled round program holds the Pallas kernels
    runner = session._runner(spec)
    row = np.zeros((1,), np.int32)
    hlo = runner.lower(row, np.full(len(gens), SEED, np.int32),
                       np.zeros(len(gens), np.int32)).as_text()
    n_custom = hlo.count("tpu_custom_call")
    print(f"round program: {n_custom} tpu_custom_call site(s)")
    check(n_custom > 0, "round program holds no Pallas kernel")

    # 1. accelerated (auto) battery: verdicts and p-value sanity
    acc = runs_of(run_timed(session, spec, clock, "bigcrush auto"), spec)
    check_pvalues(acc, len(entries), "auto")
    for gen, run in acc.items():
        print(f"verdict[{gen}]: {run.verdict}")
    check(acc["splitmix64"].verdict.decision == "PASS",
          "splitmix64 did not PASS")
    check(acc["randu"].verdict.decision == "FAIL", "randu did not FAIL")

    # 2. reference backend on the chip: parity of every Pallas entry
    ref_spec = RunSpec("bigcrush", gens, SEED, scale=SCALE,
                       backend="reference")
    ref = runs_of(run_timed(session, ref_spec, clock, "bigcrush reference"),
                  ref_spec)
    check_pvalues(ref, len(entries), "reference")
    worst, n_cmp = 0.0, 0
    for gen in gens:
        for e in entries:
            if e.impl != "accelerated":
                continue
            pa, pr = acc[gen].results[e.index][1], ref[gen].results[e.index][1]
            n_cmp += 1
            worst = max(worst, abs(pa - pr) / max(abs(pr), 1e-30))
            check(np.isclose(pa, pr, rtol=1e-5, atol=1e-7),
                  f"{gen} {e.name}: accelerated p={pa!r} reference p={pr!r}")
    print(f"parity: {n_cmp} Pallas entries match the reference "
          f"(worst relative p difference {worst:.3g})")

    # 3. the serve path: three tickets, then a cache-hit resubmission
    queue = SubmissionQueue(session=session)
    specs = [RunSpec("bigcrush", g, SEED, scale=SCALE, backend="auto")
             for g in ("splitmix64", "pcg32", "xorshift64s")]
    clock.reset()
    t0 = time.perf_counter()
    tickets = [queue.submit(s) for s in specs]
    queue.drain()
    print(f"[smoke timing, one-off] serve 3 tickets: "
          f"time_to_verdict={time.perf_counter() - t0:.1f}s {clock.line()}")
    for t, s in zip(tickets, specs):
        res = t.result()
        check(t.state == "done", f"ticket {t.id} ended {t.state!r}")
        check_pvalues({s.generators[0]: res}, len(entries), f"serve {t.id}")
        print(f"ticket {t.id} {s.generators[0]}: {t.state} {res.verdict}")
    before = queue.dispatch_rounds
    again = queue.submit(specs[0])
    queue.drain()
    again.result()
    print(f"resubmit {again.id}: {again.state} cache_hits={again.cache_hits}"
          f" dispatches_added={queue.dispatch_rounds - before}")
    check(again.state == "done" and again.cache_hits > 0
          and queue.dispatch_rounds == before,
          "resubmission was not served from the result cache")


def four_chips(jax, clock):
    from repro.core.api import PoolSession, RunSpec

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 chips, JAX sees "
          f"{len(jax.devices())}")
    spec = RunSpec("bigcrush", "splitmix64", SEED, scale=SCALE,
                   backend="auto")
    results = {}
    for w in (4, 1):
        session = PoolSession(n_workers=w)
        ids = {d.id for d in session.mesh.devices.flat}
        print(f"workers={w}: mesh over chips {sorted(ids)}")
        check(len(ids) == w, f"{w}-wide mesh spans {len(ids)} chip(s)")
        res = run_timed(session, spec, clock, f"bigcrush workers={w}")
        check_pvalues({"splitmix64": res}, 106, f"workers={w}")
        print(f"workers={w}: rounds={res.rounds_run} verdict={res.verdict}")
        results[w] = res.results
    same = results[4] == results[1]
    print(f"workers=4 vs workers=1: stitched (stat, p) bitwise equal: {same}")
    check(same, "workers=4 and workers=1 p-values differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.common.runtime import use_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({exc})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)} | jax "
          f"{jax.__version__} | compile cache {cache}", flush=True)
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(jax, clock)
    print(f"[smoke timing, one-off] total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
