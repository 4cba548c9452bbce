"""Hot-path benchmark (ISSUE 4) — the repo's perf trajectory starts here.

Measures the three legs of the Pallas-backed battery hot path and writes
``BENCH_4.json`` (the CI ``bench-hotpath`` job uploads it as an artifact):

  kernels     per-family µs, reference vs accelerated (interpret mode on
              CPU — correctness-level numbers; real-TPU perf is
              structural)
  blocks      generated-words/read-words ratio per battery, bucketed vs
              the old battery-wide-max blocks (acceptance: smallcrush
              bucketed <= 1.25)
  generators  jump-ahead vs scan block timing for the former lax.scan
              generators, plus a bit-exactness check
  rounds      fixed-seed smallcrush sequential pass, reference vs
              accelerated backend, with verdict-identity recorded

Also exposes ``run(rows)`` for the ``benchmarks/run.py`` CSV contract.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _t(fn, *a, reps=3):
    import jax
    jax.block_until_ready(fn(*a))
    t0 = time.time()
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def collect() -> dict:
    import jax
    import numpy as np

    from repro.common.compat import x64
    from repro.core import pool
    from repro.core.battery import build_battery
    from repro.core.pool import run_sequential
    from repro.rng import generators as G
    from repro.stats import backends as B

    report = {"jax_backend": jax.default_backend()}

    # -- per-kernel µs: reference vs accelerated (interpret) ---------------
    cases = {
        "gap": dict(n=16384), "poker": dict(n=4096),
        "weight": dict(n=16384), "serial2d": dict(n=8192, d=32),
        "collision": dict(n=8192, kbits=14), "rank": dict(n_mats=512),
    }
    with x64():
        bits = G.splitmix64_block(1, 1, 262144)
    kernels = {}
    for fam, kw in cases.items():
        ref = jax.jit(lambda b, f=B.route(fam, "reference")[0],
                      k=kw: f(b, **k))
        acc = jax.jit(lambda b, f=B.route(fam, "accelerated", **kw)[0],
                      k=kw: f(b, **k))
        kernels[fam] = {"params": kw,
                        "reference_us": round(_t(ref, bits), 1),
                        "accelerated_us": round(_t(acc, bits), 1)}
    report["kernels"] = kernels

    # -- bucketed bit blocks: generated/read ratio -------------------------
    blocks = {}
    for battery in ("smallcrush", "crush", "bigcrush"):
        entries = build_battery(battery, 1.0)
        read = pool.read_words(entries)
        blocks[battery] = {
            "read_words": read,
            "generated_words_bucketed": pool.generated_words(entries),
            "bucketed": round(pool.block_ratio(entries), 4),
            # pre-bucketing hot path: every slot generated max_words
            "batterywide_max": round(
                len(entries) * max(e.n_words for e in entries) / read, 4),
        }
    report["block_ratio"] = blocks

    # -- jump-ahead generators vs their scan twins -------------------------
    def gen_jit(fn):
        @jax.jit
        def block(seed):
            with x64():
                return fn(seed, 1, n)
        return block

    gens = {"bitexact": {}, "us": {}}
    n = 65536
    for name, scan in G.SCAN_REFERENCE.items():
        jump = G.GENERATORS[name]
        # seed is a RUNTIME argument — with everything static XLA
        # constant-folds the whole block and the timing is fiction
        jj, ss = gen_jit(jump), gen_jit(scan)
        gens["bitexact"][name] = bool(
            (np.asarray(jj(3)) == np.asarray(ss(3))).all())
        gens["us"][name] = {"jump": round(_t(jj, 3), 1),
                            "scan": round(_t(ss, 3), 1)}
    report["generators"] = gens

    # -- smallcrush round time, reference vs accelerated -------------------
    rounds = {}
    suspects = {}
    pvals = {}
    for backend in ("reference", "accelerated"):
        entries = build_battery("smallcrush", 0.125, backend=backend)
        stats, ps = run_sequential(entries, 3, G.GEN_IDS["pcg32"])
        t0 = time.time()
        stats, ps = run_sequential(entries, 3, G.GEN_IDS["pcg32"])
        jax.block_until_ready(ps)
        rounds[backend] = round((time.time() - t0) * 1e6, 1)
        pvals[backend] = np.asarray(ps)
        mask = (pvals[backend] < 1e-4) | (pvals[backend] > 1 - 1e-4)
        suspects[backend] = int(mask.sum())
    report["smallcrush_round_us"] = rounds
    report["smallcrush_suspects"] = suspects
    # PER-TEST agreement, not suspect-count coincidence: the backends
    # must produce the same p-value for every test
    report["verdict_identical"] = bool(np.allclose(
        pvals["reference"], pvals["accelerated"], rtol=1e-5, atol=1e-7))
    return report


def run(rows) -> None:
    """benchmarks/run.py CSV contract: name,us_per_call,derived."""
    rep = collect()
    rows.append(("hotpath_block_ratio_smallcrush", 0.0,
                 f"bucketed={rep['block_ratio']['smallcrush']['bucketed']}"
                 f"_was={rep['block_ratio']['smallcrush']['batterywide_max']}"))
    for fam, d in rep["kernels"].items():
        rows.append((f"hotpath_{fam}_ref", d["reference_us"], ""))
        rows.append((f"hotpath_{fam}_accel", d["accelerated_us"],
                     "interpret"))
    for gen, d in rep["generators"]["us"].items():
        rows.append((f"hotpath_gen_{gen}_jump", d["jump"],
                     f"bitexact={rep['generators']['bitexact'][gen]}"))
        rows.append((f"hotpath_gen_{gen}_scan", d["scan"], ""))
    for backend, us in rep["smallcrush_round_us"].items():
        rows.append((f"hotpath_smallcrush_{backend}", us,
                     f"suspects={rep['smallcrush_suspects'][backend]}"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", dest="json_path", default="BENCH_4.json")
    args = ap.parse_args()
    rep = collect()
    os.makedirs(os.path.dirname(args.json_path) or ".", exist_ok=True)
    with open(args.json_path, "w") as f:
        json.dump(rep, f, indent=2)
    print(f"hotpath report -> {args.json_path}")
    ratio = rep["block_ratio"]["smallcrush"]["bucketed"]
    print(f"smallcrush generated/read: {ratio} "
          f"(was {rep['block_ratio']['smallcrush']['batterywide_max']})")
    assert ratio <= 1.25, f"bucketed ratio {ratio} > 1.25"
    assert all(rep["generators"]["bitexact"].values()), \
        f"jump != scan: {rep['generators']['bitexact']}"
    assert rep["verdict_identical"], rep["smallcrush_suspects"]


if __name__ == "__main__":
    main()
