"""Paper §11 main result: battery wall-time, sequential vs pool — plus the
session API's compile-cache win.

Paper numbers (for reference): BigCrush stock ~12 h -> parallel ~4 h ->
HTCondor pool ~10.7 min (644 s) on 40 cores. Here: sequential (1 worker,
stock-TestU01 model) vs a pool as wide as the process's devices allow, up
to 8 (``benchmarks/run.py`` gives a host without accelerators 8 CPU
devices). Everything runs in this one process, so on an accelerator the
process that times the pool is the one that holds the chips. Speedup
structure, not absolute times, is the reproduction target.

The session rows measure what the PoolSession compile cache buys: the
first submit pays trace+compile, the second submit (same battery/scale/
workers, DIFFERENT generator) reuses the jitted round program — generator
and seed are runtime arguments.
"""
from __future__ import annotations

import time


def run(rows):
    import jax

    from repro.core.api import PoolSession, RunSpec
    from repro.core.battery import build_battery
    from repro.core.pool import run_sequential
    from repro.rng.generators import GEN_IDS

    width = min(8, len(jax.devices()))
    pool = PoolSession(n_workers=width)
    for battery, scale in (("smallcrush", 0.125), ("crush", 0.0625),
                           ("bigcrush", 0.0625)):
        entries = build_battery(battery, scale)
        t0 = time.time()
        run_sequential(entries, 1, GEN_IDS["splitmix64"])[1].block_until_ready()
        seq = time.time() - t0
        t0 = time.time()
        pool.submit(RunSpec(battery, "splitmix64", 1, scale=scale,
                            policy="roundrobin")).result()
        par = time.time() - t0
        rows.append((f"battery_{battery}_sequential_1w", seq * 1e6,
                     f"tests={len(entries)}"))
        rows.append((f"battery_{battery}_pool_{width}w", par * 1e6,
                     f"speedup_structure={seq / max(par, 1e-9):.2f}x"
                     "(incl_compile)"))

    # compile-cache: second submit with a new generator must not re-trace
    session = PoolSession()
    t0 = time.time()
    session.submit(RunSpec("smallcrush", "splitmix64", 1,
                           scale=0.125)).result()
    cold = time.time() - t0
    t0 = time.time()
    session.submit(RunSpec("smallcrush", "pcg32", 1, scale=0.125)).result()
    warm = time.time() - t0
    rows.append(("battery_session_first_submit", cold * 1e6,
                 "trace+compile+run"))
    rows.append(("battery_session_cached_submit", warm * 1e6,
                 f"speedup={cold / max(warm, 1e-9):.2f}x_"
                 f"traces={session.total_traces}"))
