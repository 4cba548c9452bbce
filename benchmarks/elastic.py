"""Elastic re-meshing churn: BigCrush with the pool width bouncing
W -> W/2 -> W mid-run (W = the process's devices, up to 8; the paper's
opportunistic condor pool — machines vacate when their owner returns and
rejoin later) vs the same battery on a fixed W-wide pool, in this one
process.

Two numbers matter: the wall-clock cost of churn (the narrow stretch
runs at half throughput and the resize recompiles one extra round
program), and the accuracy criterion — the stitched p-values of the
churned run must be BITWISE those of the fixed-width run, because job
identity (generator sub-streams) never depends on pool width.
"""
from __future__ import annotations

import time


def _battery(session, resize_at=()):
    """Run bigcrush, resizing after the given (round, width) points."""
    from repro.core.api import RunSpec
    t0 = time.time()
    handle = session.submit(RunSpec("bigcrush", "splitmix64", 42,
                                    scale=0.0625))
    for rnd, width in resize_at:
        while handle.pending_rounds and handle.rounds_run < rnd:
            handle.poll()
        if handle.pending_rounds:
            session.resize(width)
    res = handle.result()
    return time.time() - t0, res


def run(rows):
    import jax

    from repro.core.api import PoolSession

    w = min(8, len(jax.devices()))
    if w < 2:
        rows.append(("elastic_bigcrush_skipped", -1.0,
                     f"needs_2_devices_have_{w}"))
        return
    t_fixed, fixed = _battery(PoolSession(n_workers=w))
    t_churn, churn = _battery(PoolSession(n_workers=w),
                              resize_at=((3, w // 2), (6, w)))
    bitwise = fixed.results == churn.results
    rows.append((f"elastic_bigcrush_fixed_{w}w", t_fixed * 1e6,
                 f"rounds={fixed.rounds_run}"))
    rows.append((f"elastic_bigcrush_churn_{w}_{w // 2}_{w}", t_churn * 1e6,
                 f"rounds={churn.rounds_run}_"
                 f"churn_cost={t_churn / max(t_fixed, 1e-9):.2f}x_"
                 f"bitwise_equal={bitwise}"))
    assert bitwise, "churned run must stitch bitwise-identical p-values"
