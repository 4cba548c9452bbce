"""Campaign screening vs the naive per-cell loop (DESIGN.md §8).

Three numbers matter:

  * DISPATCHES — a campaign wave carries all surviving cells on the
    mapped cell axis, so a G x S grid pays one plan's round dispatches
    where the naive loop pays one plan PER CELL (the paper's batch-model
    metric: ceil(K/W) batches, here further divided by the grid size);
  * KNOCKOUT — cells failed by the cheap phases (seam check, screening
    wave) never reach the expensive confirmation wave;
  * wall clock — honest caveat: the cell axis is a ``lax.map``, so
    its cells run one after another inside a dispatch and batched wall
    ~= per-cell wall (plus the seam phase the naive loop doesn't run);
    the dispatch-count ratio is what turns into wall clock where host
    round trips dominate, which is why ``wave_makespan``'s model ratio
    is reported alongside.

Both strategies get a fresh PoolSession and full compile-cache sharing
(one trace serves every cell either way) — the measured gap isolates
dispatch batching and knockout, not re-tracing.
"""
from __future__ import annotations

import time

GENS = ("splitmix64", "threefry", "pcg32", "randu")
N_STREAMS = 2
SCALE = 0.0625


def run(rows):
    from repro.core import Campaign, CampaignSpec, PoolSession, RunSpec
    from repro.core.scheduler import wave_makespan

    # batched: one campaign over the grid — seam check, screening wave,
    # confirmation wave (randu's cells are knocked out before the last)
    session = PoolSession()
    spec = CampaignSpec("smallcrush", GENS, n_streams=N_STREAMS, seed=5,
                        waves=(SCALE, SCALE))
    t0 = time.time()
    res = Campaign(session, spec).run()
    t_campaign = time.time() - t0
    n_cells = spec.n_cells

    # naive: one single-generator submit per cell per wave (same
    # session-level compile sharing, same sub-stream offsets)
    from repro.core.campaign import default_span
    span = default_span(spec)
    naive = PoolSession()
    t0 = time.time()
    percell_rounds = 0
    for _wave in range(2):
        for gen in GENS:
            for s in range(N_STREAMS):
                r = naive.submit(RunSpec("smallcrush", gen, 5, scale=SCALE,
                                         offsets=(s * span,))).result()
                percell_rounds += r.rounds_run
    t_percell = time.time() - t0

    from repro.core.battery import build_battery
    costs = [e.cost for e in build_battery("smallcrush", SCALE)]
    est_batched, est_percell = wave_makespan(costs, session.n_workers,
                                             n_cells)
    rows.append(("campaign_batched_4x2x2waves", t_campaign * 1e6,
                 f"dispatches={res.rounds_run}_"
                 f"phases={len(res.phase_names)}_"
                 f"traces={session.total_traces}_"
                 f"knockouts={len(res.knockouts)}"))
    rows.append(("campaign_percell_4x2x2waves", t_percell * 1e6,
                 f"dispatches={percell_rounds}_"
                 f"dispatch_ratio={percell_rounds / max(res.rounds_run, 1):.1f}x_"
                 f"wall_ratio={t_percell / max(t_campaign, 1e-9):.2f}x_"
                 f"model={est_percell / max(est_batched, 1e-9):.0f}x"))
    assert len(res.knockouts) >= N_STREAMS      # randu cells never survive
    assert session.total_traces <= len(res.phase_names)
    assert res.rounds_run < percell_rounds      # batching reduces dispatches
