"""Pool scheduler — the paper's batch model behind a policy registry.

The paper observed: K independent tests on W workers finish in ceil(K/W)
batches, each batch costing ~t_max (§11: 106 tests / 40 cores -> 3 batches
~= 11 min; 70 cores -> 2; 90 cores -> still 2). ``roundrobin`` reproduces
exactly that placement; ``lpt`` and ``over_decompose`` are the beyond-paper
schedulers. The actual placement algorithms now live in
``repro.core.policies`` as registered ``SchedulePolicy`` objects — this
module keeps the classic functional surface (``make_plan``/``replan``)
as a thin delegate for callers that think in mode strings.
"""
from __future__ import annotations

from typing import Sequence, Union

import numpy as np

from repro.core.policies import (  # noqa: F401  (re-exported for compat)
    Plan,
    SchedulePolicy,
    get_policy,
)


def make_plan(costs: Sequence[float], n_workers: int,
              mode: Union[str, SchedulePolicy] = "roundrobin",
              entries: Sequence = None) -> Plan:
    """Plan via the registered policy. When ``entries`` (the battery job
    table) is given and the policy defines ``plan_entries`` — the adaptive
    policy ranks by the entries' kernel discrimination, not just cost —
    the richer form is preferred; every other policy sees only costs."""
    policy = get_policy(mode)
    plan_entries = getattr(policy, "plan_entries", None)
    if entries is not None and plan_entries is not None:
        return plan_entries(entries, n_workers)
    return policy.plan(costs, n_workers)


def wave_schedule(scales: Sequence[float]) -> list:
    """Campaign wave order (DESIGN.md §8): the given battery scales
    sorted ASCENDING, duplicates preserved. Screening cheapest-first
    maximizes the knockout value of early waves — every cell a cheap
    wave kills never pays for the expensive confirmation waves — the
    same philosophy the adaptive policy applies at round level
    (discrimination/cost priority, §3) lifted to the campaign grid."""
    out = sorted(float(s) for s in scales)
    if not out:
        raise ValueError("a campaign needs at least one wave scale")
    if any(s <= 0 for s in out):
        raise ValueError(f"wave scales must be positive, got {out}")
    return out


def wave_makespan(costs: Sequence[float], n_workers: int, n_cells: int,
                  mode: Union[str, SchedulePolicy] = "lpt") -> tuple:
    """``(batched, per_cell)`` estimated makespans of one campaign wave
    over ``n_cells`` grid cells. Batched is the campaign's model — one
    plan whose round dispatches carry every cell on the mapped cell
    axis, so the schedule is paid once; per-cell is the naive loop it
    replaces (the plan dispatched once per cell). The ratio is the
    batching win the campaign benchmark measures."""
    plan = make_plan(costs, n_workers, mode)
    return plan.est_makespan, plan.est_makespan * max(int(n_cells), 1)


def replan(missing: Sequence[int], costs: Sequence[float],
           n_workers: int, mode: Union[str, SchedulePolicy] = "lpt",
           entries: Sequence = None) -> Plan:
    """Plan covering only `missing` job indices (hold/release retry rounds,
    elastic re-meshing after worker loss, and adaptive resumes — the
    priority order is recomputed over just the still-missing entries).

    An empty ``missing`` set (every job already done when a resize
    triggers a replan) yields a zero-round plan — the run just completes,
    instead of the old ``ValueError: max() arg is an empty sequence``
    from the empty residual job table downstream."""
    missing = list(missing)
    if not missing:
        return Plan(np.zeros((0, n_workers), np.int32),
                    get_policy(mode).name, 0.0, 0.0)
    sub_entries = ([entries[i] for i in missing]
                   if entries is not None else None)
    sub = make_plan([costs[i] for i in missing], n_workers, mode,
                    entries=sub_entries)
    remap = np.asarray(list(missing) + [-1], np.int32)
    a = remap[np.where(sub.assignment >= 0, sub.assignment, len(missing))]
    return Plan(a.astype(np.int32), sub.mode, sub.est_makespan,
                sub.est_ideal)
