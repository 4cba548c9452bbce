"""Screening-as-a-service: the persistent async submission queue.

The paper's usage model is "submit from your desk, poll while the pool
works" — the user's machine is freed the moment ``condor_submit``
returns. ``SubmissionQueue`` is that model as a long-lived daemon: ONE
``PoolSession`` (one device mesh, one compile cache) serving many
concurrent clients, each of whom submits a ``RunSpec`` or
``CampaignSpec`` and gets back a ``Ticket`` with the familiar
HTCondor-shaped verbs (``poll``/``held``/``release``/``result``) that
never block the daemon loop. Three mechanisms make the repeat-heavy,
many-client screening workload cheap (DESIGN.md §10):

  admission batching   pending specs that agree on
                       (battery, scale, alpha, backend, policy,
                       stop_on_verdict) are coalesced into ONE merged
                       multi-generator spec — strangers share a round on
                       the mapped gen_ids axis, results are demuxed
                       back per ticket (``stitch.demux_positions``). A
                       ``max_wait`` bound keeps admission fair: a lone
                       submission is admitted once it has waited that
                       long, batched or not.
  result cache         every cell (generator, seed, offset, battery,
                       scale, alpha, backend) is content-addressed
                       (``serve.cache``); a repeat submission anywhere
                       in the fleet returns its memoized verdict in
                       O(1) with ZERO dispatches.
  crash recovery       a batch checkpoints under a content-derived name
                       in ``state_dir`` (the v3 layout), and the cache
                       persists there too — a restarted daemon that
                       receives the same submissions re-forms the same
                       batch and resumes its rounds instead of
                       re-executing them; campaign tickets resume from
                       their own ledger exactly as ``Campaign`` does.

The daemon loop is cooperative (``step()`` does one unit of work:
resolve cache hits, admit due groups, advance every active batch by one
round / every campaign by one phase) and can be driven either inline
(``drain()``, or a ``Ticket.result()`` call) or from the background
thread ``start()`` spawns — submissions are thread-safe either way.

Typical use::

    queue = SubmissionQueue(state_dir="serve-state")
    t1 = queue.submit(RunSpec("smallcrush", "splitmix64", seeds=(7,)))
    t2 = queue.submit(RunSpec("smallcrush", "pcg32", seeds=(7,)))
    queue.drain()                       # ONE shared dispatch per round
    print(t1.result().report)
    t3 = queue.submit(RunSpec("smallcrush", "splitmix64", seeds=(7,)))
    queue.drain()                       # cache hit: zero dispatches
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from typing import Dict, List, Optional, Union

from repro.core import stitch
from repro.core.api import (BatteryResult, CampaignSpec, PoolSession,
                            RunResult, RunSpec)
from repro.core.campaign import Campaign
from repro.core.faults import FaultPlan
from repro.core.policies import RetryBudgetExhausted, get_policy
from repro.serve.cache import CacheEntry, ResultCache, cell_digest
from repro.stats import backends as kernel_backends

# ticket lifecycle states (DESIGN.md §10; FAILED is §12's graceful-
# degradation terminal — budget exhaustion resolves tickets, never hangs)
QUEUED, RUNNING, DONE, CANCELLED, FAILED = (
    "queued", "running", "done", "cancelled", "failed")


@dataclasses.dataclass(frozen=True)
class _Cell:
    """One unit of cacheable work inside a spec: a source position with
    its seed and stream offset, plus its content address. ``source`` is
    the position's ``BitSource`` (the merged batch spec is rebuilt from
    these, so captured buffers ride through admission unchanged);
    ``generator`` keeps the reporting name."""
    generator: str
    seed: int
    offset: int
    digest: str
    source: object = None


def spec_cells(spec: RunSpec) -> List[_Cell]:
    """The spec's source positions as content-addressed cells (the
    digest folds in the spec-wide battery/scale/alpha and the RESOLVED
    backend, so "auto" shares slots with whatever it resolves to). A
    captured source's cell additionally folds the FILE CONTENT digest
    (``cell_digest``'s ``source_digest``): resubmitting the same capture
    hits its memoized verdict with zero dispatches, while a re-captured
    or byte-modified file is a different cell and misses. The spec's
    verdict engine folds in too (non-default only), so a cached
    Bonferroni decision can never answer an e-value submission."""
    resolved = kernel_backends.resolve(spec.backend)
    cells = []
    for g, src in enumerate(spec.sources):
        gen = spec.generators[g]
        off = int(spec.offsets[g]) if spec.offsets is not None else 0
        cells.append(_Cell(gen, int(spec.seeds[g]), off,
                           cell_digest(spec.battery, spec.scale, gen,
                                       spec.seeds[g], off, spec.alpha,
                                       resolved,
                                       src.digest() if src.captured
                                       else "",
                                       engine=spec.verdict_engine),
                           src))
    return cells


def admission_key(spec: RunSpec) -> tuple:
    """The compatibility class admission batching coalesces within:
    specs agreeing on (battery, scale, alpha, resolved backend, policy,
    stop_on_verdict, fault plan, verdict engine) can share one dispatch
    — everything else about them (generators, seeds, offsets) is a
    runtime argument of the merged run. A spec carrying an ``inject``
    plan only batches with specs carrying the SAME plan (fault
    injection is a property of the shared dispatch, so strangers must
    not inherit it silently); engines must match because the engine
    steers the merged run's early stopping and cache entries."""
    policy = get_policy(spec.policy)
    return (spec.battery, float(spec.scale), float(spec.alpha),
            kernel_backends.resolve(spec.backend), policy.name,
            policy.signature(), bool(spec.stop_on_verdict), spec.inject,
            spec.verdict_engine)


class Ticket:
    """A client's handle on one submission — the serve-layer analogue of
    ``BatteryRun``, with the same HTCondor-shaped verbs, none of which
    block the daemon: ``poll()`` advances the daemon one cooperative
    step (a no-op when a background thread is serving) and reports,
    ``held()``/``release()`` reach through to the shared batch run,
    ``result()`` waits for (or drives to) completion. ``cache_hits``
    counts the ticket's cells served from the result cache."""

    def __init__(self, queue: "SubmissionQueue", tid: str,
                 spec: Union[RunSpec, CampaignSpec], kind: str):
        self._queue = queue
        self.id = tid
        self.spec = spec
        self.kind = kind                      # "run" | "campaign"
        self.state = QUEUED
        self.submitted = time.monotonic()
        self.batch_id: Optional[int] = None
        self.cache_hits = 0
        self.failure: Optional[dict] = None         # FAILED terminal detail
        self._cached: Dict[int, CacheEntry] = {}    # position -> entry
        self._positions: Dict[int, int] = {}        # position -> batch pos
        self._campaign: Optional[Campaign] = None
        self._result = None
        self._event = threading.Event()

    # -- verbs -------------------------------------------------------------

    @property
    def done(self) -> bool:
        """True once the ticket reached a terminal state (DONE,
        CANCELLED or FAILED — a failed ticket is resolved, not stuck)."""
        return self.state in (DONE, CANCELLED, FAILED)

    def poll(self) -> dict:
        """One non-blocking look: advance the daemon a cooperative step
        (unless a background thread is already serving) and return this
        ticket's status snapshot."""
        if not self._queue.serving:
            self._queue.step()
        return self.status()

    def held(self) -> List[int]:
        """Job indices HELD in the shared batch this ticket rides on
        (job space is shared across the batch's tickets); empty while
        queued, cached or finished."""
        batch = self._queue._batch_of(self)
        return batch.handle.held() if batch else []

    def release(self) -> int:
        """condor_release on the shared batch run. Manual — it does NOT
        spend the driver's ``RetryPolicy`` budget (the api.py release
        discipline), and it releases the whole batch's HELD set: jobs
        are shared, so a release by any rider frees every rider."""
        batch = self._queue._batch_of(self)
        return batch.handle.release() if batch else 0

    def cancel(self) -> bool:
        """Withdraw the submission. A queued ticket leaves the pending
        set; a running one is marked cancelled and its demuxed results
        are discarded at batch finalize — the SHARED dispatch keeps
        running for the other riders (condor_rm removes your job, not
        the machine's whole batch). Returns True if a state changed."""
        return self._queue._cancel(self)

    def result(self, timeout: Optional[float] = None):
        """Block until the ticket completes and return its
        ``RunResult``/``BatteryResult`` (``CampaignResult`` for a
        campaign ticket). With a background daemon thread this waits;
        otherwise it drives the queue's cooperative loop. ``timeout``
        (seconds) raises ``TimeoutError`` when exceeded. A FAILED
        ticket (its batch exhausted the retry budget with jobs still
        HELD) raises ``RetryBudgetExhausted`` carrying the HELD job
        list — the structured terminal of DESIGN.md §12, never a
        hang."""
        if self._queue.serving:
            if not self._event.wait(timeout):
                raise TimeoutError(f"ticket {self.id} not done within "
                                   f"{timeout}s")
        else:
            deadline = (time.monotonic() + timeout
                        if timeout is not None else None)
            while not self.done:
                worked = self._queue.step(flush=True)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(f"ticket {self.id} not done "
                                       f"within {timeout}s")
                if not worked and not self.done:
                    raise RuntimeError(
                        f"ticket {self.id} stalled: the queue reports "
                        "no work left but the ticket is not terminal")
        if self.state == CANCELLED:
            raise RuntimeError(f"ticket {self.id} was cancelled")
        if self.state == FAILED:
            raise RetryBudgetExhausted(self.failure["held_jobs"],
                                       self.failure["retries"])
        return self._result

    def status(self) -> dict:
        """A condor_q-shaped snapshot: lifecycle state, batch id, cache
        hits, failure detail for a FAILED ticket, and — while the shared
        batch is live — its run counters."""
        out = {"ticket": self.id, "kind": self.kind, "state": self.state,
               "batch": self.batch_id, "cache_hits": self.cache_hits}
        if self.failure is not None:
            out["failure"] = dict(self.failure)
        batch = self._queue._batch_of(self)
        if batch is not None:
            run = batch.handle.status()
            out.update({"rounds_run": run["rounds_run"],
                        "pending_rounds": run["pending_rounds"],
                        "held": run["held"], "retries": run["retries"]})
        if self.kind == "campaign" and self._campaign is not None:
            out["phases_done"] = int(self._campaign.ledger.phases_done)
        return out


@dataclasses.dataclass
class _Batch:
    """One admitted coalition: the canonical (digest-sorted) cell list,
    the merged spec's live run handle, and the riding tickets."""
    id: int
    key: tuple
    cells: List[_Cell]
    tickets: List[Ticket]
    handle: object                  # BatteryRun
    digest: str


class SubmissionQueue:
    """The serve daemon: one ``PoolSession``, many clients (module
    docstring has the full architecture). Construct with an existing
    session to share its compile cache, or let it build one; give it a
    ``state_dir`` to persist the result cache and batch checkpoints
    across daemon restarts. ``max_wait`` (seconds) is the admission
    fairness bound — the longest any submission waits for companions
    before its batch is admitted as-is. ``inject`` applies one
    ``faults.FaultPlan`` to every merged batch the daemon forms —
    daemon-level chaos testing (DESIGN.md §12): the bitwise-degradation
    invariant means recovered results still populate the shared cache
    correctly."""

    def __init__(self, session: Optional[PoolSession] = None,
                 cache: Optional[ResultCache] = None,
                 state_dir: Optional[str] = None,
                 max_wait: float = 0.0,
                 inject: Optional[FaultPlan] = None):
        if max_wait < 0:
            raise ValueError(f"max_wait must be >= 0, got {max_wait}")
        self.session = session or PoolSession()
        self.inject = inject
        self._peak_workers = self.session.n_workers
        self.state_dir = state_dir
        if state_dir:
            os.makedirs(state_dir, exist_ok=True)
        self.cache = cache if cache is not None else ResultCache(
            os.path.join(state_dir, "cache") if state_dir else None)
        self.max_wait = float(max_wait)
        self._lock = threading.RLock()
        self._tickets: Dict[str, Ticket] = {}
        self._pending: List[Ticket] = []
        self._active: List[_Batch] = []
        self._next_ticket = 0
        self._next_batch = 0
        self.dispatch_rounds = 0        # device dispatches issued, total
        self.batches_formed = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -- client surface ----------------------------------------------------

    def submit(self, spec: Union[RunSpec, CampaignSpec]) -> Ticket:
        """Accept one submission and return its ticket immediately.
        A ``RunSpec`` whose every cell is already in the result cache
        completes here, synchronously, with zero dispatches — the O(1)
        repeat-submission path. Everything else joins the pending set
        for admission batching. Thread-safe."""
        with self._lock:
            tid = f"t{self._next_ticket}"
            self._next_ticket += 1
            kind = "campaign" if isinstance(spec, CampaignSpec) else "run"
            ticket = Ticket(self, tid, spec, kind)
            self._tickets[tid] = ticket
            if kind == "run" and self._try_cache(ticket):
                return ticket
            self._pending.append(ticket)
            return ticket

    @property
    def serving(self) -> bool:
        """True while a background daemon thread owns the loop."""
        return self._thread is not None and self._thread.is_alive()

    @property
    def idle(self) -> bool:
        """True when nothing is pending or in flight."""
        with self._lock:
            return not self._pending and not self._active

    def step(self, flush: bool = False) -> bool:
        """One cooperative unit of daemon work: complete any pending
        tickets the cache can now serve, admit every compatibility group
        past its ``max_wait`` window (``flush=True`` admits regardless
        of the window), then advance each active batch by one round and
        each active campaign by one phase. Returns True when any work
        happened — ``False`` means the queue is idle."""
        with self._lock:
            worked = self._admit(flush)
            worked = self._advance() or worked
            return worked

    def drain(self) -> None:
        """Drive the cooperative loop until every ticket is terminal
        (the inline equivalent of letting the daemon thread catch up)."""
        while self.step(flush=True):
            pass

    def start(self, poll_s: float = 0.01) -> "SubmissionQueue":
        """Spawn the background daemon thread (serve_forever): steps the
        loop, sleeping ``poll_s`` between idle checks. Returns self."""
        if self.serving:
            return self
        self._stop.clear()

        def _loop():
            while not self._stop.is_set():
                if not self.step():
                    time.sleep(poll_s)

        self._thread = threading.Thread(target=_loop, name="repro-serve",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the daemon thread (pending work stays queued — a later
        ``start()``/``drain()`` picks it up; on-disk state survives a
        full process crash via ``state_dir``)."""
        if self._thread is not None:
            self._stop.set()
            self._thread.join()
            self._thread = None

    def stats(self) -> dict:
        """Daemon counters: tickets, batches, dispatches, cache traffic,
        the session's compile-cache trace count, and the pool health
        (``workers``/``status``: a daemon whose pool shrank below its
        peak width — quarantines, lost workers — keeps serving and
        reports ``"degraded"`` instead of dying, DESIGN.md §12)."""
        with self._lock:
            cur = self.session.n_workers
            self._peak_workers = max(self._peak_workers, cur)
            return {"tickets": len(self._tickets),
                    "pending": len(self._pending),
                    "active_batches": len(self._active),
                    "batches": self.batches_formed,
                    "dispatch_rounds": self.dispatch_rounds,
                    "cache": {"hits": self.cache.hits,
                              "misses": self.cache.misses,
                              "entries": len(self.cache)},
                    "traces": self.session.total_traces,
                    "workers": cur,
                    "status": ("degraded" if cur < self._peak_workers
                               else "ok")}

    # -- cache path --------------------------------------------------------

    def _try_cache(self, ticket: Ticket) -> bool:
        """Serve the ticket entirely from the result cache when every
        cell hits; stash partial hits on the ticket either way so the
        batch only dispatches the missing cells."""
        spec = ticket.spec
        cells = spec_cells(spec)
        for g, cell in enumerate(cells):
            if g in ticket._cached:
                continue
            entry = self.cache.get(cell.digest, spec.stop_on_verdict)
            if entry is not None:
                ticket._cached[g] = entry
        ticket.cache_hits = len(ticket._cached)
        if len(ticket._cached) == len(cells):
            self._finalize_ticket(ticket, {}, rounds_run=0, retries=0,
                                  plan_rounds=0)
            return True
        return False

    # -- admission ---------------------------------------------------------

    def _admit(self, flush: bool) -> bool:
        """Form batches from the pending set: campaign tickets activate
        individually; run tickets group by ``admission_key`` and each
        group past its window is merged into one batch."""
        now = time.monotonic()
        worked = False
        groups: Dict[tuple, List[Ticket]] = {}
        for t in list(self._pending):
            if t.kind == "campaign":
                if flush or now - t.submitted >= self.max_wait:
                    self._pending.remove(t)
                    t._campaign = Campaign(self.session, t.spec)
                    t.state = RUNNING
                    worked = True
            else:
                groups.setdefault(admission_key(t.spec), []).append(t)
        for key, tickets in groups.items():
            oldest = min(t.submitted for t in tickets)
            if not flush and now - oldest < self.max_wait:
                continue
            worked = self._admit_group(key, tickets) or worked
        return worked

    def _admit_group(self, key: tuple, tickets: List[Ticket]) -> bool:
        """Merge one compatibility group into a single batch run."""
        riders: List[Ticket] = []
        need: Dict[str, _Cell] = {}
        for t in tickets:
            self._pending.remove(t)
            if self._try_cache(t):      # cache may have filled meanwhile
                continue
            riders.append(t)
            for g, cell in enumerate(spec_cells(t.spec)):
                if g not in t._cached:
                    need[cell.digest] = cell
        if not riders:
            return True
        # canonical order: sorted by digest, so the SAME submissions on
        # a restarted daemon rebuild the SAME batch (and checkpoint name)
        cells = [need[d] for d in sorted(need)]
        pos = {c.digest: i for i, c in enumerate(cells)}
        for t in riders:
            t._positions = {g: pos[c.digest]
                            for g, c in enumerate(spec_cells(t.spec))
                            if g not in t._cached}
        digest = hashlib.sha256(
            repr((key, tuple(c.digest for c in cells))).encode()
        ).hexdigest()[:16]
        spec = self._merged_spec(key, cells, riders, digest)
        batch = _Batch(self._next_batch, key, cells, riders,
                       self.session.submit(spec), digest)
        self._next_batch += 1
        self.batches_formed += 1
        for t in riders:
            t.state = RUNNING
            t.batch_id = batch.id
        self._active.append(batch)
        return True

    def _merged_spec(self, key: tuple, cells: List[_Cell],
                     riders: List[Ticket], digest: str) -> RunSpec:
        """The coalesced RunSpec: one source position per unique cell,
        every per-cell knob a runtime argument, checkpointed under a
        content-derived name so a restarted daemon resumes it. Cells
        carry their ``BitSource`` through admission, so captured buffers
        batch alongside generator positions unchanged."""
        (battery, scale, alpha, backend, _pname, _psig, sov, inject,
         engine) = key
        offsets = (tuple(c.offset for c in cells)
                   if any(c.offset for c in cells) else None)
        ck = (os.path.join(self.state_dir, f"batch-{digest}.ck")
              if self.state_dir else None)
        # the merged retry policy keeps the first rider's robustness
        # knobs (backoff, deadline, quarantine) with the group's most
        # generous budget; the daemon-level inject plan (chaos testing)
        # takes precedence over a rider-carried one
        return RunSpec(
            battery, sources=tuple(c.source for c in cells),
            seeds=tuple(c.seed for c in cells), scale=scale,
            policy=riders[0].spec.policy,
            retry=dataclasses.replace(
                riders[0].spec.retry, max_retries=max(
                    t.spec.retry.max_retries for t in riders)),
            checkpoint_path=ck, alpha=alpha, stop_on_verdict=sov,
            verdict_engine=engine, backend=backend, offsets=offsets,
            inject=self.inject if self.inject is not None else inject)

    # -- the daemon's advance ----------------------------------------------

    def _advance(self) -> bool:
        """One round per active batch, one phase per active campaign."""
        worked = False
        for batch in list(self._active):
            worked = self._advance_batch(batch) or worked
        for t in list(self._tickets.values()):
            if t.kind == "campaign" and t.state == RUNNING:
                worked = self._advance_campaign(t) or worked
        return worked

    def _advance_batch(self, batch: _Batch) -> bool:
        """Dispatch one round of the batch (or one driver-budgeted
        release pass), finalizing it once the drive policy would stop —
        the incremental twin of ``BatteryRun.drive``. A batch that
        exhausts its retry budget with jobs still HELD is routed to
        ``_fail_batch``: every rider resolves (DONE where its own cells
        are servable, FAILED otherwise) and the daemon keeps serving —
        graceful degradation, never a hang (DESIGN.md §12)."""
        h = batch.handle
        if h.pending_rounds:
            before = h.rounds_run
            h.poll()
            self.dispatch_rounds += h.rounds_run - before
            if h.pending_rounds or not (h.done or h.cancelled):
                return True
        if not (h.done or h.cancelled) and h.held():
            if h.driver_retries < h.spec.retry.max_retries:
                h._driver_release()
                return True
            self._fail_batch(batch)
            return True
        self._finalize_batch(batch)
        return True

    def _advance_campaign(self, ticket: Ticket) -> bool:
        """One campaign phase; the ticket completes when the campaign
        does (or stalls HELD through the retry budget, mirroring
        ``Campaign.run``'s stop-with-undecided-cells contract)."""
        camp = ticket._campaign
        before = camp.rounds_run
        progressed = camp.run_next_phase()
        self.dispatch_rounds += camp.rounds_run - before
        if camp.complete or not progressed:
            ticket._result = camp.result_snapshot(
                time.monotonic() - ticket.submitted)
            self._terminate(ticket, DONE)
        return True

    # -- finalize + demux --------------------------------------------------

    def _finalize_batch(self, batch: _Batch) -> None:
        """Memoize every cell's outcome, demux per-position results back
        to the riding tickets, and retire the batch."""
        h = batch.handle
        n_total = len(self.session.entries(h.spec))
        per_res = h.results_by_position()
        for c, res in zip(batch.cells, per_res):
            entry = CacheEntry.from_results(res, n_total, h.spec.alpha,
                                            engine=h.spec.verdict_engine)
            if entry.serves(stop_on_verdict=True):   # sellable to someone
                self.cache.put(c.digest, entry)
        groups = {t.id: sorted(t._positions.values())
                  for t in batch.tickets if t.state != CANCELLED}
        sliced = stitch.demux_positions(per_res, groups)
        for t in batch.tickets:
            if t.state == CANCELLED:
                continue
            by_batch_pos = dict(zip(groups[t.id], sliced[t.id]))
            per_cell = {g: by_batch_pos[p]
                        for g, p in t._positions.items()}
            self._finalize_ticket(t, per_cell, rounds_run=h.rounds_run,
                                  retries=h.retries,
                                  plan_rounds=h.plan_rounds)
        self._active.remove(batch)

    def _fail_batch(self, batch: _Batch) -> None:
        """Resolve a retry-budget-exhausted batch without hanging or
        poisoning anything: servable cells (complete, or verdict-decided
        for ``stop_on_verdict`` clients) are still memoized — the cache
        gate is ``CacheEntry.serves``, so an undecided partial NEVER
        enters the cache — riders whose own cells are all servable
        finalize DONE with their demuxed results, and every other rider
        terminates FAILED with a structured ``failure`` payload (reason,
        HELD job list, retries spent) that ``Ticket.result()`` surfaces
        as ``RetryBudgetExhausted``."""
        h = batch.handle
        held = [int(j) for j in h.held()]
        n_total = len(self.session.entries(h.spec))
        per_res = h.results_by_position()
        entries = [CacheEntry.from_results(res, n_total, h.spec.alpha,
                                           engine=h.spec.verdict_engine)
                   for res in per_res]
        for c, entry in zip(batch.cells, entries):
            if entry.serves(stop_on_verdict=True):   # sellable to someone
                self.cache.put(c.digest, entry)
        salvaged = [t for t in batch.tickets if t.state != CANCELLED
                    and all(entries[p].serves(
                        stop_on_verdict=t.spec.stop_on_verdict)
                        for p in t._positions.values())]
        groups = {t.id: sorted(t._positions.values()) for t in salvaged}
        sliced = stitch.demux_positions(per_res, groups)
        for t in batch.tickets:
            if t.state == CANCELLED:
                continue
            if t in salvaged:
                by_batch_pos = dict(zip(groups[t.id], sliced[t.id]))
                per_cell = {g: by_batch_pos[p]
                            for g, p in t._positions.items()}
                self._finalize_ticket(t, per_cell,
                                      rounds_run=h.rounds_run,
                                      retries=h.retries,
                                      plan_rounds=h.plan_rounds)
            else:
                t.failure = {
                    "reason": (f"retry budget exhausted after "
                               f"{h.driver_retries} release pass(es)"),
                    "held_jobs": held, "retries": h.driver_retries}
                self._terminate(t, FAILED)
        self._active.remove(batch)

    def _finalize_ticket(self, ticket: Ticket,
                         dispatched: Dict[int, Dict[int, tuple]],
                         rounds_run: int, retries: int,
                         plan_rounds: int) -> None:
        """Assemble the ticket's own ``RunResult``/``BatteryResult``
        from its cached cells plus the batch's demuxed positions — the
        exact shape ``BatteryRun.result()`` would have returned for the
        ticket's spec alone."""
        spec = ticket.spec
        entries = self.session.entries(spec)
        wall = time.monotonic() - ticket.submitted
        runs: Dict[str, RunResult] = {}
        for g, gen in enumerate(spec.generators):
            combined = (ticket._cached[g].results
                        if g in ticket._cached else dispatched[g])
            verdict = stitch.verdict_for(spec.verdict_engine)(
                combined, len(entries), spec.alpha)
            rep = stitch.report(entries, combined, gen, spec.seeds[g])
            runs[gen] = RunResult(combined, rep, rounds_run, retries,
                                  wall, plan_rounds, verdict=verdict)
        if spec.n_generators == 1:
            ticket._result = runs[spec.generators[0]]
        else:
            ticket._result = BatteryResult(spec, runs, rounds_run,
                                           retries, wall)
        self._terminate(ticket, DONE)

    # -- bookkeeping -------------------------------------------------------

    def _terminate(self, ticket: Ticket, state: str) -> None:
        """Move a ticket to a terminal state and wake its waiters."""
        ticket.state = state
        ticket._event.set()

    def _batch_of(self, ticket: Ticket) -> Optional[_Batch]:
        with self._lock:
            for b in self._active:
                if ticket in b.tickets:
                    return b
        return None

    def _cancel(self, ticket: Ticket) -> bool:
        with self._lock:
            if ticket.done:
                return False
            if ticket in self._pending:
                self._pending.remove(ticket)
            self._terminate(ticket, CANCELLED)
            return True
