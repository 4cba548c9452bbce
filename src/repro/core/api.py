"""Unified public API: declarative ``RunSpec`` -> compile-once
``PoolSession`` -> streaming ``BatteryRun``.

The paper's orchestration layer (`master`/`makesub`/`condor_submit`/
`empty`/`condor_release`/`superstitch`) as three first-class objects:

  ``RunSpec``      a frozen, declarative description of one run — battery,
                   scale, generator(s), seed(s), schedule policy, retry
                   policy, checkpoint path. One spec fully determines the
                   work; specs are hashable and comparable.
  ``PoolSession``  owns the device mesh and a compile cache keyed on
                   ``(battery, scale, n_workers, decomposition)``. The
                   compiled round program takes generator and seed as
                   runtime arguments, so repeated submits — different
                   generators, different seeds, replans after
                   hold/release — reuse the same jitted executable
                   instead of re-tracing. Pool width is a RUNTIME
                   property: ``resize(n)`` (and ``grow()``/``shrink()``
                   sugar — condor machines joining/vacating) swaps the
                   mesh, and live runs replan their remaining rounds
                   onto the new width at the next round boundary.
                   Executables for other widths stay cached — resizing
                   back is a cache hit, not a recompile (DESIGN.md §6).
  ``BatteryRun``   the submit handle, with HTCondor-shaped verbs:
                   ``poll()`` advances/reports one round, ``held()``
                   lists jobs with missing/invalid results, ``release()``
                   replans them, ``result()`` drives to completion,
                   ``stream()`` iterates per-round status, ``verdict()``
                   reports the sequential PASS/FAIL/UNDECIDED decision
                   after any round, ``cancel()`` drops pending rounds
                   (condor_rm). A spec with several generators fans out
                   in ONE dispatch per round (the job is mapped over a
                   ``gen_ids`` axis).

Adaptive early stopping (DESIGN.md §3-§4): ``policy="adaptive"`` orders
rounds by discrimination/cost and ``stop_on_verdict=True`` auto-cancels
work for a generator the moment the sequential verdict engine declares
it definitively failed — in a multi-generator fan-out the failed
generator drops out of the mapped ``gen_ids`` axis on subsequent
rounds, and once every generator is decided the remaining plan is never
dispatched.

Typical use::

    session = PoolSession()
    spec = RunSpec("smallcrush", generators=("splitmix64", "pcg32"),
                   seeds=(7,), scale=0.25)
    result = session.submit(spec).result()
    print(result.runs["pcg32"].report)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import numpy as np

from repro.ckpt import io as ckpt_io
from repro.core import stitch
from repro.core.battery import TestEntry, build_battery
from repro.core.faults import (CorruptResultError, FaultEvent, FaultInjector,
                               FaultPlan, WorkerHealth)
from repro.core.policies import (RetryBudgetExhausted, RetryPolicy,
                                 SchedulePolicy, get_policy)
from repro.core.pool import (gather_captured_bits, inject_round_faults,
                             make_external_runner, make_fanout_runner,
                             make_grid_runner, make_round_runner,
                             split_offsets)
from repro.core.scheduler import make_plan, replan
from repro.rng.sources import (BitSource, registry_size,
                               require_offsetable, resolve_source)
from repro.stats import backends as kernel_backends

# Battery presets (the folded BatteryConfig from common/config.py):
# test count and the sample-size multiplier of the paper-sized run.
# "pairstream" is the stream-seam machinery check the campaign subsystem
# runs as its screening phase (DESIGN.md §8), not a TestU01 analogue.
BATTERY_SIZES = {"smallcrush": 10, "crush": 96, "bigcrush": 106,
                 "pairstream": 4}
DEFAULT_SCALES = {"smallcrush": 1.0, "crush": 4.0, "bigcrush": 16.0,
                  "pairstream": 1.0}


def emit_progress(progress: Union[bool, Callable], msg: str) -> None:
    """The single progress choke point for the drive machinery.

    ``progress`` is a ``RunSpec.progress`` value: ``False`` drops the
    line, ``True`` prints it to stdout (the interactive CLI), and a
    callable receives it — which is how daemon and ``--json`` runs keep
    stdout clean while still logging (``release()`` used to ``print``
    with no way to redirect the sink).
    """
    if not progress:
        return
    if callable(progress):
        progress(msg)
    else:
        print(msg, flush=True)


# ---------------------------------------------------------------------------
# RunSpec


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """Declarative description of one battery run.

    ``generators`` may be a single name or a tuple; ``seeds`` broadcasts
    (one seed shared by every generator) or pairs element-wise.

    ``alpha`` is the family-wise error rate the sequential verdict engine
    spends across the battery (stitch.sequential_verdict);
    ``stop_on_verdict=True`` cancels pending work for a generator as soon
    as its verdict is definitive.

    ``verdict_engine`` picks WHICH engine judges the interim looks
    (stitch.VERDICT_ENGINES): ``"bonferroni"`` is the classic
    Bonferroni-sequential spending rule; ``"evalue"`` is the anytime-
    valid e-process engine (core/evidence.py, DESIGN.md §13) that FAILs
    when calibrated e-value wealth reaches ``1/alpha`` and records a
    wealth trajectory per generator. Both share alpha and the verdict
    surface, so everything downstream (checkpoints, campaigns, serve,
    CLI) is engine-agnostic.

    ``backend`` selects the test-kernel implementation family-wide
    (stats/backends.py): "reference" (pure-jnp), "accelerated" (Pallas
    kernels) or "auto" (accelerated on real TPU hardware, reference under
    interpret/CPU). Both backends share one ``bits -> (stat, p)``
    contract and stitch identical verdicts (tests/test_backends.py).

    ``offsets`` (campaign grids, DESIGN.md §8) gives each generator
    position a word offset into its (seed, stream) sequences: position g
    reads words ``[offsets[g], offsets[g] + n)`` instead of ``[0, n)``.
    ``None`` (the default) is the classic path with untouched trace
    shapes; any tuple — even all zeros — routes dispatch through the
    offset-taking grid runner, whose executables are shared across every
    offset value. Non-zero offsets require counter-based (offset-
    continuable) sources; ``mwc`` has no jump-ahead and is refused.

    ``sources`` is the BitSource spelling of the run's bit supply
    (rng/sources.py): a tuple of ``BitSource`` objects or declarative
    specs (``"pcg32"``, ``"file:capture.npy"``, a ``CapturedSource``).
    ``generators=`` remains the back-compat spelling — names resolve to
    ``GeneratorSource``s — and after construction BOTH fields are
    populated (``generators`` holds each source's reporting name), so
    every consumer that keys results by ``spec.generators[g]`` is
    untouched. Captured sources dispatch as prefetched host buffers,
    never as switch lanes (DESIGN.md §11).

    ``progress`` is ``False`` (silent), ``True`` (print to stdout) or a
    callable sink — every progress line the drive machinery emits goes
    through ``emit_progress``, so daemons can log without touching
    stdout.

    ``inject`` is an optional ``faults.FaultPlan`` (DESIGN.md §12):
    a seeded-deterministic schedule of simulated pool faults — evict,
    corrupt, straggle, lose_worker — applied at the host-side runner
    boundary (``pool.inject_round_faults``), so compiled executables
    and trace caches are untouched and the run replays bit-for-bit."""
    battery: str
    generators: Union[str, Tuple[str, ...]] = ()
    seeds: Union[int, Tuple[int, ...]] = (0,)  # repro: runtime-arg
    scale: float = 1.0
    policy: Union[str, SchedulePolicy] = "lpt"
    retry: RetryPolicy = RetryPolicy()  # repro: runtime-arg
    checkpoint_path: Optional[str] = None  # repro: runtime-arg
    progress: Union[bool, Callable] = False  # repro: runtime-arg
    alpha: float = 0.01  # repro: runtime-arg
    stop_on_verdict: bool = False  # repro: runtime-arg
    verdict_engine: str = "bonferroni"  # repro: runtime-arg
    backend: str = "auto"
    offsets: Optional[Union[int, Tuple[int, ...]]] = None
    sources: Optional[Tuple] = None
    inject: Optional[FaultPlan] = None  # repro: runtime-arg

    def __post_init__(self):
        if self.battery not in BATTERY_SIZES:
            raise KeyError(f"unknown battery {self.battery!r}; "
                           f"known: {sorted(BATTERY_SIZES)}")
        if self.sources is not None:
            given = (self.sources if isinstance(self.sources, (tuple, list))
                     else (self.sources,))
            srcs = tuple(resolve_source(s) for s in given)
            if not srcs:
                raise ValueError("sources must name at least one source")
            gens = tuple(s.name for s in srcs)
        else:
            gens = ((self.generators,) if isinstance(self.generators, str)
                    else tuple(self.generators))
            if not gens:
                gens = ("splitmix64",)
            srcs = tuple(resolve_source(g) for g in gens)
        seeds = ((self.seeds,) if isinstance(self.seeds, int)
                 else tuple(int(s) for s in self.seeds))
        if len(seeds) == 1:
            seeds = seeds * len(gens)
        if len(seeds) != len(gens):
            raise ValueError(
                f"{len(seeds)} seeds for {len(gens)} generators "
                "(give one seed, or one per generator)")
        object.__setattr__(self, "generators", gens)
        object.__setattr__(self, "seeds", seeds)
        object.__setattr__(self, "sources", srcs)
        if self.offsets is not None:
            offs = ((int(self.offsets),) if isinstance(self.offsets, int)
                    else tuple(int(o) for o in self.offsets))
            if len(offs) == 1:
                offs = offs * len(gens)
            if len(offs) != len(gens):
                raise ValueError(
                    f"{len(offs)} offsets for {len(gens)} generators "
                    "(give one offset, or one per generator)")
            for s, o in zip(srcs, offs):
                if o < 0:
                    raise ValueError(f"offsets must be >= 0, got {o}")
                require_offsetable(s, o)         # typed, single gate
            object.__setattr__(self, "offsets", offs)
        get_policy(self.policy)                  # validate early
        if not (0.0 < self.alpha < 1.0):
            raise ValueError(f"alpha must be in (0, 1), got {self.alpha}")
        stitch.verdict_for(self.verdict_engine)  # validate early
        if self.backend not in kernel_backends.BACKENDS:
            raise KeyError(f"unknown backend {self.backend!r}; "
                           f"known: {kernel_backends.BACKENDS}")
        if self.inject is not None and not isinstance(self.inject, FaultPlan):
            raise TypeError(f"inject must be a faults.FaultPlan, "
                            f"got {type(self.inject)}")

    @classmethod
    def preset(cls, battery: str, **overrides) -> "RunSpec":
        """Paper-sized spec for a battery (scale from DEFAULT_SCALES)."""
        overrides.setdefault("scale", DEFAULT_SCALES[battery])
        return cls(battery, **overrides)

    @property
    def n_tests(self) -> int:
        """Battery size in TEST space (pre-decomposition)."""
        return BATTERY_SIZES[self.battery]

    @property
    def n_generators(self) -> int:
        """Width of the fan-out axis (generator positions)."""
        return len(self.generators)

    @property
    def switch_lanes(self) -> int:
        """Minimum compiled-switch width this spec's generator-backed
        sources need: ``1 + max(gen_id)`` over the non-captured sources
        (0 when every source is captured). ``PoolSession._runner`` keys
        executables on it, so a generator registered after a switch was
        traced reuses nothing narrower than its own lane — and specs
        confined to built-in lanes keep sharing the executables they
        always shared."""
        ids = [s.gen_id for s in self.sources if not s.captured]
        return 1 + max(ids) if ids else 0

    @property
    def captured_positions(self) -> Tuple[int, ...]:
        """Source positions dispatched via the prefetched-buffer path
        (``CapturedSource``) rather than the compiled generator switch."""
        return tuple(g for g, s in enumerate(self.sources) if s.captured)


# ---------------------------------------------------------------------------
# results


@dataclasses.dataclass
class RunResult:
    """Per-generator outcome (the classic run_battery return shape)."""
    results: Dict[int, tuple]       # test index -> (stat, p), combined
    report: str
    rounds_run: int
    retries: int
    wall_s: float
    plan_rounds: int
    verdict: Optional[stitch.Verdict] = None    # sequential decision

    @property
    def n_suspect(self) -> int:
        """Tests flagged by the two-sided suspect rule."""
        return self.report.count("SUSPECT")


@dataclasses.dataclass
class BatteryResult:
    """Outcome of a (possibly multi-generator) submit."""
    spec: RunSpec
    runs: Dict[str, RunResult]      # generator name -> result
    rounds_run: int
    retries: int
    wall_s: float

    @property
    def n_suspect(self) -> int:
        """Suspect count across every generator's run."""
        return sum(r.n_suspect for r in self.runs.values())

    @property
    def verdicts(self) -> Dict[str, stitch.Verdict]:
        """Per-generator sequential verdicts, keyed by name."""
        return {g: r.verdict for g, r in self.runs.items()}


# ---------------------------------------------------------------------------
# checkpoint layout (v5: job-id keyed, worker-count independent,
# source-identity pinned, verdict-engine aware)

CKPT_VERSION = 5


@dataclasses.dataclass
class Checkpoint:
    """On-disk battery progress — v5, keyed by JOB ID, never by
    (round, worker) position. The layout is a pure function of the job
    table, so a checkpoint written on a W=8 mesh resumes bitwise on W=4
    (or any width) after elastic re-meshing (DESIGN.md §6).

    Wire layouts (``ckpt/io`` leaves)::

      v5 (written): [version, job_idx (K,), stats (G, K), ps (G, K),
                     decisions (G,) int8 — empty when absent, rounds_run,
                     alpha — nan when absent, source_uids (G,) bytes —
                     empty when absent, engine (1,) bytes,
                     log_wealth (G,) float64 — empty when absent]
      v4 (read):    v5 without the trailing engine + log_wealth leaves
      v3 (read):    v4 without the trailing source_uids leaf
      v2 (read):    [job_idx, stats, ps, decisions, rounds_run]
      v1 (read):    [job_idx, stats, ps]    (stats flat for one generator)

    Loading a v1..v4 file works transparently; the next save upgrades
    it to v5. ``decisions`` carries the verdict codes (see
    ``BatteryRun._DECISION_CODE``); ``None`` means no verdict state.
    ``alpha`` records which error rate the decisions were computed
    under — a resuming run adopts them only when its own alpha matches
    (they are a pure function of (results, alpha)). ``engine`` names the
    verdict engine that produced the decisions (v1..v4 files imply
    ``"bonferroni"``); resuming verdict state under a DIFFERENT engine
    raises ``VerdictEngineMismatch`` — the engines' decisions are not
    comparable. ``log_wealth`` snapshots each generator's accumulated
    e-process wealth under the ``evalue`` engine (DESIGN.md §13); it is
    advisory (wealth is recomputed from results on load) but makes the
    trajectory inspectable on disk. ``source_uids`` pins each generator
    position's BitSource identity (``BitSource.uid()``): for captured
    sources the uid embeds the file's content digest, so a checkpoint
    written against one capture REFUSES to resume against a re-captured
    (byte-different) file."""
    job_idx: np.ndarray                         # (K,) int32 job ids
    stats: np.ndarray                           # (G, K) float64
    ps: np.ndarray                              # (G, K) float64
    decisions: Optional[np.ndarray] = None      # (G,) int8 verdict codes
    rounds_run: int = 0
    alpha: Optional[float] = None               # decisions' error rate
    source_uids: Optional[np.ndarray] = None    # (G,) bytes BitSource.uid
    engine: str = "bonferroni"                  # decisions' verdict engine
    log_wealth: Optional[np.ndarray] = None     # (G,) float64 e-wealth
    version: int = CKPT_VERSION

    @property
    def n_generators(self) -> int:
        """Rows of the stacked (G, K) result arrays."""
        return int(self.stats.shape[0])

    @classmethod
    def load(cls, path: str) -> "Checkpoint":
        """Read any supported layout (v1..v5) into the v5 shape."""
        leaves = ckpt_io.load_flat(path)
        if len(leaves) == 10:                   # v5: verdict engine
            (ver, idx, st, pv, dec, rounds, alpha, uids, eng, lw) = leaves
            if int(ver) != CKPT_VERSION:
                raise ValueError(
                    f"checkpoint {path} declares version {int(ver)}; "
                    f"this build reads v1..v{CKPT_VERSION}")
            dec = np.asarray(dec, np.int8)
            alpha = float(alpha)
            uids = np.asarray(uids)
            eng = np.asarray(eng)
            lw = np.asarray(lw, np.float64)
            return cls(np.asarray(idx, np.int32), np.atleast_2d(st),
                       np.atleast_2d(pv), dec if dec.size else None,
                       int(rounds),
                       None if np.isnan(alpha) else alpha,
                       uids if uids.size else None,
                       engine=(bytes(eng.reshape(-1)[0]).decode()
                               if eng.size else "bonferroni"),
                       log_wealth=lw if lw.size else None,
                       version=CKPT_VERSION)
        if len(leaves) == 8:                    # v4: source identity
            ver, idx, st, pv, dec, rounds, alpha, uids = leaves
            if int(ver) != 4:
                raise ValueError(
                    f"checkpoint {path} declares version {int(ver)} in an "
                    f"8-leaf (v4) layout; this build reads "
                    f"v1..v{CKPT_VERSION}")
            dec = np.asarray(dec, np.int8)
            alpha = float(alpha)
            uids = np.asarray(uids)
            return cls(np.asarray(idx, np.int32), np.atleast_2d(st),
                       np.atleast_2d(pv), dec if dec.size else None,
                       int(rounds),
                       None if np.isnan(alpha) else alpha,
                       uids if uids.size else None, version=4)
        if len(leaves) == 7:                    # v3: no source identity
            ver, idx, st, pv, dec, rounds, alpha = leaves
            if int(ver) != 3:
                raise ValueError(
                    f"checkpoint {path} declares version {int(ver)} in a "
                    f"7-leaf (v3) layout; this build reads "
                    f"v1..v{CKPT_VERSION}")
            dec = np.asarray(dec, np.int8)
            alpha = float(alpha)
            return cls(np.asarray(idx, np.int32), np.atleast_2d(st),
                       np.atleast_2d(pv), dec if dec.size else None,
                       int(rounds),
                       None if np.isnan(alpha) else alpha, None, version=3)
        if len(leaves) == 5:                    # v2: verdict state present
            idx, st, pv, dec, rounds = leaves
            return cls(np.asarray(idx, np.int32), np.atleast_2d(st),
                       np.atleast_2d(pv),
                       np.atleast_1d(np.asarray(dec, np.int8)),
                       int(rounds), None, None, version=2)
        if len(leaves) == 3:                    # v1: classic results-only
            idx, st, pv = leaves
            return cls(np.asarray(idx, np.int32), np.atleast_2d(st),
                       np.atleast_2d(pv), None, 0, None, None, version=1)
        raise ValueError(
            f"checkpoint {path} has {len(leaves)} leaves; expected 3 (v1), "
            f"5 (v2), 7 (v3), 8 (v4) or 10 (v{CKPT_VERSION})")

    def save(self, path: str) -> None:
        """Write the v5 layout (whatever version was loaded)."""
        dec = (np.zeros((0,), np.int8) if self.decisions is None
               else np.asarray(self.decisions, np.int8))
        uids = (np.zeros((0,), "S1") if self.source_uids is None
                else np.asarray(self.source_uids))
        lw = (np.zeros((0,), np.float64) if self.log_wealth is None
              else np.asarray(self.log_wealth, np.float64))
        ckpt_io.save(path, [
            np.int64(CKPT_VERSION), np.asarray(self.job_idx, np.int32),
            np.atleast_2d(np.asarray(self.stats, np.float64)),
            np.atleast_2d(np.asarray(self.ps, np.float64)),
            dec, np.int64(self.rounds_run),
            np.float64(np.nan if self.alpha is None else self.alpha),
            uids, np.asarray([self.engine.encode()]), lw])

    def drop(self, job_ids) -> "Checkpoint":
        """A copy with the given jobs knocked out (simulated node loss /
        checkpoint surgery). Verdict state is discarded — decisions are a
        function of the full result set, and a resumed run recomputes
        them from what survives."""
        keep = ~np.isin(self.job_idx, np.asarray(list(job_ids), np.int32))
        return dataclasses.replace(
            self, job_idx=self.job_idx[keep], stats=self.stats[:, keep],
            ps=self.ps[:, keep], decisions=None, log_wealth=None,
            version=CKPT_VERSION)

    def results(self) -> List[Dict[int, tuple]]:
        """Per-generator {job_id: (stat, p)} — the in-memory form."""
        return [{int(i): (float(s), float(p))
                 for i, s, p in zip(self.job_idx, self.stats[g], self.ps[g])}
                for g in range(self.n_generators)]


# ---------------------------------------------------------------------------
# campaign spec + ledger (generator-fleet screening, DESIGN.md §8)


@dataclasses.dataclass(frozen=True)
class CampaignSpec:
    """Declarative screening grid: ``generators`` x ``n_streams``
    sub-stream offsets, screened in ``waves`` (battery scales, run
    cheapest first) with failed cells knocked out of subsequent waves.

    ``waves`` are battery scales; the campaign driver sorts them
    ascending so the cheap screening waves run before the expensive
    confirmation waves (``scheduler.wave_schedule``). ``stream_check``
    prepends the pairstream seam battery as phase 0 — the inter-stream
    disjointness/correlation check over adjacent sub-streams.

    ``span`` is the word spacing between adjacent sub-streams (stream s
    of a cell reads words ``[s * span, ...)`` of every job's sequence);
    ``None`` derives the smallest power-of-two span that keeps every
    job's block of the largest wave inside its own stream. More than one
    stream requires every source to be offset-continuable
    (``counter_based`` — mwc is refused up front, not at dispatch).

    ``sources`` is the BitSource spelling of the fleet (mirrors
    ``RunSpec.sources``): BitSource objects or declarative specs,
    captured files included — a campaign can screen a nonce dump's
    sub-streams next to in-repo generators. ``generators=`` remains the
    back-compat spelling; after construction both fields are populated
    (``generators`` holds reporting names).

    ``verdict_engine`` mirrors ``RunSpec.verdict_engine``: under
    ``"evalue"`` every cell accumulates e-process wealth across waves in
    the ledger and is knocked out when wealth reaches ``1/alpha``
    (DESIGN.md §13). ``continue_band`` is the optional-continuation
    band: a cell that finishes the last scheduled wave UNDECIDED with
    wealth in ``[continue_band/alpha, 1/alpha)`` is *re-opened* — a
    fresh continuation phase over previously unread stream words is
    appended instead of force-deciding the cell — up to
    ``max_continuations`` times (0 disables; band 0 force-decides like
    the Bonferroni engine). Both knobs are inert under
    ``"bonferroni"``."""
    battery: str
    generators: Tuple[str, ...] = ()
    n_streams: int = 1
    seed: int = 0
    waves: Tuple[float, ...] = (0.25, 1.0)
    alpha: float = 0.01
    policy: Union[str, SchedulePolicy] = "lpt"
    retry: RetryPolicy = RetryPolicy()
    backend: str = "auto"
    stream_check: bool = True
    span: Optional[int] = None
    ledger_path: Optional[str] = None
    progress: Union[bool, Callable] = False
    sources: Optional[Tuple] = None
    verdict_engine: str = "bonferroni"
    continue_band: float = 0.5
    max_continuations: int = 1

    def __post_init__(self):
        if self.battery not in BATTERY_SIZES:
            raise KeyError(f"unknown battery {self.battery!r}; "
                           f"known: {sorted(BATTERY_SIZES)}")
        if self.sources is not None:
            given = (self.sources if isinstance(self.sources, (tuple, list))
                     else (self.sources,))
            srcs = tuple(resolve_source(s) for s in given)
            gens = tuple(s.name for s in srcs)
        else:
            gens = ((self.generators,) if isinstance(self.generators, str)
                    else tuple(self.generators))
            srcs = tuple(resolve_source(g) for g in gens)
        if not gens:
            raise ValueError("a campaign needs at least one generator "
                             "(or source)")
        if len(set(gens)) != len(gens):
            raise ValueError(f"duplicate generators in {gens}")
        object.__setattr__(self, "generators", gens)
        object.__setattr__(self, "sources", srcs)
        if self.n_streams < 1:
            raise ValueError(f"n_streams must be >= 1, got {self.n_streams}")
        if self.n_streams > 1:
            bad = [s.name for s in srcs if not s.counter_based]
            if bad:
                raise ValueError(
                    f"stream grids need offset-continuable generators; "
                    f"{bad} are not COUNTER_BASED")
        waves = ((self.waves,) if isinstance(self.waves, (int, float))
                 else tuple(float(w) for w in self.waves))
        if not waves or any(w <= 0 for w in waves):
            raise ValueError(f"waves must be positive scales, got {waves}")
        object.__setattr__(self, "waves", waves)
        if not (0.0 < self.alpha < 1.0):
            raise ValueError(f"alpha must be in (0, 1), got {self.alpha}")
        get_policy(self.policy)
        if self.backend not in kernel_backends.BACKENDS:
            raise KeyError(f"unknown backend {self.backend!r}; "
                           f"known: {kernel_backends.BACKENDS}")
        if self.span is not None and self.span < 1:
            raise ValueError(f"span must be >= 1, got {self.span}")
        stitch.verdict_for(self.verdict_engine)  # validate early
        if not (0.0 <= self.continue_band < 1.0):
            raise ValueError(f"continue_band must be in [0, 1), "
                             f"got {self.continue_band}")
        if self.max_continuations < 0:
            raise ValueError(f"max_continuations must be >= 0, "
                             f"got {self.max_continuations}")
        if (self.verdict_engine != "bonferroni" and self.max_continuations
                and self.continue_band > 0.0):
            # continuation phases read fresh words past every stream's
            # scheduled block, which needs jump-ahead
            bad = [s.name for s in srcs if not s.counter_based]
            if bad:
                raise ValueError(
                    f"optional continuation needs offset-continuable "
                    f"generators; {bad} are not COUNTER_BASED (set "
                    f"max_continuations=0 or continue_band=0.0)")

    @property
    def cells(self) -> List[Tuple[str, int]]:
        """Grid cells in ledger order: (generator, stream) pairs."""
        return [(g, s) for g in self.generators
                for s in range(self.n_streams)]

    @property
    def cell_sources(self) -> List[Tuple[BitSource, int]]:
        """Grid cells in ledger order as (BitSource, stream) pairs — the
        source-resolved twin of ``cells`` the phase driver builds its
        ``RunSpec.sources`` from."""
        return [(src, s) for src in self.sources
                for s in range(self.n_streams)]

    @property
    def n_cells(self) -> int:
        """Grid size: generators x streams."""
        return len(self.generators) * self.n_streams

    def digest(self) -> int:
        """Deterministic uint64 identity of everything the campaign's
        DECISIONS depend on — battery, grid, seed, waves, alpha, policy,
        stream_check, span, and (for captured sources) the FILE CONTENT
        each cell screens: a re-captured file is a different campaign
        and refuses the old ledger. Generator-only campaigns fold
        exactly the pre-BitSource key, so their stored ledger digests
        still match; likewise the verdict engine (plus its continuation
        knobs) is folded only when non-default, so Bonferroni ledgers
        keep their historical digests while an e-value campaign can
        never resume — or be resumed by — a Bonferroni ledger. Stored in the ledger so a resume against a
        reconfigured campaign is refused instead of silently replaying
        decisions made under different settings. ``backend`` is
        deliberately excluded: both backends are parity-asserted to
        stitch identical verdicts (tests/test_backends.py), so a ledger
        may move between reference and accelerated hosts."""
        import hashlib
        policy = get_policy(self.policy)
        parts = (self.battery, self.generators, self.n_streams,
                 self.seed, self.waves, self.alpha, policy.name,
                 policy.signature(), self.stream_check, self.span)
        captured = tuple(s.uid() for s in self.sources if s.captured)
        if captured:
            parts = parts + (captured,)
        if self.verdict_engine != "bonferroni":
            # folded only when non-default so every pre-engine ledger
            # digest stays byte-identical (same pattern as captured uids)
            parts = parts + (("engine", self.verdict_engine,
                              self.continue_band, self.max_continuations),)
        key = repr(parts)
        return int.from_bytes(
            hashlib.sha256(key.encode()).digest()[:8], "big")


CAMPAIGN_LEDGER_VERSION = 3

# cell decision codes shared by the ledger and the campaign driver
# (0/1/2 match BatteryRun._DECISION_CODE; the phase axis is the ledger's)
CELL_UNDECIDED, CELL_PASS, CELL_FAIL = 0, 1, 2


@dataclasses.dataclass
class CampaignLedger:
    """On-disk campaign progress — keyed by CELL identity
    ``(gen_id, stream)``, never by wave order or grid position, the same
    discipline as the v3 run checkpoint (job-id keyed, §6): the layout
    is a pure function of the grid, so a ledger survives re-ordering of
    waves and resumes on any pool width.

    Wire layouts (``ckpt/io`` leaves)::

      v3 (written): [version, gen_ids (C,) int32, streams (C,) int32,
                     decisions (C,) int8, decided_phase (C,) int8
                     (-1 = undecided), phases_done, alpha,
                     spec_digest uint64, source_uids (C,) bytes,
                     log_wealth (C,) float64 — empty when absent,
                     engine (1,) bytes, continuations int64]
      v2 (read):    v3 without the trailing log_wealth + engine +
                    continuations leaves
      v1 (read):    v2 without the trailing source_uids leaf

    A v1/v2 ledger loads transparently; the next save upgrades it to v3.
    ``source_uids`` pins each cell's BitSource identity
    (``BitSource.uid()``; captured cells carry ``gen_id`` -1 plus a
    content-bearing uid, so a re-captured file refuses the ledger).
    ``decisions`` carries ``CELL_UNDECIDED/CELL_PASS/CELL_FAIL``;
    ``decided_phase`` records WHICH phase decided the cell (0 = stream
    check when enabled, then the waves in ascending-scale order, then
    any continuation phases). ``phases_done`` counts completed phases,
    so a resumed campaign re-enters the phase list exactly where it
    stopped; a phase interrupted mid-battery additionally resumes from
    its own per-phase run checkpoint (``<ledger>.phaseK``).
    ``log_wealth`` accumulates each cell's e-process wealth across
    phases under the ``evalue`` engine (DESIGN.md §13) — it is DECISION
    state, persisted with the decisions it feeds, which is what makes
    optional continuation resume-safe. ``engine`` names the verdict
    engine (v1/v2 files imply ``"bonferroni"``); ``continuations``
    counts how many continuation phases have been opened, so a resumed
    campaign reconstructs the exact phase list. ``spec_digest`` pins the
    full decision-relevant configuration (``CampaignSpec.digest``) —
    resuming with a different battery, waves, seed, alpha, policy,
    stream_check, span or verdict engine is refused, not silently
    replayed."""
    gen_ids: np.ndarray
    streams: np.ndarray
    decisions: np.ndarray
    decided_phase: np.ndarray
    phases_done: int = 0
    alpha: Optional[float] = None
    spec_digest: int = 0
    source_uids: Optional[np.ndarray] = None    # (C,) bytes BitSource.uid
    log_wealth: Optional[np.ndarray] = None     # (C,) float64 e-wealth
    engine: str = "bonferroni"                  # decisions' verdict engine
    continuations: int = 0                      # continuation phases opened
    version: int = CAMPAIGN_LEDGER_VERSION

    @staticmethod
    def _want_ids(spec: CampaignSpec):
        """The spec's grid as ledger columns: per-cell gen_id (-1 for a
        captured cell — it holds no switch lane) and stream index."""
        gids = [(-1 if src.captured else src.gen_id)
                for src, _ in spec.cell_sources]
        return (np.asarray(gids, np.int32),
                np.asarray([s for _, s in spec.cell_sources], np.int32))

    @classmethod
    def fresh(cls, spec: CampaignSpec) -> "CampaignLedger":
        """An all-undecided ledger for the spec's grid."""
        c = spec.n_cells
        gids, streams = cls._want_ids(spec)
        uids = np.asarray([src.uid().encode()
                           for src, _ in spec.cell_sources])
        return cls(gids, streams,
                   np.zeros((c,), np.int8), np.full((c,), -1, np.int8),
                   0, spec.alpha, spec.digest(), uids,
                   log_wealth=np.zeros((c,), np.float64),
                   engine=spec.verdict_engine)

    @classmethod
    def load(cls, path: str) -> "CampaignLedger":
        """Read (and version-check) a v1, v2 or v3 ledger file."""
        leaves = ckpt_io.load_flat(path)
        if len(leaves) == 12:                   # v3: verdict engine
            (ver, gids, streams, dec, phase, done, alpha, digest, uids,
             lw, eng, cont) = leaves
            if int(ver) != CAMPAIGN_LEDGER_VERSION:
                raise ValueError(
                    f"campaign ledger {path} declares version {int(ver)} "
                    f"in a 12-leaf layout; this build reads "
                    f"v1/v2/v{CAMPAIGN_LEDGER_VERSION}")
            uids = np.asarray(uids)
            alpha = float(alpha)
            lw = np.asarray(lw, np.float64)
            eng = np.asarray(eng)
            return cls(np.asarray(gids, np.int32),
                       np.asarray(streams, np.int32),
                       np.asarray(dec, np.int8), np.asarray(phase, np.int8),
                       int(done), None if np.isnan(alpha) else alpha,
                       int(np.uint64(digest)),
                       uids if uids.size else None,
                       log_wealth=lw if lw.size else None,
                       engine=(bytes(eng.reshape(-1)[0]).decode()
                               if eng.size else "bonferroni"),
                       continuations=int(cont),
                       version=CAMPAIGN_LEDGER_VERSION)
        if len(leaves) == 9:                    # v2: source identity
            ver, gids, streams, dec, phase, done, alpha, digest, uids = leaves
            if int(ver) != 2:
                raise ValueError(
                    f"campaign ledger {path} declares version {int(ver)} "
                    f"in a 9-leaf (v2) layout; this build reads "
                    f"v1/v2/v{CAMPAIGN_LEDGER_VERSION}")
            uids = np.asarray(uids)
            alpha = float(alpha)
            return cls(np.asarray(gids, np.int32),
                       np.asarray(streams, np.int32),
                       np.asarray(dec, np.int8), np.asarray(phase, np.int8),
                       int(done), None if np.isnan(alpha) else alpha,
                       int(np.uint64(digest)),
                       uids if uids.size else None, version=2)
        if len(leaves) == 8:                    # v1: no source identity
            ver, gids, streams, dec, phase, done, alpha, digest = leaves
            if int(ver) != 1:
                raise ValueError(
                    f"campaign ledger {path} declares version {int(ver)} "
                    f"in an 8-leaf (v1) layout; this build reads "
                    f"v1/v2/v{CAMPAIGN_LEDGER_VERSION}")
            alpha = float(alpha)
            return cls(np.asarray(gids, np.int32),
                       np.asarray(streams, np.int32),
                       np.asarray(dec, np.int8), np.asarray(phase, np.int8),
                       int(done), None if np.isnan(alpha) else alpha,
                       int(np.uint64(digest)), None, version=1)
        raise ValueError(f"campaign ledger {path} has {len(leaves)} "
                         "leaves; expected 8 (v1), 9 (v2) or 12 (v3)")

    def save(self, path: str) -> None:
        """Write the 12-leaf v3 cell-keyed wire layout (atomic)."""
        uids = (np.zeros((0,), "S1") if self.source_uids is None
                else np.asarray(self.source_uids))
        lw = (np.zeros((0,), np.float64) if self.log_wealth is None
              else np.asarray(self.log_wealth, np.float64))
        ckpt_io.save(path, [
            np.int64(CAMPAIGN_LEDGER_VERSION),
            np.asarray(self.gen_ids, np.int32),
            np.asarray(self.streams, np.int32),
            np.asarray(self.decisions, np.int8),
            np.asarray(self.decided_phase, np.int8),
            np.int64(self.phases_done),
            np.float64(np.nan if self.alpha is None else self.alpha),
            np.uint64(self.spec_digest), uids, lw,
            np.asarray([self.engine.encode()]),
            np.int64(self.continuations)])

    def matches(self, spec: CampaignSpec) -> bool:
        """Does this ledger describe exactly this campaign — same cells
        in the same order AND the same decision-relevant configuration
        (``CampaignSpec.digest``: battery, waves, seed, alpha, policy,
        stream_check, span, captured-file content)? A resumed campaign
        refuses otherwise — cell decisions are only meaningful for the
        campaign that made them. A v1 ledger (no stored uids) matches on
        the pre-BitSource columns alone; captured cells always carry
        uids, so the digest still refuses re-captured files."""
        want_g, want_s = self._want_ids(spec)
        if self.source_uids is not None:
            want_u = np.asarray([src.uid().encode()
                                 for src, _ in spec.cell_sources])
            uids = np.asarray(self.source_uids)
            if uids.shape != want_u.shape or not bool(np.all(uids == want_u)):
                return False
        return (self.gen_ids.shape == want_g.shape
                and bool(np.all(self.gen_ids == want_g))
                and bool(np.all(self.streams == want_s))
                and (self.alpha is None or self.alpha == spec.alpha)
                and self.engine == spec.verdict_engine
                and self.spec_digest == spec.digest())


# ---------------------------------------------------------------------------
# session + compile cache


@dataclasses.dataclass
class _Compiled:
    """One job-table slot: the width-INDEPENDENT battery/job tables plus
    the jitted runners, keyed ``(n_workers, n_generators)``. One table
    serves every pool width — job identity must never depend on width
    (that is what makes checkpoints and resizes reconcile, DESIGN.md §6)
    — so a resize adds runner entries, never a second table, and a live
    run's captured slot IS the slot every dispatch compiles against."""
    entries: List[TestEntry]        # original battery (test space)
    jobs: List[TestEntry]           # possibly decomposed (job space)
    costs: List[float]
    combine: str
    runners: dict       # (n_workers, G, grid, captured, lanes) -> jitted fn


class PoolSession:
    """Owns the mesh and the compile cache. Build one session, submit many
    specs; runs against the same ``(battery, scale, n_workers)`` share one
    jitted round program (generator/seed are runtime arguments).

    Pool width is a runtime property (the paper's opportunistic pool:
    machines join when idle, vacate when their owner returns) —
    ``resize``/``grow``/``shrink`` re-mesh mid-run. Each width owns its
    own mesh and cache entries, so bouncing 8 -> 4 -> 8 recompiles only
    the 4-wide program and returns to the 8-wide executables for free."""

    def __init__(self, mesh=None, n_workers: Optional[int] = None):
        if mesh is None:
            from repro.launch.mesh import make_pool_mesh
            mesh = make_pool_mesh(n_workers)
        self.mesh = mesh
        self._meshes: Dict[int, object] = {int(mesh.devices.size): mesh}
        self._cache: Dict[tuple, _Compiled] = {}
        self.trace_counts: Dict[tuple, int] = {}

    @property
    def n_workers(self) -> int:
        """Current pool width (a runtime property — see ``resize``)."""
        return int(self.mesh.devices.size)

    def resize(self, n_workers: int) -> int:
        """Elastic re-meshing: set the pool width to ``n_workers``.
        Live ``BatteryRun``s replan their remaining rounds onto the new
        width at their next round boundary (completed results, verdict
        state and sub-stream assignments are all width-independent, so
        nothing is lost or re-executed needlessly). Compiled programs
        for other widths stay cached. Returns the new width."""
        n = int(n_workers)
        if n < 1:
            raise ValueError(f"pool width must be >= 1, got {n}")
        if n != self.n_workers:
            mesh = self._meshes.get(n)
            if mesh is None:
                from repro.launch.mesh import make_pool_mesh
                mesh = make_pool_mesh(n)
                self._meshes[n] = mesh
            self.mesh = mesh
        return self.n_workers

    def grow(self, n: int = 1) -> int:
        """``n`` machines joined the pool (condor: owner went idle)."""
        return self.resize(self.n_workers + n)

    def shrink(self, n: int = 1) -> int:
        """``n`` machines vacated (condor: owner came back)."""
        return self.resize(self.n_workers - n)

    @property
    def total_traces(self) -> int:
        """Round-program traces so far (compile-cache accounting)."""
        return sum(self.trace_counts.values())

    def cache_key(self, spec: RunSpec) -> tuple:
        """Trace-accounting key: one entry per compiled pool width. The
        RESOLVED kernel backend is part of the key — reference and
        accelerated job tables compile different programs, while "auto"
        shares the slot of whatever it resolves to."""
        policy = get_policy(spec.policy)
        return (spec.battery, float(spec.scale), self.n_workers,
                policy.signature(), kernel_backends.resolve(spec.backend))

    def _table_key(self, spec: RunSpec) -> tuple:
        """Job-table key — deliberately WITHOUT the pool width: the table
        is a pure function of (battery, scale, decomposition, backend)."""
        policy = get_policy(spec.policy)
        return (spec.battery, float(spec.scale), policy.signature(),
                kernel_backends.resolve(spec.backend))

    def _compiled(self, spec: RunSpec) -> _Compiled:
        key = self._table_key(spec)
        hit = self._cache.get(key)
        if hit is None:
            entries = build_battery(spec.battery, spec.scale,
                                    backend=kernel_backends.resolve(
                                        spec.backend))
            policy = get_policy(spec.policy)
            # decompose is invoked WITHOUT the pool width: the job table
            # is shared across widths (checkpoint job ids and live runs
            # survive resize only because of that), so a width-dependent
            # decomposition is impossible by construction, not by
            # convention (SchedulePolicy protocol, DESIGN.md §6)
            jobs = policy.decompose(entries, None) or entries
            combine = getattr(policy, "combine", "stouffer")
            hit = _Compiled(entries, jobs, [j.cost for j in jobs],
                            combine, {})
            self._cache[key] = hit
        return hit

    def _runner(self, spec: RunSpec, n_gens: Optional[int] = None,
                captured: bool = False):
        """The jitted round program for this spec's shape: the current
        pool width x G generators. ``n_gens`` overrides the spec's width —
        adaptive runs shrink the mapped gen_ids axis as failed generators
        drop out — and each (width, G) pair is its own cached executable,
        so resizing back to a width seen before recompiles nothing.
        Specs carrying ``offsets`` compile the grid runner (the offset is
        a runtime argument, so ONE executable serves every cell offset of
        a campaign — wave after wave, knockout after knockout).

        Runner slots also carry the SWITCH WIDTH an executable was traced
        at: a ``lax.switch`` clamps out-of-range indices, so dispatching
        a later-registered generator through a narrower switch would
        silently run the wrong lane. ``spec.switch_lanes`` states the
        width this dispatch needs; any cached executable at least that
        wide is reused (registering a 10th generator retraces NOTHING for
        the built-in nine), a wider need compiles a fresh, wider switch.
        ``captured=True`` selects the prefetched-buffer program
        (``make_external_runner``) — no generator switch at all."""
        key = self.cache_key(spec)
        compiled = self._compiled(spec)
        g = spec.n_generators if n_gens is None else n_gens
        grid = spec.offsets is not None and not captured
        need = 0 if captured else spec.switch_lanes
        rk = (self.n_workers, g, grid, captured, need)
        runner = compiled.runners.get(rk)
        if runner is None:
            for (w, gg, gr, cap, lanes), r in compiled.runners.items():
                if ((w, gg, gr, cap) == (self.n_workers, g, grid, captured)
                        and lanes >= need):
                    runner = r
                    break
        if runner is None:
            def on_trace():
                self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
            if captured:
                runner = make_external_runner(compiled.jobs, self.mesh,
                                              on_trace=on_trace)
                lanes = 0
            else:
                make = (make_grid_runner if grid
                        else make_round_runner if g == 1
                        else make_fanout_runner)
                runner = make(compiled.jobs, self.mesh, on_trace=on_trace)
                lanes = registry_size()     # the switch traced THIS wide
            compiled.runners[(self.n_workers, g, grid, captured, lanes)] \
                = runner
        return runner

    def entries(self, spec: RunSpec) -> List[TestEntry]:
        """The spec's battery test table (test space, pre-decomposition) —
        what ``RunResult.results`` keys refer to."""
        return self._compiled(spec).entries

    def submit(self, spec: RunSpec) -> "BatteryRun":
        """condor_submit: plan the spec (resuming from its checkpoint if
        one exists) and hand back the run handle. Compilation is lazy —
        the first ``poll``/``result`` triggers it on a cache miss."""
        return BatteryRun(self, spec)


# ---------------------------------------------------------------------------
# run handle


class BatteryRun:
    """Streaming handle for one submitted spec (HTCondor verbs)."""

    def __init__(self, session: PoolSession, spec: RunSpec):
        self.session = session
        self.spec = spec
        self._compiled = session._compiled(spec)
        self._t0 = time.time()
        self.rounds_run = 0
        self.retries = 0
        self.driver_retries = 0
        self.plan_rounds = 0
        self.cancelled = False
        # fault domain (DESIGN.md §12): optional deterministic injector,
        # the event ledger, and the per-slot health/quarantine model —
        # all host-side, none of it visible to the compiled runners
        self._injector = (FaultInjector(spec.inject)
                          if spec.inject is not None else None)
        self.fault_events: List[FaultEvent] = []
        self.health = WorkerHealth()
        self.quarantines: List[dict] = []
        G = spec.n_generators
        self._results: List[Dict[int, tuple]] = [dict() for _ in range(G)]
        # verdict state under the spec's engine (stitch.VERDICT_ENGINES):
        # sticky per-generator decisions; a decided generator is dropped
        # from scheduling/dispatch when the spec asks for early stopping
        self._engine_fn = stitch.verdict_for(spec.verdict_engine)
        self._verdicts: List[stitch.Verdict] = [
            self._engine_fn({}, len(self._compiled.entries), spec.alpha)
            for _ in range(G)]
        # per-generator wealth trajectory, one sample per dispatched
        # round (evalue engine only — bonferroni has no wealth)
        self.wealth_history: List[List[float]] = [[] for _ in range(G)]
        self._restored_decisions: Optional[List[int]] = None
        self._restored_alpha: Optional[float] = None
        self._restored_engine: Optional[str] = None
        self._load_checkpoint()
        self._update_verdicts()
        if self._restored_decisions is not None:
            self._check_restored_verdicts()
        self._queue: List[np.ndarray] = []
        todo = self._missing()
        if todo:
            self._enqueue(todo, initial=True)

    # -- planning ----------------------------------------------------------

    def _active(self) -> List[int]:
        """Generator positions still being driven: everyone, minus the
        definitively-decided ones once ``stop_on_verdict`` is set."""
        if not self.spec.stop_on_verdict:
            return list(range(self.spec.n_generators))
        return [g for g in range(self.spec.n_generators)
                if not self._verdicts[g].decided]

    def _missing(self) -> List[int]:
        """Job-space HELD/missing set: union across ACTIVE generators
        (deterministic streams make duplicate re-execution for the others
        free; a verdict-decided generator stops contributing demand)."""
        n = len(self._compiled.jobs)
        held = set()
        for g in self._active():
            held.update(stitch.missing(self._results[g], n))
        return sorted(held)

    def _enqueue(self, todo: List[int], initial: bool = False) -> None:
        costs = self._compiled.costs
        jobs = self._compiled.jobs
        w = self.session.n_workers
        if initial and len(todo) == len(costs):
            plan = make_plan(costs, w, self.spec.policy, entries=jobs)
        else:
            plan = replan(todo, costs, w, self.spec.policy, entries=jobs)
        self.plan_rounds = self.plan_rounds or plan.rounds
        self._queue.extend(np.asarray(row, np.int32)
                           for row in plan.assignment)

    def _sync_width(self) -> None:
        """Elastic re-meshing: if the session was resized since this run's
        pending rounds were planned, replan the residual job set onto the
        new width at this round boundary. Completed results are untouched —
        job identity is width-independent (``pool.stream_table``), so the
        replan changes placement only, never which work remains."""
        w = self.session.n_workers
        if not self._queue or self._queue[0].shape[0] == w:
            return
        residual = sorted({int(j) for row in self._queue
                           for j in row if j >= 0})
        self._queue.clear()
        if residual:
            self._enqueue(residual)
            emit_progress(self.spec.progress,
                          f"  pool resized to {w} worker(s): {len(residual)} "
                          f"residual job(s) replanned onto "
                          f"{len(self._queue)} round(s)")

    # -- HTCondor verbs ----------------------------------------------------

    @property
    def pending_rounds(self) -> int:
        """Rounds still queued for dispatch."""
        return len(self._queue)

    @property
    def done(self) -> bool:
        """True when nothing is queued and no job is missing/held."""
        return not self._queue and not self._missing()

    def poll(self) -> dict:
        """Advance one round (one device dispatch covering every active
        generator) and report status — the paper's `master` polling
        `empty`. With ``stop_on_verdict`` each poll is also an interim
        look: decided generators leave the gen_ids axis, and the queue is
        dropped entirely once no generator remains undecided. A session
        ``resize()`` since the last poll is absorbed here: the residual
        rounds replan onto the new width before anything dispatches."""
        self._sync_width()
        self._auto_cancel()
        if self._queue:
            row = self._queue.pop(0)
            self._dispatch(row)
            self.rounds_run += 1
            self._update_verdicts()
            if self.spec.verdict_engine == "evalue":
                for g, v in enumerate(self._verdicts):
                    self.wealth_history[g].append(v.wealth)
            self._auto_cancel()
            self._save_checkpoint()
            if self.spec.progress:
                emit_progress(self.spec.progress,
                              f"  round {self.rounds_run}: "
                              f"{self._jobs_done()}/"
                              f"{len(self._compiled.jobs)} files generated")
        return self.status()

    def held(self) -> List[int]:
        """Job indices with missing/invalid results once the current plan
        is exhausted (paper: condor hold). A cancelled run holds nothing —
        its pending work is gone, not stuck."""
        return [] if (self._queue or self.cancelled) else self._missing()

    def verdict(self) -> Union[stitch.Verdict, Dict[str, stitch.Verdict]]:
        """The sequential verdict engine's current decision — a
        ``stitch.Verdict`` for a single-generator spec, else one per
        generator name. Valid after every round (Bonferroni-sequential
        spending, DESIGN.md §4), not just at completion."""
        self._update_verdicts()
        if self.spec.n_generators == 1:
            return self._verdicts[0]
        return {gen: self._verdicts[g]
                for g, gen in enumerate(self.spec.generators)}

    def results_by_position(self) -> List[Dict[int, tuple]]:
        """Combined TEST-space results per generator POSITION in the spec
        (sub-job groups folded back through the policy's combiner). The
        positional twin of ``verdicts_by_position`` — what the serve
        layer's demux slices a coalesced dispatch's results out of."""
        return [stitch.fold_groups(self._results[g], self._compiled.jobs,
                                   self._compiled.combine)
                for g in range(self.spec.n_generators)]

    def verdicts_by_position(self) -> List[stitch.Verdict]:
        """Interim verdicts indexed by generator POSITION in the spec.
        ``verdict()`` keys by name, which collapses a spec whose
        generators tuple repeats a name — exactly what a campaign grid
        does (one position per (generator, sub-stream) cell)."""
        self._update_verdicts()
        return list(self._verdicts)

    def cancel(self) -> int:
        """condor_rm: drop every pending round. Returns the number of
        rounds cancelled. Completed results (and the verdict state built
        from them) are kept; ``result()`` then finalizes immediately."""
        n = len(self._queue)
        self._queue.clear()
        self.cancelled = True
        self._save_checkpoint()
        return n

    def _check_restored_verdicts(self) -> None:
        """A v2 checkpoint's saved decisions must agree with the verdicts
        recomputed from its saved p-values — decisions are a pure function
        of results, so disagreement means the checkpoint was edited or
        written under a different alpha/battery."""
        if len(self._restored_decisions) != self.spec.n_generators:
            raise ValueError(
                f"checkpoint {self.spec.checkpoint_path} holds verdict "
                f"state for {len(self._restored_decisions)} generator(s), "
                f"spec has {self.spec.n_generators}")
        code = self._DECISION_CODE
        saved_alpha = self._restored_alpha
        for g, saved in enumerate(self._restored_decisions):
            if saved != code[self._verdicts[g].decision]:
                raise ValueError(
                    f"checkpoint {self.spec.checkpoint_path}: generator "
                    f"{self.spec.generators[g]!r} was saved as decision "
                    f"code {saved} (engine "
                    f"{self._restored_engine or self.spec.verdict_engine!r}, "
                    f"checkpoint alpha="
                    f"{'unrecorded' if saved_alpha is None else saved_alpha}"
                    f") but its saved results recompute to "
                    f"{self._verdicts[g].decision} under the spec's "
                    f"{self.spec.verdict_engine!r} engine at alpha="
                    f"{self.spec.alpha} — resumed with a different spec?")

    def _update_verdicts(self) -> None:
        """Recompute interim verdicts (test-space, after sub-job combine)
        under the spec's engine. Bonferroni decisions are sticky outright
        (a crossed boundary never un-crosses, so revisiting is pointless);
        evalue decisions are sticky only under ``stop_on_verdict``, where
        a decided generator's result set freezes — without early stopping
        wealth keeps moving as results land (e-values below 1 SHRINK it),
        and the final verdict must be the checkpoint-resumable pure
        function of the COMPLETE result set."""
        sticky = (self.spec.verdict_engine == "bonferroni"
                  or self.spec.stop_on_verdict)
        for g in range(self.spec.n_generators):
            if sticky and self._verdicts[g].decided:
                continue
            combined = stitch.fold_groups(self._results[g],
                                          self._compiled.jobs,
                                          self._compiled.combine)
            self._verdicts[g] = self._engine_fn(
                combined, len(self._compiled.entries), self.spec.alpha)

    def _auto_cancel(self) -> None:
        """stop_on_verdict: once every generator is decided, pending
        rounds are never dispatched."""
        if (self.spec.stop_on_verdict and self._queue
                and not self._active()):
            dropped = len(self._queue)
            self._queue.clear()
            self.cancelled = True
            emit_progress(self.spec.progress,
                          f"  verdict decided for all generators — "
                          f"{dropped} pending round(s) cancelled")

    def release(self) -> int:
        """condor_release: replan the HELD set. Returns #jobs released.

        A manual release is FREE with respect to the ``RetryPolicy``
        budget: ``retries`` counts every release pass (reporting truth),
        but the driver's own hold/release loop budgets against the
        separate ``driver_retries`` counter — a user who released once
        by hand does not get fewer automatic retries from ``result()``
        or ``stream()``."""
        h = self.held()
        if not h:
            return 0
        self.retries += 1
        self._enqueue(h)
        emit_progress(self.spec.progress,
                      f"  {len(h)} held tests released for retry")
        return len(h)

    def _driver_release(self) -> int:
        """A release initiated by the drive loop itself — the only kind
        that spends the ``RetryPolicy`` budget. Sleeps the policy's
        exponential backoff (``RetryPolicy.backoff_for``; 0.0 by
        default, so pre-existing drive loops stay sleepless) before
        replanning — the condor_release etiquette of not hammering a
        pool that is actively misbehaving."""
        delay = self.spec.retry.backoff_for(self.driver_retries)
        if delay > 0:
            emit_progress(self.spec.progress,
                          f"  backing off {delay:.2f}s before release "
                          f"pass {self.driver_retries + 1}")
            time.sleep(delay)
        self.driver_retries += 1
        return self.release()

    def drive(self, stop_when=None,
              raise_on_exhausted: bool = True) -> "BatteryRun":
        """The hold/release drive loop shared by ``result()``,
        ``stream()`` and the campaign phase driver: dispatch every queued
        round, then release-and-retry the HELD set until it clears or
        the ``RetryPolicy`` budget (driver-initiated releases only) is
        spent. ``stop_when`` is an optional ``handle -> bool`` predicate
        checked after every round; when it fires the remaining rounds
        are cancelled (the campaign uses it to stop a phase the moment
        every real cell's verdict is decided). Returns ``self``.

        Budget exhaustion with jobs still HELD raises
        ``RetryBudgetExhausted`` (carrying the final HELD job list)
        instead of silently finalising with missing results;
        ``raise_on_exhausted=False`` restores the old give-up behaviour
        for callers that treat a stalled run as data (the campaign
        phase driver, the serve daemon's failed-ticket path)."""
        while True:
            while self._queue:
                self.poll()
                if stop_when is not None and stop_when(self):
                    self.cancel()
                    break
            if self.done or self.cancelled:
                break
            held = self.held()
            if not held:
                break
            if self.driver_retries >= self.spec.retry.max_retries:
                if raise_on_exhausted:
                    raise RetryBudgetExhausted(held, self.driver_retries)
                break
            self._driver_release()
        return self

    def stream(self) -> Iterator[dict]:
        """Yield one status per round until the run completes — INCLUDING
        hold/release retry rounds, exactly like ``result()``'s drive
        loop, so a streaming client sees the retries instead of the
        stream ending silently while jobs are still HELD. Like
        ``drive()``, budget exhaustion with jobs still HELD raises
        ``RetryBudgetExhausted``."""
        while True:
            while self._queue:
                yield self.poll()
            if self.done or self.cancelled:
                return
            held = self.held()
            if not held:
                return
            if self.driver_retries >= self.spec.retry.max_retries:
                raise RetryBudgetExhausted(held, self.driver_retries)
            self._driver_release()

    def result(self) -> Union[RunResult, BatteryResult]:
        """Drive to completion (rounds + hold/release retries) and stitch.
        Returns ``RunResult`` for a single-generator spec, ``BatteryResult``
        otherwise."""
        return self.drive()._finalize()

    def status(self) -> dict:
        """One condor_q-shaped snapshot: state, job/round counters, the
        HELD set and the per-generator interim verdicts. Cancellation is
        STICKY: a cancelled run reports ``"cancelled"`` even when every
        job it executed happens to have completed (``done`` must not win
        the ladder — condor_rm'ing a finished queue is still a rm)."""
        state = ("cancelled" if self.cancelled
                 else "done" if self.done
                 else "running" if self._queue else "held")
        return {"state": state, "jobs_done": self._jobs_done(),
                "jobs_total": len(self._compiled.jobs),
                "pending_rounds": len(self._queue),
                "rounds_run": self.rounds_run, "retries": self.retries,
                "held": self.held(),
                "verdicts": {gen: self._verdicts[g].decision
                             for g, gen in enumerate(self.spec.generators)}}

    # -- execution ---------------------------------------------------------

    def _jobs_done(self) -> int:
        """Jobs with results for EVERY generator — reporting truth, not
        scheduling demand (_missing spans only active generators, so a
        cancelled generator's unexecuted jobs must not read as done)."""
        n = len(self._compiled.jobs)
        undone = set()
        for res in self._results:
            undone.update(stitch.missing(res, n))
        return n - len(undone)

    def _dispatch(self, row: np.ndarray) -> None:
        """One round's dispatches covering the ACTIVE generators. When
        early stopping has decided some of a fan-out's generators, the
        dispatch shrinks to the survivors — the mapped gen_ids axis
        narrows, the failed generator's remaining tests are never
        executed. Positions backed by a ``CapturedSource`` dispatch
        through the prefetched-buffer program (their bits are gathered
        host-side from the memory-mapped capture), switch-backed
        positions through the classic generator switch — at most one
        device dispatch per family per round."""
        active = self._active()
        if not active:
            return
        srcs = self.spec.sources
        switched = [g for g in active if not srcs[g].captured]
        captured = [g for g in active if srcs[g].captured]
        per_gen = []
        if switched:
            runner = self.session._runner(self.spec, n_gens=len(switched))
            if self.spec.offsets is not None:
                seeds = np.asarray([self.spec.seeds[g] for g in switched],
                                   np.int32)
                gids = np.asarray([srcs[g].gen_id for g in switched],
                                  np.int32)
                offs = split_offsets([self.spec.offsets[g]
                                      for g in switched])
                stats, ps = runner(row, seeds, gids, offs)
                stats, ps = np.asarray(stats), np.asarray(ps)
                per_gen += [(g, stats[a], ps[a])
                            for a, g in enumerate(switched)]
            elif len(switched) == 1:
                g0 = switched[0]
                stats, ps = runner(row, np.int32(self.spec.seeds[g0]),
                                   np.int32(srcs[g0].gen_id))
                per_gen.append((g0, np.asarray(stats), np.asarray(ps)))
            else:
                seeds = np.asarray([self.spec.seeds[g] for g in switched],
                                   np.int32)
                gids = np.asarray([srcs[g].gen_id for g in switched],
                                  np.int32)
                stats, ps = runner(row, seeds, gids)
                stats, ps = np.asarray(stats), np.asarray(ps)
                per_gen += [(g, stats[a], ps[a])
                            for a, g in enumerate(switched)]
        if captured:
            runner = self.session._runner(self.spec, n_gens=len(captured),
                                          captured=True)
            lanes = [(srcs[g], self.spec.seeds[g],
                      None if self.spec.offsets is None
                      else self.spec.offsets[g]) for g in captured]
            bits = gather_captured_bits(self._compiled.jobs, row, lanes)
            stats, ps = runner(row, bits)
            stats, ps = np.asarray(stats), np.asarray(ps)
            per_gen += [(g, stats[a], ps[a])
                        for a, g in enumerate(captured)]
        # ---- fault domain (DESIGN.md §12): everything below is host-side
        # post-processing of materialised numpy results — the compiled
        # runners above never see a fault, a gate, or a quarantine
        injected: List[FaultEvent] = []
        resize_to: Optional[int] = None
        if self._injector is not None:
            per_gen = [(g, np.array(st, np.float64), np.array(pv, np.float64))
                       for g, st, pv in per_gen]
            injected, resize_to = inject_round_faults(
                self._injector, self.rounds_run, row,
                [(st, pv) for _, st, pv in per_gen],
                deadline=self.spec.retry.deadline)
            self.fault_events.extend(injected)
            for ev in injected:
                emit_progress(self.spec.progress,
                              f"  fault[{ev.kind}] round {ev.round} "
                              f"slot {ev.slot} job {ev.job}: {ev.detail}")
        per_gen, gate_events = self._sanity_gate(row, per_gen, injected)
        if resize_to is not None and resize_to != self.session.n_workers:
            emit_progress(self.spec.progress,
                          f"  worker lost: pool resizes to {resize_to}")
            self.session.resize(resize_to)
        self._update_health(row, injected + gate_events)
        for g, st, pv in per_gen:
            self._results[g] = stitch.fold(row[None, :], st[None, :],
                                           pv[None, :], self._results[g])

    def _sanity_gate(self, row: np.ndarray, per_gen: list,
                     injected: List[FaultEvent]) -> tuple:
        """The result sanity gate: a non-idle slot whose stat or p is
        non-finite, or whose p falls outside [0, 1], is a corrupt
        result. It is nulled to NaN — so ``stitch.missing`` marks the
        job HELD and the retry machinery re-executes it — and recorded
        in the fault ledger as a ``corrupt_result`` event carrying the
        :class:`CorruptResultError` text. Silent corruption therefore
        becomes HELD+retry, never a wrong verdict. Slots an injected
        ``evict``/deadline-exceeded ``straggle`` already nulled this
        round are skipped (they are accounted faults, not corruption).
        Returns ``(per_gen, gate_events)``."""
        nulled = {ev.slot for ev in injected
                  if ev.kind == "evict"
                  or (ev.kind == "straggle" and "HELD" in ev.detail)}
        row = np.asarray(row)
        events: List[FaultEvent] = []
        out = []
        for g, st, pv in per_gen:
            st, pv = np.asarray(st), np.asarray(pv)
            bad = (row >= 0) & ~(np.isfinite(st) & np.isfinite(pv)
                                 & (pv >= 0.0) & (pv <= 1.0))
            for w in np.nonzero(bad)[0]:
                bad[w] = int(w) not in nulled
            if bad.any():
                st = np.array(st, np.float64)
                pv = np.array(pv, np.float64)
                for w in np.nonzero(bad)[0]:
                    err = CorruptResultError(
                        f"job {int(row[w])} (slot {int(w)}, generator "
                        f"position {g}) returned stat={float(st[w])!r} "
                        f"p={float(pv[w])!r}; p must be finite and in "
                        f"[0, 1] — result quarantined to HELD")
                    events.append(FaultEvent(
                        self.rounds_run, "corrupt_result", int(w),
                        int(row[w]), -1, str(err)))
                    emit_progress(self.spec.progress,
                                  f"  corrupt result gated: {err}")
                st[bad] = np.nan
                pv[bad] = np.nan
            out.append((g, st, pv))
        self.fault_events.extend(events)
        return out, events

    def _update_health(self, row: np.ndarray,
                       events: List[FaultEvent]) -> None:
        """Advance the per-slot health model with this round's outcome
        and quarantine flaky slots. Every non-idle slot either faulted
        (an injected evict/corrupt/straggle or a gated corrupt result
        landed on it) or ran clean; a slot whose consecutive-fault
        streak reaches ``RetryPolicy.quarantine_after`` is removed from
        the pool via the elastic ``resize`` path (floored at one
        worker), and its residual jobs replan onto the survivors at the
        next round boundary. After the re-mesh slot identities change,
        so all streaks reset."""
        faulted = {int(ev.slot) for ev in events if ev.slot >= 0}
        for w in range(row.shape[0]):
            if int(row[w]) >= 0:
                self.health.record(w, w in faulted)
        qa = self.spec.retry.quarantine_after
        if not qa:
            return
        flaky = self.health.flaky(qa)
        cur = self.session.n_workers
        if not flaky or cur <= 1:
            return
        new_w = max(1, cur - len(flaky))
        self.quarantines.append({"round": self.rounds_run,
                                 "slots": flaky, "workers": new_w})
        self.fault_events.append(FaultEvent(
            self.rounds_run, "quarantine", flaky[0], -1, -1,
            f"slot(s) {flaky} quarantined after {qa} consecutive "
            f"fault(s); pool shrinks to {new_w} worker(s)"))
        emit_progress(self.spec.progress,
                      f"  slot(s) {flaky} quarantined — pool shrinks "
                      f"to {new_w} worker(s)")
        self.health.reset()
        self.session.resize(new_w)

    # -- checkpointing -----------------------------------------------------

    _DECISION_CODE = {stitch.UNDECIDED: 0, stitch.PASS: 1, stitch.FAIL: 2}

    def _save_checkpoint(self) -> None:
        """Write the v5 layout: results keyed by JOB ID (never by the
        (round, worker) position of the dispatch that produced them), so
        the file is a pure function of the job table and resumes on any
        pool width. Verdict state always rides along — tagged with the
        engine that computed it, plus the per-generator wealth snapshot
        under the evalue engine; ``rounds_run`` is adopted on resume
        only by ``stop_on_verdict`` runs (their round count is part of
        the sequential-look bookkeeping)."""
        path = self.spec.checkpoint_path
        if not path:
            return
        idx = np.array(sorted(set().union(*[set(r) for r in self._results])),
                       np.int32)
        st = np.array([[r.get(int(i), (np.nan, np.nan))[0] for i in idx]
                       for r in self._results], np.float64)
        pv = np.array([[r.get(int(i), (np.nan, np.nan))[1] for i in idx]
                       for r in self._results], np.float64)
        decisions = np.array([self._DECISION_CODE[v.decision]
                              for v in self._verdicts], np.int8)
        uids = np.asarray([s.uid().encode() for s in self.spec.sources])
        lw = None
        if self.spec.verdict_engine == "evalue":
            lw = np.array([v.log_wealth for v in self._verdicts], np.float64)
        Checkpoint(idx, st, pv, decisions, self.rounds_run,
                   alpha=self.spec.alpha, source_uids=uids,
                   engine=self.spec.verdict_engine,
                   log_wealth=lw).save(path)

    def _load_checkpoint(self) -> None:
        path = self.spec.checkpoint_path
        if not (path and ckpt_io.exists(path)):
            return
        ck = Checkpoint.load(path)          # v1..v4 upgrade path lives here
        # Saved decisions are BINDING only for a stop_on_verdict run that
        # uses the SAME alpha they were computed under — there they drive
        # scheduling (decided generators are never re-enqueued) and the
        # round count is sequential-look bookkeeping, and the cross-check
        # catches tampering. Under any other (spec, alpha) they are
        # advisory: verdicts are a pure function of (results, alpha), so
        # the resumed run just recomputes them fresh. v2 files predate
        # the recorded alpha (ck.alpha is None) and keep their
        # documented refuse-on-mismatch behavior. Decisions made by a
        # DIFFERENT verdict engine are never comparable — not even
        # advisorily — so an engine mismatch on verdict-bearing state is
        # a typed refusal, not a silent recompute.
        if (ck.decisions is not None and self.spec.stop_on_verdict
                and ck.engine != self.spec.verdict_engine):
            raise stitch.VerdictEngineMismatch(
                f"checkpoint {path} holds verdict state computed by the "
                f"{ck.engine!r} engine (alpha="
                f"{'unrecorded' if ck.alpha is None else ck.alpha}) but "
                f"the spec resumes with verdict_engine="
                f"{self.spec.verdict_engine!r} (alpha={self.spec.alpha}) "
                f"— the engines' decisions are not comparable; re-run "
                f"from scratch or resume with the original engine")
        if (ck.decisions is not None and self.spec.stop_on_verdict
                and (ck.alpha is None or ck.alpha == self.spec.alpha)):
            self._restored_decisions = [int(d) for d in ck.decisions]
            self._restored_alpha = ck.alpha
            self._restored_engine = ck.engine
            self.rounds_run = ck.rounds_run
        if ck.n_generators != self.spec.n_generators:
            raise ValueError(
                f"checkpoint {path} holds {ck.n_generators} generator "
                f"row(s), spec has {self.spec.n_generators}")
        if ck.source_uids is not None:
            saved = [u.decode() for u in np.asarray(ck.source_uids)]
            want = [s.uid() for s in self.spec.sources]
            if saved != want:
                raise ValueError(
                    f"checkpoint {path} was written against sources "
                    f"{saved}, spec names {want} — for a captured source "
                    f"the uid embeds the file's content digest, so a "
                    f"re-captured (byte-different) file must re-run, "
                    f"never resume")
        if len(ck.job_idx) and int(np.max(ck.job_idx)) >= len(self._compiled.jobs):
            raise ValueError(
                f"checkpoint {path} references job {int(np.max(ck.job_idx))} "
                f"but this spec's job table has {len(self._compiled.jobs)} "
                "entries — it was written by a different battery/scale/"
                "decomposition")
        self._results = ck.results()

    # -- stitching ---------------------------------------------------------

    def _finalize(self) -> Union[RunResult, BatteryResult]:
        wall = time.time() - self._t0
        self._update_verdicts()
        per_pos = self.results_by_position()
        runs: Dict[str, RunResult] = {}
        for g, gen in enumerate(self.spec.generators):
            combined = per_pos[g]
            rep = stitch.report(self._compiled.entries, combined, gen,
                                self.spec.seeds[g])
            runs[gen] = RunResult(combined, rep, self.rounds_run,
                                  self.retries, wall, self.plan_rounds,
                                  verdict=self._verdicts[g])
        if self.spec.n_generators == 1:
            return runs[self.spec.generators[0]]
        return BatteryResult(self.spec, runs, self.rounds_run, self.retries,
                             wall)
