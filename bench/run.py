"""The benchmark: one cell, one run, one JSON result line.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a configuration (``bench/configs/<config>.json``: battery,
scale, pool width, policy) under a traffic mix
(``bench/traffic/<traffic>.json``). The traffic file names its driver,
``bench/drivers/<driver>.py``, which reads the file's parameters and
drives the window through ``Window``; a new kind of traffic is a new
driver file, found by name. Every request goes through the path a user
drives: ``PoolSession(n_workers=W).submit(RunSpec(...))``, then
``BatteryRun.poll`` round by round and ``BatteryRun.result``. Request
``i`` is ``request(config, traffic, --seed, i)``: the seed changes the
bits and nothing else.

Set-up (imports, the backend, the pool, the warm-up round that compiles
the round program or loads it from the persistent compile cache) is
``setup_s``, counted from the start of this module. Then the window runs
for ``--seconds``; it ends with the first round that returns after that,
and its length is the time to the end of that round. After the window
the harness checks a sample of the completed requests, drawn from
``--seed``, lane by lane against the plain reference
(``bench/reference.py``), and prints the result.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` one block of the window's rounds runs under the profiler
(``Profiler``) and the metrics are the cell's per-layer metrics, each
read by ``bench/metrics/<name>.py`` from that block, set-up's compile
clock, or the window's round times.

Exit codes: 0 with a result line; 1 when JAX finds no accelerator or
fewer chips than the cell asks for; 2 when the program under test is not
in the checkout. No result line is printed then.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse  # noqa: E402
import collections  # noqa: E402
import concurrent.futures  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402
from bench import roofline  # noqa: E402
from bench.trace import Trace, self_times  # noqa: E402

# Words of completed requests that the check runs the reference over,
# at most: two BigCrush verdicts at scale 16, a few hundred SmallCrush.
CHECK_WORDS = 320_000_000
CHECK_THREADS = 4

# Families whose test runs one device loop step per word. The profiler
# records every step as an op, so the traced block holds at most
# TRACE_SERIAL_STEPS of them (about a million events), and at most
# TRACE_ROUNDS rounds.
SERIAL_FAMILIES = ("coupon",)
TRACE_SERIAL_STEPS = 1 << 19
TRACE_ROUNDS = 128

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def _annotate(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class ProgramMissing(RuntimeError):
    """The program under test is not in the checkout."""


# ---------------------------------------------------------------------------
# the cell, found by name


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_module(kind: str, name: str, bench_dir: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module."""
    if not NAME.fullmatch(name):
        raise ValueError(f"not a name: {name!r}")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"[.-]", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, bench_dir: str = BENCH):
    """The driver module ``<bench_dir>/drivers/<name>.py``: it has
    ``validate(traffic)``, which refuses a key or a value it does not
    read, and ``drive(window)``."""
    return _load_module("drivers", name, bench_dir)


def load_cell(workload: str, bench_dir: str = BENCH,
              root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, its
    configuration ``<bench_dir>/configs/<config>.json``, its traffic
    ``<bench_dir>/traffic/<traffic>.json`` and that traffic's driver, and
    the metrics it reports."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    with open(os.path.join(bench_dir, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if "driver" not in traffic:
        raise ValueError(f"traffic {w['traffic']!r} names no driver")
    driver = load_driver(traffic["driver"], bench_dir)
    driver.validate(traffic)
    return Cell(workload, int(w["chips"]), config, traffic, driver,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)])


def quantity(name: str) -> str:
    """What a metric measures: a name ``<quantity>.<group>`` is that
    quantity in a group of cells that has a bound, or moves an end-to-end
    metric, of its own (``words_per_s.smallcrush``)."""
    return name.split(".", 1)[0]


def load_reader(name: str, bench_dir: str = BENCH) -> Callable:
    """``read(ctx)`` of ``<bench_dir>/metrics/<quantity>.py``."""
    return _load_module("metrics", quantity(name), bench_dir).read


# ---------------------------------------------------------------------------
# requests


def request_seed(seed: int, i) -> int:
    """The ``RunSpec`` seed of request ``i`` under run seed ``seed``: 31
    bits of SHA-256, so any run seed (however large) maps into the
    program's int32 seed argument."""
    h = hashlib.sha256(f"{int(seed)}:{i}".encode()).digest()
    return int.from_bytes(h[:4], "big") & 0x7FFFFFFF


def request(config: dict, traffic: dict, seed: int, i) -> dict:
    """The ``RunSpec`` arguments of request ``i``: the traffic's
    ``generators`` as its lanes, each with a seed of its own. Everything
    but ``seeds`` comes from the cell, so two run seeds give the same
    requests in every other field (battery, scale, generators, policy),
    and so the same words, in the same order."""
    gens = tuple(traffic["generators"])
    seeds = (request_seed(seed, i) if len(gens) == 1 else
             tuple(request_seed(seed, f"{i}:{g}") for g in range(len(gens))))
    return {"battery": config["battery"], "scale": config["scale"],
            "generators": gens, "seeds": seeds, "policy": config["policy"],
            "alpha": config["alpha"], "backend": config["backend"],
            "stop_on_verdict": config["stop_on_verdict"],
            "verdict_engine": config["verdict_engine"]}


@dataclasses.dataclass
class Lane:
    """One generator of a completed request, as the program stitched it."""
    generator: str
    seed: int
    results: Dict[int, tuple]
    decision: str
    failed_tests: tuple


@dataclasses.dataclass
class Record:
    """One request: its seeds, latency, and each lane's stitched results
    (``lanes`` is None until it completes)."""
    i: int
    seed: object                    # an int, or one per lane
    generators: tuple = ()
    latency_s: float = 0.0
    lanes: Optional[List[Lane]] = None
    rounds: int = 0
    retries: int = 0
    fault_events: int = 0
    error: str = ""

    def lane_seeds(self) -> tuple:
        if isinstance(self.seed, int):
            return (self.seed,) * len(self.generators)
        return tuple(self.seed)


def finish(rec: Record, handle, t_sub: float) -> None:
    """Stitch a request whose rounds are done into its record."""
    with _annotate("stitch"):
        res = handle.result()
    rec.latency_s = time.perf_counter() - t_sub
    runs = getattr(res, "runs", None) or {rec.generators[0]: res}
    rec.lanes = [Lane(g, s, dict(runs[g].results), runs[g].verdict.decision,
                      tuple(runs[g].verdict.failed_tests))
                 for g, s in zip(rec.generators, rec.lane_seeds())]
    rec.rounds, rec.retries = res.rounds_run, res.retries
    rec.fault_events = len(handle.fault_events)


def done_tests(handle) -> set:
    """Tests of a request that have a result, over all its lanes."""
    return {t for res in handle.results_by_position()
            for t, (_, p) in res.items() if np.isfinite(p)}


# ---------------------------------------------------------------------------
# set-up


class CompileClock:
    """JAX's compile-phase seconds, compile count and persistent-cache
    hits and misses (``jax.monitoring``), between ``reset`` calls."""

    def __init__(self, jax):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.secs = {v: 0.0 for v in COMPILE_EVENTS.values()}
        self.compiles = 0
        self.cache = {v: 0 for v in CACHE_EVENTS.values()}

    def _dur(self, event, duration, **_):
        kind = COMPILE_EVENTS.get(event)
        if kind:
            self.secs[kind] += duration
            self.compiles += kind == "compile"

    def _event(self, event, **_):
        kind = CACHE_EVENTS.get(event)
        if kind:
            self.cache[kind] += 1


def _device_info(jax, devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


@dataclasses.dataclass
class Pool:
    """What set-up leaves for the window: the program's session and
    ``RunSpec``, the reference's test table, JAX's compile clock, and
    set-up's parts in seconds."""
    jax: object
    RunSpec: type
    session: object
    devices: list
    table: list
    table_mismatch: int
    clock: CompileClock
    setup_clock: dict
    parts: dict


def open_pool(cell: Cell, seed: int, root: str = ROOT,
              need_accelerator: bool = True) -> Pool:
    """Import the program, check the chips, build the pool and run one
    warm-up round, which compiles the round program (or loads it from
    the persistent compile cache at ``<root>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` says)."""
    config, traffic = cell.config, cell.traffic
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ProgramMissing(f"no program under test at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    # libtpu logs under the run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    parts = {}
    t = time.perf_counter()
    import jax
    parts["import_jax"] = time.perf_counter() - t
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t = time.perf_counter()
    devices = jax.devices()
    parts["backend"] = time.perf_counter() - t
    if need_accelerator and devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"the cell asks for {cell.chips} chips; JAX "
                            f"sees {len(devices)}")
    clock = CompileClock(jax)
    t = time.perf_counter()
    from repro.core.api import PoolSession, RunSpec

    session = PoolSession(n_workers=config["n_workers"])
    mesh_devs = list(session.mesh.devices.flat)
    if len({d.id for d in mesh_devs}) != config["n_workers"]:
        raise RuntimeError(f"the pool spans {len(mesh_devs)} devices, not "
                           f"{config['n_workers']}")
    table = reference.battery(config["battery"], config["scale"])
    warm = RunSpec(**request(config, traffic, seed, "warm-up"))
    entries = session.entries(warm)
    table_mismatch = sum(
        e.name != reference.test_name(k, p) or e.n_words != w
        for e, (k, p, w) in zip(entries, table)) + abs(
        len(entries) - len(table)) + (len(table) != config["tests"]) + (
        sum(w for _, _, w in table) != config["words_per_generator"])
    parts["pool"] = time.perf_counter() - t
    t = time.perf_counter()
    run = session.submit(warm)
    run.poll()
    run.cancel()
    parts["warm_up"] = time.perf_counter() - t
    return Pool(jax, RunSpec, session, mesh_devs, table, table_mismatch,
                clock, dict(clock.secs, **clock.cache), parts)


# ---------------------------------------------------------------------------
# the window


class Profiler:
    """Puts one block of whole polls of the window under the profiler.

    ``plan`` gets the serial steps of each round of a request, learned
    from the window's first request: every request of a cell has the
    same plan, and requests run one after another. The block starts
    right after the last round of the second request that runs a serial
    family's test, and takes the polls that follow, across request
    boundaries, while the serial steps they hold stay within
    ``TRACE_SERIAL_STEPS``. The block is the ``traced`` span."""

    def __init__(self, jax):
        self.jax = jax
        self.block = None                   # [first, end) window polls
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    @property
    def active(self) -> bool:
        return self._span is not None

    def plan(self, steps: List[int]) -> None:
        n = len(steps)
        if not n:
            return
        serial = [r for r, st in enumerate(steps) if st]
        first = n + (serial[-1] + 1 if serial else 0)
        end, used = first, 0
        while end - first < TRACE_ROUNDS:
            used += steps[end % n]
            if used > TRACE_SERIAL_STEPS:
                break
            end += 1
        self.block = [first, end]

    def before_poll(self, g: int) -> None:
        """Window poll ``g`` is about to run."""
        if self.block and g == self.block[0] and self._span is None:
            self.jax.profiler.start_trace(self.dir)
            self._span = _annotate("traced")
            self._span.__enter__()
        elif self.block and g == self.block[1]:
            self.close()

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self._span = None
            self.block = None

    def load(self) -> Optional[Trace]:
        found = []
        for d, _, files in os.walk(self.dir):
            found += [os.path.join(d, f) for f in files
                      if f.endswith(".xplane.pb")]
        try:
            return Trace.load(found[0]) if len(found) == 1 else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Request:
    """A request a driver holds: its record and the program's handle."""
    rec: Record
    handle: object = None
    t_sub: float = 0.0

    @property
    def pending(self) -> int:
        if self.rec.error or self.handle is None:
            return 0
        return self.handle.pending_rounds


class Window:
    """The measured window, as a driver (``bench/drivers/<name>.py``)
    drives it: ``submit`` the next request, ``poll`` one round of a
    request, ``finish`` a request whose rounds are done (or that raised),
    and, once ``expired()``, ``close`` each request still in flight.

    The window keeps the records and counts polls and the words of an
    in-flight request's finished tests. In a traced run it also times
    every poll outside the traced block, learns the round plan from
    request 0, and drives the profiler. A request that raises is
    recorded as failed."""

    def __init__(self, cell: Cell, pool: Pool, seed: int, seconds: float,
                 prof: Optional[Profiler]):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.session, self.RunSpec = pool.session, pool.RunSpec
        self.table = pool.table
        self.words_per_test = [w for _, _, w in pool.table]
        self.prof = prof
        self.records: List[Record] = []
        self.partial_words = 0
        self.in_flight = 0
        self.polls = 0
        self.poll_s: List[tuple] = []       # (round of its request, s)
        self.plan: Optional[List[set]] = None
        self._learn: List[set] = []
        self._seen: set = set()
        self._next = 0
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds

    def expired(self) -> bool:
        return time.perf_counter() >= self.deadline

    def submit(self) -> Request:
        i = self._next
        self._next += 1
        req = request(self.config, self.traffic, self.seed, i)
        r = Request(Record(i, req["seeds"], req["generators"]),
                    t_sub=time.perf_counter())
        try:
            with _annotate("submit"):
                r.handle = self.session.submit(self.RunSpec(**req))
        except Exception as exc:  # a request that raises is failed
            r.rec.error = f"{type(exc).__name__}: {exc}"
        return r

    def poll(self, r: Request) -> None:
        h = r.handle
        k = h.rounds_run
        if self.prof:
            self.prof.before_poll(self.polls)
        traced = self.prof is not None and self.prof.active
        t = time.perf_counter()
        try:
            with _annotate("poll"):
                h.poll()
        except Exception as exc:
            r.rec.error = f"{type(exc).__name__}: {exc}"
        dt = time.perf_counter() - t
        self.polls += 1
        if self.prof is None:
            return
        if not traced:
            self.poll_s.append((k, dt))
        if r.rec.i == 0 and not r.rec.error:
            new = done_tests(h) - self._seen
            self._seen |= new
            self._learn.append(new)

    def finish(self, r: Request) -> None:
        if not r.rec.error:
            try:
                finish(r.rec, r.handle, r.t_sub)
            except Exception as exc:
                r.rec.error = f"{type(exc).__name__}: {exc}"
        self.records.append(r.rec)
        if self.prof and r.rec.i == 0 and not r.rec.error:
            self.plan = self._learn
            self.prof.plan([sum(w for t, (k, _, w) in enumerate(self.table)
                                if t in new and k in SERIAL_FAMILIES)
                            for new in self.plan])

    def close(self, r: Request) -> None:
        """A request still in flight when the window closes: the words of
        its finished tests count, the request does not."""
        if r.pending:
            self.partial_words += sum(
                self.words_per_test[t]
                for res in r.handle.results_by_position()
                for t, (_, p) in res.items() if np.isfinite(p))
            self.in_flight += 1

    def round_times(self) -> List[tuple]:
        """``(families, seconds)`` of each timed poll: the families of
        the tests that round runs in the plan, and its wall time."""
        plan = self.plan or []
        fams = [frozenset(self.table[t][0] for t in new) for new in plan]
        return [(fams[k] if k < len(fams) else frozenset(), s)
                for k, s in self.poll_s]


def thread_cpu() -> Dict[tuple, float]:
    """CPU seconds (user + system) of each live thread of this process,
    keyed by ``(thread id, name)``; empty where ``/proc`` is missing."""
    tick = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
    out = {}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return out
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                s = f.read()
        except OSError:
            continue
        name = s[s.index("(") + 1:s.rindex(")")]
        fields = s[s.rindex(")") + 2:].split()
        out[(tid, name)] = (int(fields[11]) + int(fields[12])) / tick
    return out


def cpu_by_thread_name(before: dict, after: dict) -> List[tuple]:
    """CPU seconds spent between two ``thread_cpu`` readings, summed by
    thread name, largest first."""
    by = collections.Counter()
    for key, s in after.items():
        by[key[1]] += s - before.get(key, 0.0)
    return [(n, s) for n, s in by.most_common() if s > 0]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             bench_dir: str = BENCH, root: str = ROOT,
             need_accelerator: bool = True, t_start: Optional[float] = None,
             log=print) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    t_cell = time.perf_counter()
    cell = load_cell(workload, bench_dir, root)
    config = cell.config
    pool = open_pool(cell, seed, root, need_accelerator)
    jax, session = pool.jax, pool.session
    table, clock, setup_clock = pool.table, pool.clock, pool.setup_clock
    mesh_devs = pool.devices
    words_per_lane = sum(w for _, _, w in table)
    peaks = roofline.peaks_for(mesh_devs[0].device_kind) if trace else None

    # -- the window --------------------------------------------------------
    prof = Profiler(jax) if trace else None
    gc.collect()
    clock.reset()
    threads0 = thread_cpu()
    cpu0 = time.process_time()
    w = Window(cell, pool, seed, seconds, prof)
    t0 = w.t0
    cell.driver.drive(w)
    t1 = time.perf_counter()
    cpu_s = time.process_time() - cpu0
    threads = cpu_by_thread_name(threads0, thread_cpu())
    window_s = t1 - t0
    compiles = clock.compiles
    if prof:
        prof.close()
    device = _device_info(jax, mesh_devs)

    # -- end-to-end metrics -----------------------------------------------
    records = w.records
    done = [r for r in records if r.lanes is not None]
    failed = [r for r in records
              if r.lanes is None or len(r.lanes) != len(r.generators)
              or any(not np.isfinite(lane.results.get(t, (0, np.nan))[1])
                     for lane in r.lanes for t in range(len(table)))]
    words = words_per_lane * sum(len(r.generators) for r in done) \
        + w.partial_words
    setup_s = t0 - t_start
    e2e = {"words_per_s": words / window_s, "setup_s": setup_s}
    if words:
        e2e["host_cpu_ms_per_Mword"] = cpu_s * 1e3 / (words / 1e6)
    lat = [r.latency_s for r in done]
    if len(lat) >= 2:
        e2e["verdict_p95_s"] = statistics.quantiles(
            lat, n=100, method="inclusive")[94]

    per_req = collections.Counter(
        (r.rounds, r.retries, r.fault_events) for r in done)
    log(f"requests: {len(done)} completed, {len(failed)} failed, "
        f"{w.in_flight} in flight at the close ({w.partial_words} words "
        f"of their finished tests count); {words_per_lane} words per lane; "
        "(rounds, retries, fault events) per request: "
        + ", ".join(f"{k}: {v}" for k, v in sorted(per_req.items())))
    for r in records:
        if r.error:
            log(f"request {r.i} (seed {r.seed}) failed: {r.error}")
    log(f"window: {window_s:.3f} s, {w.polls} rounds, {len(done)} verdicts, "
        f"process CPU {cpu_s:.3f} s; compiles in the window: {compiles}")
    log("window CPU by thread: " + ", ".join(
        f"{n} {s:.2f} s" for n, s in threads[:8]))
    parts = dict(pool.parts, imports=t_cell - t_start)
    parts["other"] = setup_s - sum(parts.values())
    setup = {"setup_s": setup_s, **{k: parts[k] for k in (
        "imports", "import_jax", "backend", "pool", "warm_up", "other")},
        **setup_clock}
    log("set-up: " + ", ".join(f"{k} {v:.3f}" if isinstance(v, float)
                               else f"{k} {v}" for k, v in setup.items()))

    out = {"attempted": len(records), "failed": len(failed),
           "device": device}
    rounds = w.round_times()
    tr = prof.load() if prof else None
    if tr is not None:
        ctx = Context(tr, setup_clock, peaks, config["n_workers"], rounds)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"], bench_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"].update(busy_s=ctx.busy_s(), window_s=ctx.window_s())
        out["breakdown"] = ctx.breakdown()
    elif trace:
        out["metrics"] = {}
        log("trace: the window closed before the traced block began")
    else:
        out["metrics"] = {m["name"]: {"value": e2e[quantity(m["name"])],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end
                          if quantity(m["name"]) in e2e}
    out["setup"] = setup

    # -- correctness, once the window has closed and the pool is freed ----
    table_mismatch = pool.table_mismatch
    del session, pool, w
    gc.collect()
    checks = check(done, failed, table, config, seed, table_mismatch)
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    return out


# ---------------------------------------------------------------------------
# what the metric readers get


class Context:
    """The traced block, set-up's compile seconds, the peaks of the
    device, and the wall time of each round of the window outside the
    block, for ``bench/metrics/<name>.py``'s ``read(ctx)``."""

    def __init__(self, trace: Trace, setup_clock: dict, peaks: dict,
                 n_workers: int, rounds=()):
        self.trace = trace
        self.setup_clock = setup_clock
        self.peaks = peaks
        self.n_workers = n_workers
        self.rounds = list(rounds)      # (families, seconds) per poll
        self._busy = {}

    def device_ids(self) -> List[int]:
        return sorted(self.trace.devices)[:self.n_workers]

    def busy(self, device: int):
        if device not in self._busy:
            self._busy[device] = self.trace.busy(device)
        return self._busy[device]

    def window_s(self) -> float:
        lo, hi = self.trace.window
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the pool's devices."""
        ids = self.device_ids()
        return sum(self.busy(d).total() for d in ids) / len(ids) / 1e9

    def breakdown(self) -> dict:
        """The ten device ops that took most time (self time in seconds,
        averaged over the pool's devices), and the idle time by what the
        harness was doing on the host (the span around the gap)."""
        ids = self.device_ids()
        lo, hi = self.trace.window
        per_op = collections.Counter()
        for d in ids:
            for key, ns in self_times(self.trace.devices[d], lo, hi).items():
                per_op[key[:120]] += ns / 1e9 / len(ids)
        idle = collections.defaultdict(list)
        spans = sorted((a, b, n) for n, a, b in self.trace.host
                       if n != "traced")
        starts = [s[0] for s in spans]
        for d in ids:
            for a, b in self.busy(d).gaps(lo, hi):
                mid = (a + b) // 2
                k = int(np.searchsorted(starts, mid, side="right")) - 1
                inside = k >= 0 and spans[k][1] > mid
                idle[spans[k][2] if inside else "between spans"].append(
                    (b - a) / 1e9 / len(ids))
        gaps = sorted(((f"{name}: {len(v)} gaps, longest "
                        f"{max(v) * 1e3:.4f} ms", sum(v))
                       for name, v in idle.items()),
                      key=lambda g: -g[1])
        return {"device_ops": [[k, v] for k, v in per_op.most_common(10)],
                "idle_gaps": [[k, v] for k, v in gaps[:10]]}


# ---------------------------------------------------------------------------
# how `correct` is decided


def compare(results: Dict[int, tuple], ref: Dict[int, tuple]) -> dict:
    """Widest gaps of one lane against the reference: statistics
    relative to ``max(|ref|, 1)``, p-values absolute."""
    stat_gap = p_gap = 0.0
    for t, (s_ref, p_ref) in ref.items():
        s, p = results.get(t, (math.nan, math.nan))
        sg = abs(s - s_ref) / max(abs(s_ref), 1.0)
        pg = abs(p - p_ref)
        stat_gap = max(stat_gap, sg if np.isfinite(sg) else 1e300)
        p_gap = max(p_gap, pg if np.isfinite(pg) else 1e300)
    return {"stat_gap": stat_gap, "p_gap": p_gap}


def verdict_mismatch(lane: Lane, n_total: int, alpha: float) -> int:
    """1 where the lane's verdict is not the reference's rule applied to
    the lane's own stitched p-values, else 0. The decision has to match,
    and a FAIL has to name some of the tests past the boundary and no
    other: the program freezes a verdict at its first crossing, so a
    crossing in a later round may be missing from it. The p-values
    answer to the reference through ``p_gap``: a float32 p within its
    gap of a boundary may rightly fall on the other side of it."""
    decision, failed = reference.verdict(lane.results, n_total, alpha)
    named = set(lane.failed_tests)
    sound = (lane.decision == decision and named <= set(failed)
             and bool(named) == (decision == "FAIL"))
    return int(not sound)


def lane_gaps(lane: Lane, table, alpha: float, ft=np.float64) -> dict:
    """One lane against the reference run over the same generator and
    seed: the widest gaps, and whether the verdict breaks the rule."""
    ref = reference.run_request(table, lane.generator, lane.seed, ft)
    gaps = compare(lane.results, ref)
    gaps["verdict_mismatch"] = verdict_mismatch(lane, len(table), alpha)
    return gaps


def sample(done: List[Record], words_per_request: int, seed: int):
    """The completed requests the check compares, drawn from the seed."""
    k = min(len(done), max(1, CHECK_WORDS // max(words_per_request, 1)))
    rng = np.random.default_rng(request_seed(seed, "check"))
    pick = sorted(rng.choice(len(done), size=k, replace=False).tolist())
    return [done[j] for j in pick]


def check(done, failed, table, config, seed, table_mismatch,
          ft=np.float64) -> dict:
    """The numbers compared, each with its limit (``config["limits"]``
    for the gaps; 0 for the counts). Every lane of every sampled request
    is compared."""
    lanes = max((len(r.lanes) for r in done), default=1)
    picked = sample(done, sum(w for _, _, w in table) * lanes, seed) \
        if done else []
    tasks = [lane for rec in picked for lane in rec.lanes]
    with concurrent.futures.ThreadPoolExecutor(CHECK_THREADS) as pool:
        per = list(pool.map(
            lambda lane: lane_gaps(lane, table, config["alpha"], ft), tasks))
    limits = config["limits"]
    none = 1e300    # the reading when no request completed
    return {
        "failed_requests": {"value": len(failed), "limit": 0},
        "table_mismatch": {"value": table_mismatch, "limit": 0},
        "verdict_mismatch": {"value": sum(g["verdict_mismatch"]
                                          for g in per), "limit": 0},
        "stat_gap": {"value": max((g["stat_gap"] for g in per),
                                  default=none),
                     "limit": limits["stat_gap"]},
        "p_gap": {"value": max((g["p_gap"] for g in per), default=none),
                  "limit": limits["p_gap"]},
    }


# ---------------------------------------------------------------------------
# the command


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_PROCESS,
                       log=lambda m: print(m, flush=True))
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    except ProgramMissing as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    checks = out.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": out.pop("correct"), **out, "checks": checks}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
