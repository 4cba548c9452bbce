"""SPMD battery pool — the HTCondor pool mapped onto a device mesh.

One compiled program covers the whole battery: a worker's round executes
``lax.switch`` over the uniform job table (every test kernel has signature
``bits -> (stat, p)``), with the job's bit-stream derived from
``(seed, stream_table[job_id])`` — fresh-generator-per-test semantics
(paper §4.1). For a plain battery the stream table is the identity, so
results are bitwise those of the classic path; over-decomposed sub-jobs
get disjoint sub-streams (``group + n_groups * part``) that are stable
across pool width and schedule, which keeps hold/release and speculative
re-execution reconcilable.

Three compiled shapes, all pure functions of the job table (generator and
seed are runtime arguments — the same executable serves every generator,
which is what ``PoolSession``'s compile cache exploits):

  ``make_round_runner``   one round across workers via ``shard_map`` (the
                          paper's "submit a batch, wait for output files");
                          the host driver in ``core/api.py`` loops rounds so
                          progress is checkpointable between batches.
  ``make_fanout_runner``  the same round mapped over a ``gen_ids`` axis —
                          G generators assessed in ONE dispatch (multi-
                          generator batteries, Wartel & Hill-style).
  ``make_grid_runner``    the fan-out with a per-lane runtime stream
                          offset — the campaign screening grid's
                          (generator, sub-stream) cells in one dispatch
                          (core/campaign.py, DESIGN.md §8).
  ``make_batch_runner``   whole plan in one dispatch (benchmarks).

``on_trace`` (when given) fires once per trace of the round body; the
session uses it to assert/count cache behaviour.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.compat import x64
from repro.common.trace import scope
from repro.core.battery import TestEntry
from repro.rng.sources import switch_block


def word_bucket(n: int) -> int:
    """The power-of-two bucket a job's bit block is generated at: the
    smallest power of two >= n (0 for an empty block). Bucketing bounds
    generated-but-unread words at <2x per job while keeping the number of
    distinct generation shapes (and so trace size) logarithmic in the
    spread of battery block sizes."""
    return 0 if n <= 0 else 1 << max(int(n) - 1, 0).bit_length()


def bucket_table(entries: List[TestEntry]):
    """``(sizes, bucket_ids)``: the sorted distinct power-of-two bucket
    sizes present in the job table, and each job's index into them."""
    sizes = sorted({word_bucket(e.n_words) for e in entries})
    index = {s: i for i, s in enumerate(sizes)}
    bids = np.asarray([index[word_bucket(e.n_words)] for e in entries],
                      np.int32)
    return sizes, bids


def generated_words(entries: List[TestEntry]) -> int:
    """Words the bucketed hot path generates for one pass over the table
    (each job pays its own bucket, not the battery-wide max)."""
    return sum(word_bucket(e.n_words) for e in entries)


def read_words(entries: List[TestEntry]) -> int:
    """Words the kernels actually consume in one pass over the table."""
    return sum(e.n_words for e in entries)


def block_ratio(entries: List[TestEntry]) -> float:
    """generated/read words under bucketing (1.0 = nothing wasted). The
    pre-bucketing hot path paid ``len(entries) * max_words`` instead."""
    r = read_words(entries)
    return generated_words(entries) / r if r else 1.0


def stream_table(entries: List[TestEntry]) -> np.ndarray:
    """Per-job generator stream ids. Identity for an unsplit battery;
    sub-jobs get ``group + n_groups * part`` — unique, deterministic, and
    independent of worker count or plan. An empty job table (a replan of
    nothing after elastic re-meshing) yields an empty table, not a
    ``max()`` crash."""
    if not entries:
        return np.zeros((0,), np.int32)
    n_groups = max(e.group for e in entries) + 1
    return np.asarray([e.group + n_groups * e.part for e in entries],
                      np.int32)


def _kernels(entries: List[TestEntry]):
    """The uniform kernel switch table: every test as ``bits ->
    (float32 stat, float32 p)`` — shared by the generator-switch job and
    the captured-buffer job so both dispatch paths score bits
    identically (the ingest parity guarantee). Each kernel's device ops
    are scoped ``repro.test.<family>`` (``repro.test.custom`` for an
    entry with no family name)."""
    def kernel(e):
        def run(bits):
            with scope("test." + (e.kname or "custom")):
                return tuple(jnp.asarray(v, jnp.float32)
                             for v in e.kernel(bits))
        return run
    return [kernel(e) for e in entries]


def _job_fn(entries: List[TestEntry], with_offset: bool = False,
            block_provider: Optional[Callable] = None):
    """(job_id, seed, gen_id[, offset]) -> (stat, p). job_id == -1 -> idle.

    ``with_offset=True`` adds a runtime stream-offset argument routed to
    the generator switch (campaign grids, ``make_grid_runner``), as the
    ``(hi, lo)`` uint32 words of ``split_offsets`` so the program's
    arguments stay 32-bit and only generation traces in 64 bits; the
    default path traces exactly the classic three-argument job, so
    existing executables and trace counts are untouched.

    ``block_provider`` is the abstract bit-supply seam: any
    ``(gen_id, seed, stream, n[, offset]) -> uint32[n]`` traceable
    callable; the default is the registry-backed ``sources.switch_block``
    (the historical ``gen_block_by_id``). Captured sources never pass
    through here — they enter as prefetched buffers via
    ``make_external_runner``/``gather_captured_bits``.

    Generation is BUCKETED: jobs are grouped into power-of-two word
    buckets (``bucket_table``) and an inner ``lax.switch`` generates
    exactly the job's bucket — a 4k-word birthday job no longer pays for
    the battery-wide ``max_words`` block a 160k-word coupon/poker job
    needs (the block is zero-padded to the widest bucket so the kernel
    switch sees one static shape, but padding is a broadcast, not
    generator work); a bucket's generation and its pad are scoped
    ``repro.gen`` on the device. Idle slots (``job_id == -1``) take a
    zero-length sentinel path: the outer ``lax.cond`` returns ``(0, nan)``
    directly, so a padded round pays neither generation NOR kernel work
    — no ``n_words`` zero block is ever materialized or routed through
    the kernel switch. Both the cond predicate and the switch indices are
    per-shard scalars, and the fan-out runners map (``lax.map``), not
    vmap, the job over generator lanes, so every branch stays a real
    branch: a vmapped switch on a batched generator index would run all
    registered generators (mwc's serial scan among them) for every lane."""
    provider = switch_block if block_provider is None else block_provider
    kernels = _kernels(entries)
    streams = jnp.asarray(stream_table(entries))
    sizes, bids = bucket_table(entries)
    bucket_ids = jnp.asarray(bids)
    n_max = sizes[-1] if sizes else 0

    def gen_branch(nb):
        def gen(seed, gen_id, stream, offset=None):
            with scope("gen"):
                with x64():
                    if offset is not None:
                        offset = ((offset[0].astype(jnp.uint64) << 32)
                                  | offset[1].astype(jnp.uint64))
                    block = provider(gen_id, seed, stream, nb, offset)
                if nb < n_max:
                    block = jnp.concatenate(
                        [block, jnp.zeros((n_max - nb,), jnp.uint32)])
                return block
        return gen
    gen_branches = [gen_branch(nb) for nb in sizes]

    if with_offset:
        def run(job_id, seed, gen_id, offset):
            def idle(_):
                return jnp.float32(0.0), jnp.float32(jnp.nan)

            def work(ops):
                seed, gen_id, offset = ops
                j = jnp.clip(job_id, 0, len(entries) - 1)
                bits = jax.lax.switch(bucket_ids[j], gen_branches,
                                      seed, gen_id, streams[j], offset)
                return jax.lax.switch(j, kernels, bits)

            return jax.lax.cond(job_id < 0, idle, work,
                                (seed, gen_id, offset))

        return run

    def run(job_id, seed, gen_id):
        def idle(_):
            return jnp.float32(0.0), jnp.float32(jnp.nan)

        def work(ops):
            seed, gen_id = ops
            j = jnp.clip(job_id, 0, len(entries) - 1)
            bits = jax.lax.switch(bucket_ids[j], gen_branches,
                                  seed, gen_id, streams[j])
            return jax.lax.switch(j, kernels, bits)

        return jax.lax.cond(job_id < 0, idle, work, (seed, gen_id))

    return run


def make_round_runner(entries: List[TestEntry], mesh,
                      on_trace: Optional[Callable[[], None]] = None,
                      block_provider: Optional[Callable] = None):
    """Compiled fn: (round_assignment (W,), seed, gen_id) -> stats, ps (W,)."""
    job = _job_fn(entries, block_provider=block_provider)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("workers"), P(), P()),
        out_specs=(P("workers"), P("workers")), check_vma=False)
    def round_fn(jobs, seed, gen_id):
        if on_trace is not None:
            on_trace()
        stat, p = job(jobs[0], seed, gen_id)
        return stat[None], p[None]

    return jax.jit(round_fn)


def make_fanout_runner(entries: List[TestEntry], mesh,
                       on_trace: Optional[Callable[[], None]] = None,
                       block_provider: Optional[Callable] = None):
    """Multi-generator round: (round_assignment (W,), seeds (G,),
    gen_ids (G,)) -> stats, ps (G, W). The job is mapped over the
    generator axis, so G generators are assessed in one device dispatch."""
    job = _job_fn(entries, block_provider=block_provider)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("workers"), P(), P()),
        out_specs=(P(None, "workers"), P(None, "workers")), check_vma=False)
    def round_fn(jobs, seeds, gen_ids):
        if on_trace is not None:
            on_trace()
        stat, p = jax.lax.map(lambda a: job(jobs[0], *a), (seeds, gen_ids))
        return stat[:, None], p[:, None]

    return jax.jit(round_fn)


def split_offsets(offsets) -> np.ndarray:
    """(G,) non-negative word offsets -> (G, 2) uint32 ``(hi, lo)`` words,
    the grid runner's offset argument."""
    offs = np.asarray(offsets, np.uint64)
    return np.stack([offs >> np.uint64(32), offs & np.uint64(0xFFFFFFFF)],
                    axis=-1).astype(np.uint32)


def make_grid_runner(entries: List[TestEntry], mesh,
                     on_trace: Optional[Callable[[], None]] = None,
                     block_provider: Optional[Callable] = None):
    """Campaign-grid round: (round_assignment (W,), seeds (G,),
    gen_ids (G,), offsets (G, 2) from ``split_offsets``) -> stats, ps
    (G, W). Like the fan-out runner but each lane of the mapped cell axis
    also carries a runtime stream offset, so one executable serves every
    (generator, sub-stream) cell of a screening grid — wave after wave,
    knockout after knockout, no retrace (DESIGN.md §8)."""
    job = _job_fn(entries, with_offset=True, block_provider=block_provider)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("workers"), P(), P(), P()),
        out_specs=(P(None, "workers"), P(None, "workers")), check_vma=False)
    def round_fn(jobs, seeds, gen_ids, offsets):
        if on_trace is not None:
            on_trace()
        stat, p = jax.lax.map(lambda a: job(jobs[0], *a),
                              (seeds, gen_ids, offsets))
        return stat[:, None], p[:, None]

    return jax.jit(round_fn)


def _external_job_fn(entries: List[TestEntry]):
    """(job_id, bits (n_max,)) -> (stat, p) — the captured-buffer twin of
    ``_job_fn``: no generator switch at all, the block arrives prefetched
    (``gather_captured_bits``). The kernel table, idle sentinel and
    clip-then-switch job routing are IDENTICAL to the generator path, so
    the same bits score the same p-values whichever door they enter by."""
    kernels = _kernels(entries)

    def run(job_id, bits):
        def idle(_):
            return jnp.float32(0.0), jnp.float32(jnp.nan)

        def work(bits):
            j = jnp.clip(job_id, 0, len(entries) - 1)
            return jax.lax.switch(j, kernels, bits)

        return jax.lax.cond(job_id < 0, idle, work, bits)

    return run


def make_external_runner(entries: List[TestEntry], mesh,
                         on_trace: Optional[Callable[[], None]] = None):
    """Captured-source round: (round_assignment (W,), bits (L, W, n_max))
    -> stats, ps (L, W). The lane axis L plays the role the ``gen_ids``
    axis plays in ``make_fanout_runner`` — one (source, seed, offset)
    cell per lane — but the bits are HOST-PREFETCHED buffers sharded over
    workers, not switch lanes: external bitstreams never join (or widen)
    the compiled generator switch, so screening a nonce dump can never
    retrace a generator battery and vice versa."""
    job = _external_job_fn(entries)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("workers"), P(None, "workers", None)),
        out_specs=(P(None, "workers"), P(None, "workers")), check_vma=False)
    def round_fn(jobs, bits):
        if on_trace is not None:
            on_trace()
        stat, p = jax.vmap(lambda b: job(jobs[0], b))(bits[:, 0, :])
        return stat[:, None], p[:, None]

    return jax.jit(round_fn)


def gather_captured_bits(entries: List[TestEntry], jobs, lanes) -> np.ndarray:
    """Host-side prefetch for ``make_external_runner``: a (L, W, n_max)
    uint32 buffer where slot ``[l, w]`` holds worker w's job block read
    from lane l's captured source — each job reads its power-of-two
    BUCKET (``bucket_table``) starting at the job's stream-table word
    offset within the lane's sub-stream, zero-padded to the widest
    bucket. Bucket sizing, stream ids and padding mirror ``_job_fn``
    exactly; that mirroring is what makes captured-vs-generator parity
    bitwise rather than approximate. ``lanes`` is a sequence of
    ``(source, seed, offset)`` cells (offset ``None`` = the canonical
    "no offset"); idle slots (job -1) stay zero and are never read."""
    streams = stream_table(entries)
    sizes, bids = bucket_table(entries)
    n_max = sizes[-1] if sizes else 0
    jobs = np.asarray(jobs, np.int64)
    out = np.zeros((len(lanes), len(jobs), n_max), np.uint32)
    for li, (source, seed, offset) in enumerate(lanes):
        for wi, j in enumerate(jobs):
            if j < 0:
                continue
            nb = sizes[bids[j]]
            out[li, wi, :nb] = source.block(seed, int(streams[j]), nb,
                                            offset)
    return out


def make_batch_runner(entries: List[TestEntry], mesh):
    """Whole-plan runner: (plan (R, W), seed, gen_id) -> (R, W) stats/ps.
    Single dispatch — used by benchmarks; the checkpointing driver prefers
    round-by-round."""
    job = _job_fn(entries)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(None, "workers"), P(), P()),
        out_specs=(P(None, "workers"), P(None, "workers")), check_vma=False)
    def plan_fn(jobs, seed, gen_id):
        def body(_, jid):
            s, p = job(jid[0], seed, gen_id)
            return 0, (s, p)
        _, (stats, ps) = jax.lax.scan(body, 0, jobs)
        return stats[:, None], ps[:, None]

    return jax.jit(plan_fn)


def inject_round_faults(injector, round_idx, row, arrays,  # repro: fault-boundary
                        deadline=None):
    """THE host-side fault-injection boundary (DESIGN.md §12, RPA106).

    Called by the driver in ``core/api.py`` strictly AFTER the compiled
    runner returned and materialised host numpy arrays, and strictly
    BEFORE the results are folded by ``stitch`` — the one point where a
    simulated eviction/corruption/straggle can touch results without
    the traced executables or their compile caches ever seeing it.
    ``arrays`` is the round's per-generator ``[(stats, ps), ...]``
    (each (W,)), mutated in place; returns ``(events, resize_to)``
    from :meth:`repro.core.faults.FaultInjector.apply_round`.

    Fault logic must never move inside a jitted/shard_mapped body:
    analysis rule RPA106 flags any injector call site in a traced
    context, and only this annotated host boundary is sanctioned.
    """
    return injector.apply_round(round_idx, np.asarray(row), arrays,
                                deadline=deadline)


def _entry_signature(e: TestEntry) -> tuple:
    """Structural identity of an entry for compile caching: everything
    ``_job_fn`` consumes. Registry-built kernels are a pure function of
    (kname, backend, params), so two ``build_battery`` calls with the
    same arguments key equal; entries carrying a custom callable (no
    kname) fall back to the callable's identity."""
    return (e.kname or id(e.kernel), e.params, e.backend, e.n_words,
            e.group, e.part)


_SEQ_RUNNERS: dict = {}


def run_sequential(entries: List[TestEntry], seed: int, gen_id: int):
    """Stock-TestU01 model: every test in order on ONE worker (baseline).

    The jitted pass is cached on the table's STRUCTURAL signature —
    repeated calls over equal job tables (seed sweeps, generator sweeps,
    fresh ``build_battery`` results) reuse one executable instead of
    re-tracing, the same compile-once discipline ``PoolSession`` applies
    to the pool runners."""
    key = tuple(_entry_signature(e) for e in entries)
    runner = _SEQ_RUNNERS.get(key)
    if runner is None:
        job = _job_fn(entries)

        @jax.jit
        def runner(seed, gen_id):
            def body(_, jid):
                s, p = job(jid, seed, gen_id)
                return 0, (s, p)
            _, (stats, ps) = jax.lax.scan(
                body, 0, jnp.arange(len(entries), dtype=jnp.int32))
            return stats, ps

        if len(_SEQ_RUNNERS) >= 32:              # bound the executable pool
            _SEQ_RUNNERS.pop(next(iter(_SEQ_RUNNERS)))
        _SEQ_RUNNERS[key] = runner
    return runner(jnp.asarray(seed, jnp.int32),
                  jnp.asarray(gen_id, jnp.int32))
