"""Tests for the public session API: RunSpec normalization, the
PoolSession compile cache (trace counting), checkpoint resume, multi-
generator fan-out, schedule-policy registry, and over-decomposition."""
import dataclasses

import numpy as np
import pytest

from repro.core import stitch
from repro.core.api import BatteryResult, Checkpoint, PoolSession, RunSpec
from repro.core.battery import build_battery, split_entry
from repro.core.policies import (
    OverDecomposePolicy,
    RetryPolicy,
    get_policy,
    register_policy,
)
from repro.core.pool import stream_table

SCALE = 0.125


@pytest.fixture(scope="module")
def session():
    return PoolSession()


# ------------------------------------------------------------------ RunSpec

def test_runspec_normalizes_scalars():
    spec = RunSpec("smallcrush", "splitmix64", 3)
    assert spec.generators == ("splitmix64",)
    assert spec.seeds == (3,)


def test_runspec_broadcasts_seeds():
    spec = RunSpec("smallcrush", ("splitmix64", "pcg32"), 3)
    assert spec.seeds == (3, 3)
    spec2 = RunSpec("smallcrush", ("splitmix64", "pcg32"), (3, 4))
    assert spec2.seeds == (3, 4)


def test_runspec_validates():
    with pytest.raises(KeyError):
        RunSpec("megacrush", "splitmix64", 1)
    with pytest.raises(KeyError):
        RunSpec("smallcrush", "notagen", 1)
    with pytest.raises(ValueError):
        RunSpec("smallcrush", ("splitmix64", "pcg32"), (1, 2, 3))
    with pytest.raises(ValueError):
        RunSpec("smallcrush", "splitmix64", 1, policy="nope")


def test_runspec_frozen_and_hashable():
    spec = RunSpec("smallcrush", "splitmix64", 3)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.scale = 2.0
    assert spec == RunSpec("smallcrush", "splitmix64", 3)
    assert hash(spec) == hash(RunSpec("smallcrush", "splitmix64", 3))


def test_runspec_preset_folds_battery_config():
    assert RunSpec.preset("bigcrush").scale == 16.0
    assert RunSpec.preset("crush").n_tests == 96
    assert RunSpec.preset("bigcrush", scale=0.5).scale == 0.5


# ------------------------------------------------------------ compile cache

def test_compile_cache_single_trace_across_generators():
    """Two submits with the same (battery, scale, workers) but different
    generators must trace the round program exactly once."""
    session = PoolSession()
    r1 = session.submit(RunSpec("smallcrush", "splitmix64", 7,
                                scale=SCALE)).result()
    r2 = session.submit(RunSpec("smallcrush", "pcg32", 13,
                                scale=SCALE)).result()
    assert session.total_traces == 1
    assert len(r1.results) == len(r2.results) == 10
    key = session.cache_key(RunSpec("smallcrush", "splitmix64", 0,
                                    scale=SCALE))
    assert session.trace_counts == {key: 1}


def test_compile_cache_keyed_on_battery_and_scale():
    session = PoolSession()
    session.submit(RunSpec("smallcrush", "splitmix64", 1,
                           scale=SCALE)).result()
    session.submit(RunSpec("smallcrush", "splitmix64", 1,
                           scale=SCALE / 2)).result()
    assert session.total_traces == 2            # different scale -> new key
    assert len(session.trace_counts) == 2


# --------------------------------------------------------- checkpoint resume

def test_checkpoint_resume_runs_only_missing(tmp_path):
    """save -> knock entries out -> restart re-runs only the missing
    indices and reconciles bitwise (deterministic streams)."""
    ck = str(tmp_path / "resume.ck")
    session = PoolSession()
    spec = RunSpec("smallcrush", "splitmix64", 11, scale=SCALE,
                   checkpoint_path=ck)
    res1 = session.submit(spec).result()
    assert res1.rounds_run > 0

    Checkpoint.load(ck).drop([2, 8]).save(ck)

    run2 = session.submit(spec)
    status = run2.status()
    assert status["jobs_total"] - status["jobs_done"] == 2
    res2 = run2.result()
    w = session.n_workers
    assert res2.rounds_run == -(-2 // w)         # one replan round set
    assert res2.results == res1.results          # bitwise reconciliation
    assert session.total_traces == 1             # cache hit on restart


# ------------------------------------------------------------------ fan-out

G8 = ("splitmix64", "msweyl", "threefry", "pcg32", "lcg64", "xorshift64s",
      "randu", "minstd")


@pytest.mark.parametrize("gens, seeds", [
    (("splitmix64", "pcg32", "randu"), (7, 7, 7)),
    (G8, tuple(101 + 7 * k for k in range(len(G8)))),
], ids=["three", "eight"])
def test_multi_generator_fanout_matches_single_runs(session, gens, seeds):
    """G generators in one dispatch == the same generators run alone,
    each with its lane's seed: every (stat, p) bitwise."""
    spec = RunSpec("smallcrush", gens, seeds, scale=SCALE)
    multi = session.submit(spec).result()
    assert isinstance(multi, BatteryResult)
    assert list(multi.runs) == list(gens)
    for gen, seed in zip(gens, seeds):
        single = session.submit(RunSpec("smallcrush", gen, seed,
                                        scale=SCALE)).result()
        for i in range(10):
            lane = np.asarray(multi.runs[gen].results[i], np.float64)
            alone = np.asarray(single.results[i], np.float64)
            assert lane.tobytes() == alone.tobytes(), (gen, i)
    assert multi.runs["randu"].n_suspect >= 2    # canary still flagged
    assert multi.runs["splitmix64"].n_suspect == 0


# ----------------------------------------------------------------- policies

def test_policy_registry():
    assert get_policy("lpt").name == "lpt"
    assert get_policy("roundrobin").name == "roundrobin"
    assert get_policy("over_decompose").name == "over_decompose"
    pol = OverDecomposePolicy(max_parts=3)
    assert get_policy(pol) is pol
    with pytest.raises(ValueError):
        get_policy("not_a_policy")


def test_register_custom_policy():
    base = get_policy("lpt")

    @dataclasses.dataclass(frozen=True)
    class Reversed:
        name: str = "reversed_rr"

        def plan(self, costs, n_workers):
            return get_policy("roundrobin").plan(list(costs)[::-1], n_workers)

        def decompose(self, entries, n_workers):
            return None

        def signature(self):
            return None

    register_policy(Reversed())
    assert get_policy("reversed_rr").name == "reversed_rr"
    assert get_policy("lpt") is base


# ----------------------------------------------------------- over_decompose

def test_split_entry_shrinks_and_groups():
    entries = build_battery("smallcrush", 1.0)   # full size: floors don't bind
    heavy = entries[7]                           # rank: the heaviest kernel
    subs = split_entry(heavy, 4, start_index=20)
    assert [s.index for s in subs] == [20, 21, 22, 23]
    assert all(s.group == heavy.index for s in subs)
    assert all(s.n_parts == len(subs) for s in subs)
    assert all(s.n_words < heavy.n_words for s in subs)
    assert sum(s.cost for s in subs) <= heavy.cost + 1e-9
    # floors binding -> refuse to split rather than emit useless sub-jobs
    tiny = build_battery("smallcrush", SCALE)[7]
    assert len(split_entry(tiny, 4, start_index=0)) == 1


def test_split_entry_floors_never_add_words():
    # crush at 1/4 scale: rank over 512 matrices; a quarter would be 128,
    # under the 256-matrix floor, so four parts would read twice the words
    rank = next(e for e in build_battery("crush", 0.25)
                if e.name == "rank_n_mats512")
    subs = split_entry(rank, 4, start_index=0)
    assert len(subs) == 2
    assert sum(s.n_words for s in subs) <= rank.n_words
    assert all(s.n_parts == 2 for s in subs)


def test_decompose_covers_all_tests_with_unique_streams():
    entries = build_battery("smallcrush", SCALE)
    jobs = OverDecomposePolicy(threshold=0.05, max_parts=4).decompose(
        entries, n_workers=8)
    assert jobs is not None and len(jobs) > len(entries)
    assert sorted({j.group for j in jobs}) == [e.index for e in entries]
    assert [j.index for j in jobs] == list(range(len(jobs)))
    streams = stream_table(jobs)
    assert len(set(streams.tolist())) == len(jobs)


def test_over_decompose_end_to_end(session):
    pol = OverDecomposePolicy(threshold=0.05, max_parts=4)
    res = session.submit(RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                                 policy=pol)).result()
    assert len(res.results) == 10                # combined back to test space
    assert all(0.0 <= res.results[i][1] <= 1.0 for i in range(10))
    assert res.n_suspect == 0                    # good generator stays good
    bad = session.submit(RunSpec("smallcrush", "randu", 7, scale=SCALE,
                                 policy=pol)).result()
    assert bad.n_suspect >= 2                    # canary survives the combine


def test_combiners():
    stat, p = stitch.combine_stouffer([0.5, 0.5, 0.5])
    assert abs(stat) < 1e-9 and abs(p - 0.5) < 1e-9
    _, p_low = stitch.combine_stouffer([1e-9, 1e-9])
    assert p_low < 1e-6
    _, p_high = stitch.combine_stouffer([1 - 1e-9, 1 - 1e-9])
    assert p_high > 1 - 1e-6                     # both tails preserved
    stat_f, p_f = stitch.combine_fisher([1e-9, 1e-9])
    assert p_f < 1e-6
    _, p_null = stitch.combine_fisher([0.5, 0.5, 0.5, 0.5])
    assert 0.01 < p_null < 0.99


def test_fold_groups_passthrough_is_bitwise():
    entries = build_battery("smallcrush", SCALE)
    job_results = {e.index: (1.0 + e.index, 0.25) for e in entries}
    out = stitch.fold_groups(job_results, entries)
    assert out == job_results                    # no combine applied
