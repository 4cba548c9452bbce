"""Elastic pool re-meshing: `session.resize()` invariants, the
worker-count-independent checkpoint v3 layout (with v1/v2 upgrade), and
the empty-residual guards.

The multi-width assertions run a subprocess scenario
(tests/elastic_scenario.py) because the forced host-device count must be
set before jax initializes; everything it checks is summarized into one
JSON dict the tests here assert on."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.ckpt import io as ckpt_io
from repro.core.api import Checkpoint, PoolSession, RunSpec
from repro.core.battery import build_battery, max_words
from repro.core.policies import OverDecomposePolicy
from repro.core.pool import _job_fn, stream_table
from repro.core.scheduler import replan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -------------------------------------------------- empty-residual guards

def test_replan_of_nothing_returns_empty_plan():
    """All jobs done + a resize-triggered replan must complete, not raise
    ``ValueError: max() arg is an empty sequence``."""
    plan = replan([], [1.0] * 10, 4)
    assert plan.rounds == 0
    assert plan.assignment.shape == (0, 4)
    assert plan.est_makespan == 0.0
    entries = build_battery("smallcrush", 0.125)
    for mode in ("roundrobin", "lpt", "adaptive", "over_decompose"):
        sub = replan([], [e.cost for e in entries], 3, mode,
                     entries=entries)
        assert sub.rounds == 0 and sub.assignment.shape == (0, 3)


def test_empty_residual_tables_do_not_raise():
    assert stream_table([]).shape == (0,)
    assert max_words([]) == 0
    assert OverDecomposePolicy().decompose([], 8) is None


# ------------------------------------------------------- resize validation

def test_resize_validates_width():
    session = PoolSession()
    assert session.resize(session.n_workers) == session.n_workers  # no-op
    with pytest.raises(ValueError):
        session.resize(0)
    with pytest.raises(RuntimeError, match="XLA_FLAGS"):
        session.resize(len(jax.devices()) + 7)
    # a failed resize leaves the session usable at its old width
    assert session.n_workers >= 1
    assert session.submit(
        RunSpec("smallcrush", "splitmix64", 3, scale=0.0625)
    ).result().rounds_run > 0


def test_grow_shrink_sugar():
    session = PoolSession()
    w = session.n_workers
    with pytest.raises((ValueError, RuntimeError)):
        session.shrink(w)                       # to zero
    assert session.n_workers == w


# ----------------------------------------------------- idle-slot gating

def test_idle_slot_generation_is_gated():
    """Padded rounds must not pay generator cost for empty slots: the
    idle branch is a zero-length sentinel (the lax.cond returns (0, nan)
    before any bit block exists), and real jobs are untouched."""
    entries = build_battery("smallcrush", 0.0625)
    job = _job_fn(entries)
    jaxpr = str(jax.make_jaxpr(job)(
        np.int32(-1), np.int32(0), np.int32(0)))
    assert "cond" in jaxpr                      # generation is branched
    stat, p = jax.jit(job)(np.int32(-1), np.int32(0), np.int32(0))
    assert float(stat) == 0.0 and np.isnan(float(p))
    stat0, p0 = jax.jit(job)(np.int32(0), np.int32(7), np.int32(0))
    assert np.isfinite(float(stat0)) and 0.0 <= float(p0) <= 1.0


# --------------------------------------------------- checkpoint v3 layout

def _toy_ckpt():
    idx = np.arange(4, dtype=np.int32)
    st = np.arange(4, dtype=np.float64)[None, :] + 1.0
    pv = np.full((1, 4), 0.5)
    return idx, st, pv


def test_checkpoint_v5_roundtrip_and_drop(tmp_path):
    path = str(tmp_path / "v5.ck")
    idx, st, pv = _toy_ckpt()
    Checkpoint(idx, st, pv, np.array([1], np.int8), rounds_run=3,
               alpha=0.05).save(path)
    ck = Checkpoint.load(path)
    assert ck.version == 5 and ck.rounds_run == 3 and ck.alpha == 0.05
    assert ck.engine == "bonferroni" and ck.log_wealth is None
    assert ck.n_generators == 1
    np.testing.assert_array_equal(ck.job_idx, idx)
    np.testing.assert_array_equal(ck.stats, st)
    assert list(ck.decisions) == [1]
    assert ck.results() == [{i: (float(st[0, i]), 0.5) for i in range(4)}]
    dropped = ck.drop([1, 2])
    assert list(dropped.job_idx) == [0, 3]
    assert dropped.stats.shape == (1, 2)
    assert dropped.decisions is None            # verdict state discarded


def test_checkpoint_v1_v2_load_and_upgrade(tmp_path):
    idx, st, pv = _toy_ckpt()
    p1 = str(tmp_path / "v1.ck")
    ckpt_io.save(p1, [idx, st[0], pv[0]])       # classic flat single-gen
    c1 = Checkpoint.load(p1)
    assert c1.version == 1 and c1.decisions is None
    assert c1.stats.shape == (1, 4)
    p2 = str(tmp_path / "v2.ck")
    ckpt_io.save(p2, [idx, st, pv, np.array([2], np.int8), np.int64(5)])
    c2 = Checkpoint.load(p2)
    assert c2.version == 2 and c2.rounds_run == 5
    assert c2.alpha is None                     # v2 never recorded alpha
    assert list(c2.decisions) == [2]
    c2.save(p2)                                 # upgrade on next save
    assert Checkpoint.load(p2).version == 5
    assert len(ckpt_io.load_flat(p2)) == 10


def test_non_adaptive_resume_ignores_alpha_change(tmp_path):
    """v3 always saves verdict decisions, but they are binding only for
    ``stop_on_verdict`` runs — a plain run resumed under a different
    alpha must resume cleanly, not fail the verdict cross-check (alpha
    never affected its execution)."""
    ck = str(tmp_path / "alpha.ck")
    session = PoolSession()
    res1 = session.submit(RunSpec("smallcrush", "splitmix64", 3,
                                  scale=0.125,
                                  checkpoint_path=ck)).result()
    res2 = session.submit(RunSpec("smallcrush", "splitmix64", 3,
                                  scale=0.125, checkpoint_path=ck,
                                  alpha=0.9)).result()
    assert res2.rounds_run == 0
    assert res2.results == res1.results


def test_adaptive_resume_of_plain_checkpoint_any_alpha(tmp_path):
    """A plain run's checkpoint resumed with ``stop_on_verdict`` under a
    DIFFERENT alpha must recompute verdicts fresh, not fail the binding
    cross-check — v3 records which alpha the saved decisions were
    computed under, and a mismatch makes them advisory."""
    ck = str(tmp_path / "plain.ck")
    session = PoolSession()
    res1 = session.submit(RunSpec("smallcrush", "splitmix64", 3,
                                  scale=0.125,
                                  checkpoint_path=ck)).result()
    res2 = session.submit(RunSpec("smallcrush", "splitmix64", 3,
                                  scale=0.125, checkpoint_path=ck,
                                  stop_on_verdict=True,
                                  alpha=0.9)).result()
    assert res2.rounds_run == 0                 # nothing re-executed
    assert res2.results == res1.results
    assert res2.verdict.decided                 # recomputed under 0.9


def test_checkpoint_rejects_unknown_layouts(tmp_path):
    idx, st, pv = _toy_ckpt()
    bad_ver = str(tmp_path / "bad_ver.ck")
    ckpt_io.save(bad_ver, [np.int64(9), idx, st, pv,
                           np.zeros(0, np.int8), np.int64(0),
                           np.float64(0.01)])
    with pytest.raises(ValueError, match="version"):
        Checkpoint.load(bad_ver)
    bad_len = str(tmp_path / "bad_len.ck")
    ckpt_io.save(bad_len, [idx, st])
    with pytest.raises(ValueError, match="leaves"):
        Checkpoint.load(bad_len)


# -------------------------------------------- multi-width scenario (W=8)

@pytest.fixture(scope="module")
def scenario(tmp_path_factory):
    """Run the 8-device subprocess scenario once; share its JSON verdict."""
    td = tmp_path_factory.mktemp("elastic")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)                  # the scenario forces its own
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "elastic_scenario.py"),
         str(td)],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_resize_bitwise_single_generator(scenario):
    assert scenario["single_bitwise"]


def test_width_one_matches_wide_pool_bitwise(scenario):
    """A 1-worker pool and an 8-worker pool stitch bitwise-equal p-values
    (no statistic may depend on how the round program was partitioned)."""
    assert scenario["width1_bitwise"]


def test_resize_bitwise_fanout(scenario):
    assert scenario["fanout_bitwise"]


def test_resize_bitwise_over_decompose(scenario):
    assert scenario["overdec_bitwise"]


def test_resize_recompiles_only_new_width(scenario):
    """8 -> 3 -> 8: the 3-wide program traces once; growing back to 8 is
    a compile-cache hit, so width 8 stays at one trace."""
    assert scenario["single_trace_widths"] == [[3, 1], [8, 1]]


def test_w8_checkpoint_resumes_on_w4(scenario):
    """THE regression: a checkpoint saved on an 8-wide mesh, with results
    knocked out, resumes on a 4-wide mesh — only the missing jobs rerun,
    and the stitched results reconcile bitwise."""
    assert scenario["resume_missing"] == 2
    assert scenario["resume_rounds"] == 1       # ceil(2 jobs / 4 workers)
    assert scenario["resume_bitwise"]
    assert scenario["resume_ckpt_version"] == 5


def test_v2_checkpoint_upgrades_across_widths(scenario):
    assert scenario["v2_upgrade_bitwise"]
    assert scenario["v2_upgraded_leaves"] == 10
