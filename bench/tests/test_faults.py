"""``correct`` on a sound run, and its control and faults planted under
the timed path, on the CPU at a size a test run holds (SmallCrush at
scale 1/16). The harness's look for a chip is skipped; the rest of the
run is what the benchmark runs."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import faults
from bench.tests.helpers import ROOT, tiny_tree


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("one"))


def test_sound_run_is_correct(tree):
    out = faults.run_with(None, *tree)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["stat_gap"]["value"] < 1e-4


@pytest.mark.parametrize("fault", ["control", "altered_answer",
                                   "half_left_out", "stale_answer",
                                   "altered_verdict"])
def test_fault_is_not_correct(tree, fault):
    out = faults.run_with(fault, *tree)
    assert out["correct"] is False, (fault, out["checks"])
    if fault == "altered_verdict":
        assert out["checks"]["verdict_mismatch"]["value"] >= 1


TWO = {"driver": "closed_loop", "generators": ["splitmix64", "msweyl"],
       "why": "two lanes"}


@pytest.fixture(scope="module")
def two_lanes(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("two"), traffic="two",
                     traffic_body=TWO)


def test_two_lanes_sound_run_is_correct(two_lanes):
    out = faults.run_with(None, *two_lanes)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["altered_lane", "control",
                                   "stale_answer"])
def test_two_lanes_fault_is_not_correct(two_lanes, fault):
    """``altered_lane`` alters only the second generator's answers: the
    check compares every lane, not the first alone."""
    out = faults.run_with(fault, *two_lanes)
    assert out["correct"] is False, (fault, out["checks"])


def _w4(tmp_path, fault):
    root, bench, cell = tiny_tree(tmp_path, n_workers=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.faults", root, bench, cell,
         fault], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_workers_sound_run_is_correct(tmp_path):
    out = _w4(tmp_path, "none")
    assert out["correct"] is True, out["checks"]


def test_four_workers_exchange_left_out_is_not_correct(tmp_path):
    out = _w4(tmp_path, "exchange_left_out")
    assert out["correct"] is False, out["checks"]
