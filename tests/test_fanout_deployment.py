"""The fan-out deployment (``bench/configs/bigcrush-s16-g8.json`` under the
traffic ``bench/traffic/fanout8.json``) at CPU sizes: one request of the
eight counter-based generators through ``PoolSession(n_workers=1)``, polled
round by round, every lane held to the benchmark's plain reference
(``bench/reference.py``) by the limits and the verdict rule that decide
``correct`` on the chip."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402
from bench import run as bench_run  # noqa: E402
from repro.core.api import PoolSession, RunSpec  # noqa: E402

with open(os.path.join(ROOT, "bench", "configs", "bigcrush-s16-g8.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "bench", "traffic", "fanout8.json")) as f:
    G8 = tuple(json.load(f)["generators"])
SEEDS = tuple(101 + 7 * k for k in range(len(G8)))


@pytest.fixture(scope="module")
def session():
    return PoolSession(n_workers=1)


def _request(session, battery, scale):
    spec = RunSpec(battery, generators=G8, seeds=SEEDS, scale=scale,
                   policy=CONFIG["policy"], backend=CONFIG["backend"],
                   alpha=CONFIG["alpha"],
                   verdict_engine=CONFIG["verdict_engine"],
                   stop_on_verdict=CONFIG["stop_on_verdict"])
    run = session.submit(spec)
    while run.pending_rounds:
        run.poll()
    return run.result()


@pytest.mark.parametrize("battery, scale", [("smallcrush", 0.125),
                                            ("bigcrush", 1 / 64)])
def test_every_lane_agrees_with_the_reference(session, battery, scale):
    """Each lane's statistics and p-values within the configuration's
    limits of the reference run over the lane's generator and seed, and
    its verdict the reference's rule over its own p-values."""
    res = _request(session, battery, scale)
    table = reference.battery(battery, scale)
    assert list(res.runs) == list(G8)
    limits = CONFIG["limits"]
    for g, seed in zip(G8, SEEDS):
        run = res.runs[g]
        lane = bench_run.Lane(g, seed, dict(run.results),
                              run.verdict.decision,
                              tuple(run.verdict.failed_tests))
        assert len(lane.results) == len(table), g
        gaps = bench_run.lane_gaps(lane, table, CONFIG["alpha"])
        assert gaps["stat_gap"] <= limits["stat_gap"], (g, gaps)
        assert gaps["p_gap"] <= limits["p_gap"], (g, gaps)
        assert gaps["verdict_mismatch"] == 0, (g, lane.decision)
    decisions = {g: res.runs[g].verdict.decision for g in G8}
    assert decisions["randu"] == decisions["minstd"] == "FAIL"
    if battery == "smallcrush":
        assert decisions["splitmix64"] == decisions["threefry"] == "PASS"
