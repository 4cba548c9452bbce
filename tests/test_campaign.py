"""Campaign subsystem tests (ISSUE 5, DESIGN.md §8): stream-offset
grids, the pairstream seam family, wave planning, the knockout loop,
batched-dispatch trace accounting, and ledger resume."""
import numpy as np
import pytest

from repro.common.compat import x64
from repro.core import stitch
from repro.core.api import (CELL_FAIL, CELL_PASS, CELL_UNDECIDED,
                            CampaignLedger, CampaignSpec, PoolSession,
                            RunSpec)
from repro.core.campaign import Campaign, default_span, screen
from repro.core.scheduler import wave_makespan, wave_schedule
from repro.rng import generators as G
from repro.stats.tests import pairstream

SCALE = 0.0625


@pytest.fixture(scope="module")
def session():
    return PoolSession()


# ------------------------------------------------------- offset machinery

def test_stream_offsets_grid():
    assert G.stream_offsets(4, 100).tolist() == [0, 100, 200, 300]
    with pytest.raises(ValueError):
        G.stream_offsets(0, 100)


def test_seam_offsets_straddle():
    # pair s reads [ (s+1)*span - n, (s+1)*span + n )
    assert G.seam_offsets(3, 1000, 64).tolist() == [936, 1936]
    assert G.seam_offsets(1, 1000, 64).size == 0
    with pytest.raises(ValueError):
        G.seam_offsets(3, 100, 200)        # seam block wider than span


def test_runspec_offsets_normalize_and_validate():
    spec = RunSpec("smallcrush", ("splitmix64", "pcg32"), 1,
                   offsets=(0, 4096))
    assert spec.offsets == (0, 4096)
    spec1 = RunSpec("smallcrush", ("splitmix64", "pcg32"), 1, offsets=64)
    assert spec1.offsets == (64, 64)            # broadcast
    with pytest.raises(ValueError):
        RunSpec("smallcrush", "mwc", 1, offsets=64)    # no jump-ahead
    with pytest.raises(ValueError):
        RunSpec("smallcrush", "splitmix64", 1, offsets=-1)
    with pytest.raises(ValueError):
        RunSpec("smallcrush", ("splitmix64", "pcg32"), 1,
                offsets=(1, 2, 3))


def test_grid_dispatch_offset_zero_matches_classic(session):
    """offsets=(0, 0) routes through the grid runner but must reproduce
    the classic fan-out results bitwise (the 64-bit ladder fallback is
    exact for any offset, including 0)."""
    classic = session.submit(RunSpec(
        "smallcrush", ("splitmix64", "randu"), 7, scale=SCALE)).result()
    grid = session.submit(RunSpec(
        "smallcrush", ("splitmix64", "randu"), 7, scale=SCALE,
        offsets=0)).result()
    for gen in ("splitmix64", "randu"):
        assert grid.runs[gen].results == classic.runs[gen].results


def test_grid_dispatch_offset_reads_substream(session):
    """A non-zero offset must change the words every job consumes (the
    cell reads its own sub-stream), while staying a valid battery."""
    a = session.submit(RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                               offsets=0)).result()
    b = session.submit(RunSpec("smallcrush", "splitmix64", 7, scale=SCALE,
                               offsets=(1 << 16,))).result()
    assert a.results != b.results
    assert all(0.0 <= b.results[i][1] <= 1.0 for i in range(10))


# ------------------------------------------------------- pairstream family

def test_pairstream_null_is_calibrated():
    with x64():
        bits = G.splitmix64_block(3, 5, 8192)
    for mode in ("corr", "hamcorr", "match", "shift"):
        _, p = pairstream(bits, n=4096, mode=mode)
        assert 1e-4 < float(p) < 1.0 - 1e-4, mode


def test_pairstream_catches_duplicated_stream():
    """If the two halves are the SAME words (span-0 overlap bug), the
    match mode must blow up."""
    with x64():
        half = G.splitmix64_block(3, 5, 4096)
    bits = np.concatenate([np.asarray(half), np.asarray(half)])
    _, p = pairstream(bits, n=4096, mode="match")
    assert float(p) < 1e-10


def test_pairstream_catches_off_by_k_seam():
    """An off-by-two seam (stream s+1 starting 2 words early) is exactly
    what the shift mode exists for."""
    with x64():
        blk = np.asarray(G.splitmix64_block(3, 5, 8194))
    bits = np.concatenate([blk[:4096], blk[4094:8190]])
    _, p = pairstream(bits, n=4096, mode="shift")
    assert float(p) < 1e-10


def test_battery_pairstream_builds():
    from repro.core.battery import build_battery
    entries = build_battery("pairstream", 0.25)
    assert len(entries) == 4
    assert len({e.n_words for e in entries}) == 1    # one seam alignment


# -------------------------------------------------------- wave planning

def test_wave_schedule_sorts_ascending():
    assert wave_schedule((1.0, 0.25, 0.5)) == [0.25, 0.5, 1.0]
    assert wave_schedule((0.25, 0.25)) == [0.25, 0.25]
    with pytest.raises(ValueError):
        wave_schedule(())
    with pytest.raises(ValueError):
        wave_schedule((0.5, -1.0))


def test_wave_makespan_models_batching():
    batched, per_cell = wave_makespan([1.0] * 10, 2, 16)
    assert per_cell == pytest.approx(batched * 16)


# ---------------------------------------------------------- spec + ledger

def test_campaign_spec_validates():
    with pytest.raises(ValueError):
        CampaignSpec("smallcrush", ("mwc",), n_streams=2)   # no jump-ahead
    with pytest.raises(ValueError):
        CampaignSpec("smallcrush", ("splitmix64", "splitmix64"))
    with pytest.raises(ValueError):
        CampaignSpec("smallcrush", ("splitmix64",), waves=())
    with pytest.raises(KeyError):
        CampaignSpec("megacrush", ("splitmix64",))
    spec = CampaignSpec("smallcrush", ("splitmix64", "pcg32"), n_streams=3)
    assert spec.n_cells == 6
    assert spec.cells[0] == ("splitmix64", 0)
    assert spec.cells[-1] == ("pcg32", 2)


def test_campaign_ledger_roundtrip(tmp_path):
    spec = CampaignSpec("smallcrush", ("splitmix64", "pcg32"), n_streams=2)
    led = CampaignLedger.fresh(spec)
    led.decisions[3] = CELL_FAIL
    led.decided_phase[3] = 1
    led.phases_done = 2
    path = str(tmp_path / "campaign.ck")
    led.save(path)
    back = CampaignLedger.load(path)
    assert back.matches(spec)
    assert back.phases_done == 2
    assert back.decisions.tolist() == led.decisions.tolist()
    assert back.decided_phase.tolist() == led.decided_phase.tolist()
    other = CampaignSpec("smallcrush", ("splitmix64", "pcg32"), n_streams=3)
    assert not back.matches(other)
    # same grid, different decision-relevant config -> digest refuses
    assert not back.matches(CampaignSpec(
        "smallcrush", ("splitmix64", "pcg32"), n_streams=2, waves=(0.5,)))
    assert not back.matches(CampaignSpec(
        "smallcrush", ("splitmix64", "pcg32"), n_streams=2, seed=99))


def test_default_span_covers_widest_block():
    from repro.core.battery import build_battery, max_words
    spec = CampaignSpec("smallcrush", ("splitmix64",), n_streams=4,
                        waves=(SCALE, 0.125))
    span = default_span(spec)
    assert span >= max_words(build_battery("smallcrush", 0.125))
    assert span & (span - 1) == 0                    # power of two


# ------------------------------------------------- the acceptance campaign

GENS8 = ("splitmix64", "msweyl", "threefry", "pcg32", "lcg64",
         "xorshift64s", "randu", "minstd")


def test_campaign_8x4_acceptance(tmp_path):
    """ISSUE 5 acceptance: >= 8 generators x 4 stream offsets complete
    smallcrush with one batched dispatch per wave — compile count scales
    with PHASES, not with the 32 cells — producing a per-cell matrix
    with knocked-out cells skipping later waves, resumable from the
    ledger."""
    ledger = str(tmp_path / "campaign.ck")
    session = PoolSession()
    spec = CampaignSpec("smallcrush", GENS8, n_streams=4, seed=7,
                        waves=(SCALE, SCALE), ledger_path=ledger)
    campaign = Campaign(session, spec)
    phases = campaign.phases()
    assert [p.name for p in phases] == ["streamcheck",
                                        f"x{SCALE:g}", f"x{SCALE:g}"]
    res = campaign.run()

    # one batched dispatch per wave: every phase compiled at most one
    # grid program — 32 cells never caused per-cell recompiles
    assert session.total_traces <= len(phases)
    # ... and the two same-scale waves shared ONE executable (the second
    # wave's survivor count pads back to a seen power-of-two bucket)
    assert session.total_traces == len(phases) - 1

    # the matrix: randu knocked out (stream check or wave 1 — never the
    # final wave), the robust generators pass every cell
    mat = res.matrix
    assert mat.shape == (8, 4)
    gidx = {g: i for i, g in enumerate(GENS8)}
    assert set(mat[gidx["randu"]].tolist()) == {CELL_FAIL}
    assert int(res.decided_phase.reshape(8, 4)[gidx["randu"]].max()) \
        < len(phases) - 1                            # skipped later waves
    for good in ("splitmix64", "threefry", "pcg32", "lcg64"):
        assert set(mat[gidx[good]].tolist()) == {CELL_PASS}, good
    assert not np.any(mat == CELL_UNDECIDED)
    assert "campaign screening matrix" in res.report

    # ledger resume: a fresh campaign over the same ledger replays
    # NOTHING and reports the identical matrix
    session2 = PoolSession()
    res2 = Campaign(session2, spec).run()
    assert res2.rounds_run == 0
    assert session2.total_traces == 0
    assert res2.decisions.tolist() == res.decisions.tolist()
    assert res2.decided_phase.tolist() == res.decided_phase.tolist()


def test_campaign_mid_run_resume(tmp_path):
    """A campaign interrupted between phases resumes at the next phase:
    decided cells stay decided, completed phases are not re-run."""
    ledger = str(tmp_path / "campaign.ck")
    spec = CampaignSpec("smallcrush", ("splitmix64", "randu"), n_streams=2,
                        seed=7, waves=(SCALE,), ledger_path=ledger)
    session = PoolSession()
    c1 = Campaign(session, spec)
    phases = c1.phases()
    c1._run_phase(0, phases[0])                  # stream check only
    c1.ledger.phases_done = 1
    c1._save_ledger()
    rounds_phase0 = c1.rounds_run
    assert np.all(np.asarray(c1.ledger.decisions).reshape(2, 2)[1]
                  == CELL_FAIL)                  # randu seam-knocked

    c2 = Campaign(session, spec)
    assert c2.ledger.phases_done == 1
    res = c2.run()
    wave_rounds = -(-10 // session.n_workers)    # smallcrush jobs / width
    assert 0 < res.rounds_run <= wave_rounds     # phase 0 was NOT re-run
    assert rounds_phase0 > 0
    mat = res.matrix
    assert set(mat[0].tolist()) == {CELL_PASS}   # splitmix64
    assert set(mat[1].tolist()) == {CELL_FAIL}   # randu stays knocked out


def test_campaign_knockout_skips_later_phases(session):
    """_phase_cells: a knocked-out cell contributes no work to any later
    phase (wave or seam)."""
    spec = CampaignSpec("smallcrush", ("splitmix64", "randu"), n_streams=2,
                        waves=(SCALE, 1.0))
    c = Campaign(session, spec)
    c.ledger.decisions[2:] = CELL_FAIL           # knock out randu's cells
    wave = [p for p in c.phases() if p.offset_rule == "stream"][0]
    assert c._phase_cells(wave) == [(0,), (1,)]
    seam = c.phases()[0]
    assert seam.offset_rule == "seam"
    assert c._phase_cells(seam) == [(0, 1)]      # only the surviving pair


def test_screen_one_call(tmp_path):
    """The one-call helper: no streams, no seam phase, single wave."""
    res = screen(CampaignSpec("smallcrush", ("splitmix64",), seed=3,
                              waves=(SCALE,), stream_check=True))
    assert res.phase_names == [f"x{SCALE:g}"]    # n_streams=1: no seams
    assert res.decision("splitmix64", 0) == stitch.PASS


# ---------------------------------------------------------- stitch report

def test_campaign_matrix_and_report():
    dec = [CELL_PASS, CELL_FAIL, CELL_UNDECIDED, CELL_PASS]
    mat = stitch.campaign_matrix(dec, 2, 2)
    assert mat.tolist() == [[1, 2], [0, 1]]
    rep = stitch.campaign_report(["alpha", "beta"], 2, dec,
                                 [1, 0, -1, 2], ["streamcheck", "x1", "x2"])
    assert "P@1" in rep and "F@0" in rep and "?" in rep
    assert "knocked out 1 cell(s)" in rep
    with pytest.raises(ValueError):
        stitch.campaign_matrix(dec, 3, 2)
