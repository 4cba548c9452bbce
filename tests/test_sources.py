"""BitSource layer tests (DESIGN.md §11): the generator plugin
registry (stable ids, duplicate hard error, compiled-switch reuse,
serve-restart re-registration), captured-bitstream ingestion (bitwise
battery + campaign parity against the generator that produced the
bits, typed bounds errors), the content-addressed cache behaviour a
capture must have (same bytes HIT with zero dispatches, different
bytes MISS), the canonical offset convention, and the v4 checkpoint /
v2 campaign-ledger source-identity wire upgrades."""
import os

import numpy as np
import pytest

from repro.ckpt import io as ckpt_io
from repro.common.compat import x64
from repro.core import stitch
from repro.core.api import (CAMPAIGN_LEDGER_VERSION, CKPT_VERSION,
                            CampaignLedger, CampaignSpec, Checkpoint,
                            PoolSession, RunSpec)
from repro.core.campaign import Campaign
from repro.rng import generators as G
from repro.rng.sources import (CapturedBitsError, CapturedSource,
                               GeneratorSource, OffsetNotSupportedError,
                               capture_generator, counter_based_names,
                               register_generator, registry_size,
                               require_offsetable, resolve_source,
                               unregister_generator)
from repro.serve import SubmissionQueue

SCALE = 0.01
STRIDE = 1 << 15                     # words per captured stream shard


def _spec(src, seed=7, **kw):
    kw.setdefault("scale", SCALE)
    return RunSpec("smallcrush", sources=(src,), seeds=(seed,), **kw)


@pytest.fixture(scope="module")
def session():
    return PoolSession()


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """A splitmix64 capture wide/deep enough for every test here."""
    td = tmp_path_factory.mktemp("capture")
    return capture_generator("splitmix64", str(td / "cap.npy"), seed=7,
                             n_streams=16, stride=STRIDE)


# ------------------------------------------------------------- resolution

def test_resolve_source_grammar(capture, tmp_path):
    src = resolve_source("splitmix64")
    assert isinstance(src, GeneratorSource) and not src.captured
    assert resolve_source(src) is src           # BitSource passthrough
    cap = resolve_source(f"file:{capture}")
    assert isinstance(cap, CapturedSource) and cap.captured
    assert cap.fmt == "npy" and cap.name == "cap:cap"
    raw_path = str(tmp_path / "bits.dat")
    np.arange(8, dtype="<u4").tofile(raw_path)
    raw = resolve_source(f"file:{raw_path}:u32")
    assert isinstance(raw, CapturedSource) and raw.fmt == "u32"
    # an unknown suffix is part of the path (paths may contain colons)
    with pytest.raises(FileNotFoundError):
        resolve_source(f"file:{capture}:bogus")
    with pytest.raises(TypeError):
        resolve_source(123)


def test_registry_views_stay_live():
    """The back-compat GENERATORS/GEN_IDS/COUNTER_BASED views derive
    from the live registry: ids are dense and registration-ordered,
    and the non-counter-based complement is exactly mwc."""
    assert G.GEN_IDS["splitmix64"] == 0
    assert sorted(G.GEN_IDS.values()) == list(range(registry_size()))
    assert set(G.GENERATORS) - set(G.COUNTER_BASED) == {"mwc"}
    assert counter_based_names() == G.COUNTER_BASED


def test_duplicate_registration_is_hard_error():
    with pytest.raises(ValueError, match="already registered"):
        register_generator("splitmix64", G.splitmix64_block,
                           counter_based=True)
    with pytest.raises(TypeError):              # declaration is required
        register_generator("nodecl", G.splitmix64_block)


def test_unregister_only_pops_the_last_lane():
    register_generator("tail_a", G.splitmix64_block, counter_based=True)
    register_generator("tail_b", G.splitmix64_block, counter_based=True)
    try:
        with pytest.raises(ValueError, match="most recently"):
            unregister_generator("tail_a")
    finally:
        unregister_generator("tail_b")
        unregister_generator("tail_a")
    with pytest.raises(KeyError):
        unregister_generator("tail_a")


def test_registered_generator_joins_switch_without_retracing(session):
    """A plugin generator gets a NEW (wider) switch without retracing
    the executables existing widths already compiled — and those stay
    live for the built-in lanes afterwards."""
    r_base = session.submit(_spec("splitmix64")).result()
    t0 = session.total_traces
    session.submit(_spec("pcg32")).result()     # same width: reused
    assert session.total_traces == t0
    register_generator("ext_sm64", G.splitmix64_block,
                       counter_based=True)
    try:
        r_ext = session.submit(_spec("ext_sm64")).result()
        assert session.total_traces == t0 + 1   # exactly one wider trace
        # the clone of splitmix64's block is bitwise splitmix64
        assert r_ext.results == r_base.results
        session.submit(_spec("lcg64")).result()
        assert session.total_traces == t0 + 1   # old widths still cached
    finally:
        unregister_generator("ext_sm64")


# --------------------------------------------------- the offset convention

def test_offset_convention_single_gate():
    """``offset=None`` and 0 always pass the gate; a non-zero offset on
    a non-counter-based source raises the SAME typed error everywhere
    (RunSpec, CampaignSpec, the gate itself)."""
    mwc = GeneratorSource("mwc")
    require_offsetable(mwc, None)
    require_offsetable(mwc, 0)
    with pytest.raises(OffsetNotSupportedError):
        require_offsetable(mwc, 64)
    assert issubclass(OffsetNotSupportedError, ValueError)
    with pytest.raises(OffsetNotSupportedError):
        RunSpec("smallcrush", "mwc", seeds=(7,), scale=SCALE, offsets=64)
    with pytest.raises(ValueError, match="COUNTER_BASED"):
        CampaignSpec("smallcrush", generators=("mwc",), n_streams=2)


def test_block_offset_continuation():
    """The registry switch honours the canonical convention: None is
    the offset-free trace, an integer continues the stream exactly."""
    with x64():
        full = np.asarray(G.gen_block_by_id(0, 7, 3, 128, offset=None))
        head = np.asarray(G.gen_block_by_id(0, 7, 3, 64))
        tail = np.asarray(G.gen_block_by_id(0, 7, 3, 64, offset=64))
    np.testing.assert_array_equal(full, np.concatenate([head, tail]))


def test_stream_and_seam_offsets_validate_bounds():
    with pytest.raises(ValueError, match="span must be >= 1"):
        G.stream_offsets(4, 0)
    with pytest.raises(ValueError, match="span must be >= 1"):
        G.seam_offsets(3, -64, 64)
    with pytest.raises(ValueError, match="n_words"):
        G.seam_offsets(3, 1000, 0)
    with pytest.raises(ValueError, match="span >= n_words"):
        G.seam_offsets(3, 100, 200)
    with pytest.raises(ValueError, match="stream 3"):
        G.stream_offsets(4, 2 ** 62)
    with pytest.raises(ValueError, match="stream"):
        G.seam_offsets(4, 2 ** 62, 64)


# ------------------------------------------------------ captured parity

def test_captured_battery_bitwise_parity(session, capture):
    """ISSUE 8 acceptance: a memory-mapped capture of splitmix64's
    words earns the SAME p-values, bit for bit, as the generator."""
    r_gen = session.submit(_spec("splitmix64")).result()
    r_cap = session.submit(_spec(f"file:{capture}")).result()
    assert r_cap.results == r_gen.results
    assert r_cap.verdict.decision == r_gen.verdict.decision == stitch.PASS


def test_captured_campaign_parity(session, capture):
    """The campaign phase machinery (stream grid + seam check) decides
    captured cells exactly as the generator cells of the same bits."""
    def cspec(src):
        return CampaignSpec("smallcrush", sources=(src,), n_streams=2,
                            seed=7, waves=(SCALE,))
    res_gen = Campaign(session, cspec("splitmix64")).run()
    res_cap = Campaign(session, cspec(f"file:{capture}")).run()
    np.testing.assert_array_equal(res_cap.decisions, res_gen.decisions)
    np.testing.assert_array_equal(res_cap.decided_phase,
                                  res_gen.decided_phase)


def test_captured_bounds_errors_are_typed(tmp_path):
    path = str(tmp_path / "tiny.npy")
    np.save(path, np.arange(8, dtype=np.uint32).reshape(2, 4))
    src = CapturedSource(path)
    np.testing.assert_array_equal(src.block(0, 1, 4, None),
                                  np.arange(4, 8, dtype=np.uint32))
    with pytest.raises(CapturedBitsError, match="stream 0"):
        src.block(0, 0, 5, None)                # word range past shard
    with pytest.raises(CapturedBitsError, match="stream 2"):
        src.block(0, 2, 1, None)                # shard index out of range
    raw = str(tmp_path / "words.u32")
    np.arange(16, dtype="<u4").tofile(raw)
    u32 = CapturedSource(raw, "u32")
    np.testing.assert_array_equal(u32.block(0, 0, 4, 4),
                                  np.arange(4, 8, dtype=np.uint32))
    with pytest.raises(CapturedBitsError, match="stream 1"):
        u32.block(0, 1, 4, None)                # raw u32 = one stream


# ------------------------------------------------------- serve behaviour

def test_captured_resubmission_hits_modified_copy_misses(tmp_path,
                                                         capture):
    """ISSUE 8 acceptance: resubmitting the same captured file (even
    from a copied path) HITS the result cache with zero added
    dispatches; a byte-modified copy under the SAME name MISSES."""
    q = SubmissionQueue(session=PoolSession(),
                        state_dir=str(tmp_path / "state"))
    t1 = q.submit(_spec(f"file:{capture}"))
    q.drain()
    r1 = t1.result()
    base = q.dispatch_rounds
    assert base > 0
    data = open(capture, "rb").read()
    copy_dir = tmp_path / "copy"
    copy_dir.mkdir()
    copy = str(copy_dir / os.path.basename(capture))   # same cap: name
    with open(copy, "wb") as f:
        f.write(data)
    t2 = q.submit(_spec(f"file:{copy}"))
    q.drain()
    assert t2.result().verdict.decision == r1.verdict.decision
    assert q.dispatch_rounds == base            # zero added dispatches
    assert t2.cache_hits == 1
    mod_dir = tmp_path / "mod"
    mod_dir.mkdir()
    mod = str(mod_dir / os.path.basename(capture))     # same cap: name
    tampered = bytearray(data)
    tampered[-1] ^= 0xFF                        # flip one payload byte
    with open(mod, "wb") as f:
        f.write(bytes(tampered))
    t3 = q.submit(_spec(f"file:{mod}"))
    q.drain()
    t3.result()
    assert t3.cache_hits == 0                   # different bits: MISS
    assert q.dispatch_rounds > base


def test_external_generator_survives_daemon_restart(tmp_path):
    """An out-of-repo generator's in-flight work resumes across a serve
    restart PROVIDED the hook re-registers it first; without the
    registration the resume fails loudly with the re-register hint."""
    state = str(tmp_path / "state")
    register_generator("extgen", G.splitmix64_block, counter_based=True)
    try:
        q1 = SubmissionQueue(session=PoolSession(), state_dir=state)
        q1.submit(_spec("extgen"))
        q1.step(flush=True)                     # admit + round 1
        q1.step(flush=True)                     # round 2
        before = q1.dispatch_rounds
        assert 0 < before < 10                  # mid-flight "crash"
    finally:
        unregister_generator("extgen")
    with pytest.raises(KeyError, match="re-registered"):
        _spec("extgen")                         # lost without the hook
    register_generator("extgen", G.splitmix64_block, counter_based=True)
    try:
        q2 = SubmissionQueue(session=PoolSession(), state_dir=state)
        t = q2.submit(_spec("extgen"))
        q2.drain()
        assert t.result().verdict.decision == stitch.PASS
        # only the rounds the first daemon hadn't finished dispatched
        assert before + q2.dispatch_rounds == 10
    finally:
        unregister_generator("extgen")


# ------------------------------------------------- wire-format upgrades

def test_checkpoint_v5_roundtrip_and_v3_v4_upgrade(tmp_path):
    path = str(tmp_path / "ck.ck")
    ck = Checkpoint(np.arange(3, dtype=np.int32),
                    np.ones((1, 3)), np.ones((1, 3)) * 0.5,
                    source_uids=np.asarray([b"gen:splitmix64"]))
    ck.save(path)
    back = Checkpoint.load(path)
    assert back.version == CKPT_VERSION == 5
    assert [u.decode() for u in back.source_uids] == ["gen:splitmix64"]
    leaves = ckpt_io.load_flat(path)
    assert len(leaves) == 10                    # v5 wire layout pin
    # a v4 file (no engine/wealth leaves) loads transparently
    v4 = str(tmp_path / "v4.ck")
    ckpt_io.save(v4, [np.int64(4)] + leaves[1:8])
    mid = Checkpoint.load(v4)
    assert mid.version == 4 and mid.engine == "bonferroni"
    assert mid.log_wealth is None
    np.testing.assert_array_equal(mid.job_idx, back.job_idx)
    # a v3 file (no source identity either) loads transparently
    v3 = str(tmp_path / "v3.ck")
    ckpt_io.save(v3, [np.int64(3)] + leaves[1:7])
    old = Checkpoint.load(v3)
    assert old.version == 3 and old.source_uids is None
    np.testing.assert_array_equal(old.job_idx, back.job_idx)
    with pytest.raises(ValueError, match="leaves"):
        ckpt_io.save(v3, leaves[:6])
        Checkpoint.load(v3)


def test_checkpoint_refuses_recaptured_file(tmp_path):
    """A checkpoint written against one capture refuses to resume
    against a byte-different re-capture of the same path."""
    path = capture_generator("splitmix64", str(tmp_path / "c.npy"),
                             seed=7, n_streams=16, stride=STRIDE)
    ck = str(tmp_path / "run.ck")
    PoolSession().submit(
        _spec(f"file:{path}", checkpoint_path=ck)).result()
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF
    with open(path, "wb") as f:                 # re-capture, same path
        f.write(bytes(data))
    with pytest.raises(ValueError, match="re-captured"):
        PoolSession().submit(
            _spec(f"file:{path}", checkpoint_path=ck)).result()


def test_campaign_ledger_v2_upgrade_and_recapture_refusal(tmp_path):
    path = capture_generator("splitmix64", str(tmp_path / "c.npy"),
                             seed=7, n_streams=16, stride=STRIDE)
    ledger_path = str(tmp_path / "camp.ck")
    spec = CampaignSpec("smallcrush", sources=(f"file:{path}",),
                        n_streams=2, seed=7, waves=(SCALE,),
                        ledger_path=ledger_path)
    Campaign(PoolSession(), spec).run()
    led = CampaignLedger.load(ledger_path)
    assert led.version == CAMPAIGN_LEDGER_VERSION == 3
    assert led.engine == "bonferroni" and led.continuations == 0
    assert led.source_uids is not None and led.matches(spec)
    # a v1 ledger (no uids leaf) loads transparently and still matches
    # a generator-only campaign of the same grid
    gspec = CampaignSpec("smallcrush", generators=("splitmix64",),
                         n_streams=2, seed=7, waves=(SCALE,))
    v1_path = str(tmp_path / "v1.ck")
    v1 = CampaignLedger.fresh(gspec)
    leaves = (ckpt_io.load_flat(ledger_path))
    ckpt_io.save(v1_path, [
        np.int64(1), np.asarray(v1.gen_ids), np.asarray(v1.streams),
        np.asarray(v1.decisions), np.asarray(v1.decided_phase),
        np.int64(0), np.float64(gspec.alpha),
        np.uint64(gspec.digest())])
    old = CampaignLedger.load(v1_path)
    assert old.version == 1 and old.source_uids is None
    assert old.matches(gspec)
    assert len(leaves) == 12
    # a v2 ledger (uids, but no wealth/engine leaves) also upgrades
    v2_path = str(tmp_path / "v2.ck")
    ckpt_io.save(v2_path, [np.int64(2)] + leaves[1:9])
    mid = CampaignLedger.load(v2_path)
    assert mid.version == 2 and mid.engine == "bonferroni"
    assert mid.log_wealth is None and mid.continuations == 0
    assert mid.matches(spec)
    # re-capture the file: the v2 ledger refuses the new spec
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    respec = CampaignSpec("smallcrush", sources=(f"file:{path}",),
                          n_streams=2, seed=7, waves=(SCALE,),
                          ledger_path=ledger_path)
    assert not led.matches(respec)
