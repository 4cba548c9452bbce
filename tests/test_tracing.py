"""The program's profiler spans and device scopes (``repro.common.trace``):
a profiled run shows one ``repro.round`` span per dispatching poll, with
its ``run``/``round``/``jobs``/``lanes`` args and its phases nested
inside, on every dispatch path; the compiled round program names
generation and every test family in its HLO metadata, inside
``repro.lane`` where the job is mapped over generator lanes."""
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.common.trace import scope, span
from repro.core.api import PoolSession, RunSpec
from repro.core.battery import build_battery
from repro.core.pool import bucket_table
from repro.rng.sources import capture_generator

SCALE = 0.01
PHASES = ("plan", "launch", "wait", "fold", "verdict", "status")


@pytest.fixture(scope="module")
def session():
    return PoolSession()


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    td = tmp_path_factory.mktemp("capture")
    return capture_generator("splitmix64", str(td / "cap.npy"), seed=7,
                             n_streams=16, stride=1 << 15)


def _profile(tmp_path, body):
    """Run ``body()`` under the profiler; the ``repro.*`` host events of
    the trace as ``(name, start, end, args)``, by start."""
    with jax.profiler.trace(str(tmp_path)):
        body()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                {k: v for k, v in ev.stats
                                 if k in ("run", "round", "jobs", "lanes")}))
    return sorted(out, key=lambda e: e[1])


def _spec(kind, capture, **kw):
    kw.setdefault("scale", SCALE)
    if kind == "single":
        return RunSpec("smallcrush", "splitmix64", seeds=(7,), **kw)
    if kind == "fanout":
        return RunSpec("smallcrush", ("splitmix64", "pcg32"), seeds=(7,),
                       **kw)
    if kind == "grid":
        return RunSpec("smallcrush", ("splitmix64", "splitmix64"),
                       seeds=(7,), offsets=(0, 1 << 20), **kw)
    return RunSpec("smallcrush", sources=(f"file:{capture}",), seeds=(7,),
                   **kw)


@pytest.mark.parametrize("kind", ["single", "fanout", "grid", "captured"])
def test_every_dispatch_path_spans_its_rounds(session, capture, tmp_path,
                                              kind):
    """One ``repro.round`` per poll with the run's id, the round's index,
    its non-idle slots and its generator lanes; each phase once inside
    its round (plan twice: the row, then the runner and its arguments);
    no checkpoint span without a checkpoint path; ``repro.finalize``
    once, after the rounds."""
    spec = _spec(kind, capture)
    session.submit(spec).poll()                 # compile outside the trace
    rows, runs = [], []

    def body():
        run = session.submit(spec)
        runs.append(run)
        while run.pending_rounds:
            rows.append(int(np.count_nonzero(run._queue[0] >= 0)))
            run.poll()
        run.poll()                              # nothing queued: no round
        run.result()

    events = _profile(tmp_path, body)
    run, = runs
    rounds = [e for e in events if e[0] == "repro.round"]
    lanes = len(spec.generators) if kind in ("fanout", "grid") else 1
    assert [e[3] for e in rounds] == [{"run": run.run_id, "round": k,
                                       "jobs": j, "lanes": lanes}
                                      for k, j in enumerate(rows)]
    assert len(rounds) == run.rounds_run > 0
    for _, a, b, _ in rounds:
        inside = [e[0] for e in events if e[0].startswith("repro.round.")
                  and a <= e[1] <= e[2] <= b]
        assert sorted(inside) == sorted(
            ["repro.round." + p for p in PHASES] + ["repro.round.plan"])
    children = [e for e in events if e[0].startswith("repro.round.")]
    assert all(any(a <= e[1] <= e[2] <= b for _, a, b, _ in rounds)
               for e in children)
    assert not [e for e in events if e[0] == "repro.round.checkpoint"]
    fin = [e for e in events if e[0] == "repro.finalize"]
    assert [e[3] for e in fin] == [{"run": run.run_id}]
    assert fin[0][1] >= rounds[-1][2]


def test_checkpoint_span_only_with_a_checkpoint(session, tmp_path):
    spec = RunSpec("smallcrush", "splitmix64", seeds=(9,), scale=SCALE,
                   checkpoint_path=str(tmp_path / "run.ck"))
    session.submit(RunSpec("smallcrush", "splitmix64", seeds=(9,),
                           scale=SCALE)).poll()

    def body():
        run = session.submit(spec)
        while run.pending_rounds:
            run.poll()

    events = _profile(tmp_path / "prof", body)
    rounds = [e for e in events if e[0] == "repro.round"]
    ckpts = [e for e in events if e[0] == "repro.round.checkpoint"]
    assert len(ckpts) == len(rounds) > 0
    assert all(a <= c[1] <= c[2] <= b
               for c, (_, a, b, _) in zip(ckpts, rounds))


def test_run_ids_are_session_wide(session):
    a = session.submit(RunSpec("smallcrush", "splitmix64", scale=SCALE))
    b = session.submit(RunSpec("smallcrush", "pcg32", scale=SCALE))
    assert b.run_id == a.run_id + 1
    assert PoolSession().submit(a.spec).run_id == 0


def test_scope_names_the_ops_traced_in_it():
    def f(x):
        with scope("gen"):
            return x * 3
    text = jax.jit(f).lower(np.float32(1)).compile().as_text()
    assert 'op_name="jit(f)/repro.gen/mul"' in text
    with span("round", run=1, round=2) as s:    # inert without a profiler
        s.set_metadata(jobs=3)


def test_round_program_names_generation_and_every_family(session):
    """The compiled round program's HLO metadata holds ``repro.gen`` and
    ``repro.test.<family>`` for every family of the battery; the
    captured-buffer program holds every family and no generation."""
    spec = RunSpec("smallcrush", "splitmix64", scale=SCALE)
    families = {e.kname for e in build_battery("smallcrush", SCALE)}
    assert len(families) == 10
    runner = session._runner(spec)
    row = np.zeros((session.n_workers,), np.int32)
    text = runner.lower(row, np.int32(7), np.int32(0)).compile().as_text()
    assert 'op_name="jit(round_fn)/' in text
    assert "/repro.gen/" in text
    for fam in families:
        assert f"/repro.test.{fam}/" in text, fam
    ext = session._runner(spec, n_gens=1, captured=True)
    n_max = bucket_table(session._compiled(spec).jobs)[0][-1]
    bits = np.zeros((1, session.n_workers, n_max), np.uint32)
    text = ext.lower(row, bits).compile().as_text()
    assert "/repro.gen/" not in text
    for fam in families:
        assert f"/repro.test.{fam}/" in text, fam


@pytest.mark.parametrize("kind", ["fanout", "grid"])
def test_mapped_programs_scope_each_lane(session, capture, kind):
    """In the runners that map the job over generator lanes, every
    generation and kernel op sits in ``repro.lane``, outside its
    ``repro.gen`` or ``repro.test.<family>``; the one-lane round program
    has no lane scope."""
    spec = _spec(kind, capture)
    n = len(spec.generators)
    row = np.zeros((session.n_workers,), np.int32)
    args = (row, np.full(n, 7, np.int32), np.arange(n, dtype=np.int32))
    if kind == "grid":
        args += (np.zeros((n, 2), np.uint32),)
    text = session._runner(spec).lower(*args).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)]
    inner = [[k for k, c in enumerate(p)
              if c == "repro.gen" or c.startswith("repro.test.")]
             for p in paths]
    scoped = [(p, k[0]) for p, k in zip(paths, inner) if k]
    assert any("repro.gen" in p for p, _ in scoped)
    assert scoped and all("repro.lane" in p[:k] for p, k in scoped)
    one = session._runner(_spec("single", capture))
    text = one.lower(row, np.int32(7), np.int32(0)).compile().as_text()
    assert "/repro.gen/" in text and "repro.lane" not in text
