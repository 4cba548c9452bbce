"""Compiles for a described TPU v5e chip (no chip needed): the Pallas
kernels at the sizes the battery routes to them and one battery round
program, traced the way the runners trace them (32-bit, x64 scoped to
generation). What Mosaic or the TPU compiler would refuse on the chip,
these refuse here.

Every test that compiles for the described chip lives in this file; the
topology is described inside a fixture, so only the worker running this
file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.battery import build_battery
from repro.core.pool import make_round_runner
from repro.stats import backends as B


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh(topo):
    """A one-device pool mesh on the described chip."""
    return Mesh(np.array(topo.devices[:1]), ("workers",))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels' "auto" interpret knob sees this process's CPU; the
    chip compiles them, so resolve it the way it resolves on a TPU."""
    import repro.kernels.gf2_rank.ops as rank_ops
    import repro.kernels.histogram.ops as hist_ops
    for mod in (rank_ops, hist_ops):
        monkeypatch.setattr(mod, "resolve_interpret", lambda i="auto": False)


def _compile(fn, mesh, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
            for s, d, spec in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _routed_bins():
    """The largest histogram the accelerated backend sends to the kernel
    in any battery at scale 1.0 (serial2d's 128 x 128 cells)."""
    out = 0
    for battery in ("smallcrush", "crush", "bigcrush"):
        for e in build_battery(battery, 1.0, backend="accelerated"):
            p = dict(e.params)
            if e.impl == "accelerated" and e.kname == "serial2d":
                out = max(out, p["d"] ** 2)
            if e.impl == "accelerated" and e.kname == "collision":
                out = max(out, 1 << p["kbits"])
    return out


@pytest.mark.parametrize("k", ["battery", B.HIST_MAX_BINS])
def test_histogram_compiles_for_v5e(mesh, compiled_kernels, k):
    from repro.kernels.histogram.ops import bincount
    k = _routed_bins() if k == "battery" else k
    assert k <= B.HIST_MAX_BINS
    n = 262144                                  # serial2d's largest block
    text = _compile(lambda idx: bincount(idx, k), mesh,
                    ((n,), jnp.int32, P()))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_mats", [512, 4096])
def test_gf2_rank_compiles_for_v5e(mesh, compiled_kernels, n_mats):
    from repro.kernels.gf2_rank.ops import rank32
    text = _compile(rank32, mesh, ((n_mats, 32), jnp.uint32, P()))
    assert "tpu_custom_call" in text


def test_smallcrush_round_program_compiles_for_v5e(mesh, compiled_kernels):
    """The accelerated smallcrush round program at scale 1.0 on a
    one-device mesh: it holds the Pallas kernels and no float64 op."""
    entries = build_battery("smallcrush", 1.0, backend="accelerated")
    runner = make_round_runner(entries, mesh)
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
            for s, d, spec in (((1,), jnp.int32, P("workers")),
                               ((), jnp.int32, P()), ((), jnp.int32, P()))]
    text = runner.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "f64" not in text
