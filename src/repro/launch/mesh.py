"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization. The dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing jax
(see dryrun.py); smoke tests and benches see the real single CPU device.

Mesh layout (TPU v5e-class pods of 256 chips):
  single pod : (16, 16)        axes ("data", "model")
  multi pod  : (2, 16, 16)     axes ("pod", "data", "model")
The battery pool (the paper's HTCondor-pool analogue) uses the flattened
"workers" view of the same device set — see ``repro.core.pool``.
"""
from __future__ import annotations

import numpy as np

import jax


def _mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _too_few(what: str, n: int, have) -> str:
    """The error text for a mesh wider than the visible devices."""
    platform = have[0].platform if have else "none"
    return (f"{what} needs {n} devices, have {len(have)} ({platform}); "
            f"on accelerators run on a host with >= {n} chips "
            f"(chip_smoke.py --chips 4 wants a four-chip host); on the "
            f"CPU set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before importing jax")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(_too_few(f"mesh {shape}", n, devices))
    return _mesh(shape, axes, devices[:n])


def make_pool_mesh(n_workers: int | None = None):
    """Flat 1-D mesh for the battery pool ('workers' axis).

    ``PoolSession.resize`` calls this for every width the pool bounces
    through, so the width must be validated here — a clear error beats
    ``make_mesh`` failing on a short device slice."""
    devices = jax.devices()
    n = n_workers or len(devices)
    if n < 1:
        raise ValueError(f"pool width must be >= 1, got {n}")
    if n > len(devices):
        raise RuntimeError(_too_few(f"pool of {n} workers", n, devices))
    return _mesh((n,), ("workers",), devices[:n])
