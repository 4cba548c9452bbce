"""Process-level JAX set-up the launchers make before their first compile."""
from __future__ import annotations

import os

import jax

# the checkout's root (src/repro/common/runtime.py -> three levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    it itself and nothing else is set in code. Otherwise the cache lives
    at the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``), so
    every process of one checkout shares it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices, so a pool of ``n`` workers can
    run on a host without accelerators. Must run before JAX picks its
    backend. It configures the CPU client only — no ``XLA_FLAGS`` — so on
    an accelerator host the pool still spans the chips; a device count
    already forced through ``XLA_FLAGS`` is left as it is."""
    if n > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_num_cpu_devices", n)
