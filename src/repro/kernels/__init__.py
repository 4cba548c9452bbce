"""Shared kernel-layer plumbing: the ``interpret="auto"`` resolution every
``ops.py`` wrapper uses, so a kernel on TPU hardware is always compiled
by Mosaic, and CPU/CI keeps working through the Pallas interpreter."""
from __future__ import annotations

import os
from typing import Union


def resolve_interpret(interpret: Union[str, bool] = "auto") -> bool:
    """Resolve a Pallas ``interpret`` knob. "auto" interprets exactly when
    the backend is not a TPU. An explicit ``True`` on a TPU is refused
    outside a pytest run: the interpreter there would hide the device."""
    import jax
    if interpret == "auto":
        return jax.default_backend() != "tpu"
    if interpret:
        _refuse_interpreter_on_tpu(jax.default_backend())
    return bool(interpret)


def _refuse_interpreter_on_tpu(backend: str) -> None:
    if backend == "tpu" and "PYTEST_CURRENT_TEST" not in os.environ:
        raise ValueError("interpret=True on a TPU backend runs the Pallas "
                         "interpreter instead of the chip; only tests may "
                         "ask for that")
