"""The program's spans and device scopes, for the JAX profiler.

``span(name, **args)`` is a host span: a ``jax.profiler.TraceAnnotation``
named ``repro.<name>``. Under a profiler session (``jax.profiler.trace``)
it lands on the profiler's host clock, the clock the device planes are
on, with its integer ``args`` attached; with no session running it
records nothing and costs about a microsecond.

``scope(name)`` names the device work traced inside it: a
``jax.named_scope("repro.<name>")``. Each op traced in it carries
``.../repro.<name>/...`` in its HLO ``op_name`` metadata, which the
profiler shows for the device op. It changes no instruction and costs
nothing when the program runs.

Span args are integers: the profiler encodes them into the event's name
and cuts a string value at its first comma.

Spans (``core/api.py``, ``BatteryRun``; the children of a round nest in
its span):

- ``repro.round``: one ``poll`` that dispatches a round, with the run's
  session-wide id ``run``, the round's index ``round`` and its non-idle
  slots ``jobs``;
- ``repro.round.plan``: choosing the row, the runner and its arguments;
- ``repro.round.launch``: the runner calls, up to their return;
- ``repro.round.wait``: copying the results back, waiting for the device;
- ``repro.round.fold``: fault injection, the sanity gate, worker health
  and stitching the results in;
- ``repro.round.verdict``: the interim verdicts;
- ``repro.round.checkpoint``: the checkpoint, when the spec names one;
- ``repro.round.status``: the status ``poll`` returns;
- ``repro.finalize``: the stitched report and verdicts of ``result()``,
  with ``run``.

Scopes (``core/pool.py``): ``repro.gen`` (a job's bit block and its zero
pad) and ``repro.test.<family>`` (a test kernel; ``repro.test.custom``
for an entry with no family name). Inside ``repro.test.coupon``, each
pass of coupon's blocked scan is ``repro.test.coupon.pass``
(``stats/tests.py``).
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **args: int):
    """Context manager: the host span ``repro.<name>`` with ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    """Context manager: name the device ops traced inside it
    ``repro.<name>``."""
    return jax.named_scope(PREFIX + name)
