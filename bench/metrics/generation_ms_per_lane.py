"""Generation: device milliseconds one lane spends generating its job's
bit block, over the generator lanes of the traced block.

A job's block comes from the bucket switch of the round program
(``pool._job_fn``): a ``conditional`` whose result is a single
``u32[N]`` array (``%conditional.10 = (u32[16777216]{0:T(1024)})
conditional(...)``). The generator switch inside it
(``sources.switch_block``) is such a conditional too; taking only the
ones that run inside no other leaves one per lane of each round on
each of the pool's devices, so their count is the lanes times the jobs
of the block's rounds. A lane's time is the union of the device ops run
inside its conditional: the conditional's own time beyond them is the
switch choosing its branch, not generation (in a recorded SmallCrush
block, 21 us of conditional around 7 us of generation). Over a block
this is the device time under the program's ``repro.gen`` scope
(``bench/spans.py`` ``generation_ms_per_round``), read from the op text
that the harness keeps.

None when the block holds no such conditional. No roofline share: the
generators are emulated 64-bit integer arithmetic bound by the vector
units, and generation adds no kernel of its own.
"""
import re

import numpy as np

from bench.trace import union

GEN = re.compile(r"^%\S+ = \(u32\[\d+\]\{[^}]*\}\) conditional\(")


def lanes(trace, device: int):
    """``(start, end, ns)`` of each outermost ``u32[N]`` conditional of
    ``device`` inside the traced window, by start: ``ns`` is the union of
    the ops inside it."""
    lo, hi = trace.window
    ops = sorted((a, b) for _, a, b, _ in trace.devices.get(device, ())
                 if a < hi and b > lo)
    starts = np.asarray([a for a, _ in ops], np.int64)
    out = []
    for _, a, b, hlo in sorted(trace.devices.get(device, ()),
                               key=lambda o: (o[1], -o[2])):
        if not GEN.match(hlo) or a < lo or b > hi:
            continue
        if out and b <= out[-1][1]:
            continue                    # inside the last lane's
        i = int(np.searchsorted(starts, a, side="left"))
        j = int(np.searchsorted(starts, b, side="right"))
        inner = [(x, y) for x, y in ops[i:j] if y <= b and (x, y) != (a, b)]
        out.append((a, b, union(inner).total()))
    return out


def read(ctx):
    found = [ns for d in ctx.device_ids() for _, _, ns in lanes(ctx.trace, d)]
    return sum(found) / len(found) / 1e6 if found else None
