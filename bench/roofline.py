"""The work a kernel's algorithm needs, and its share of the roofline.

Work is counted from the kernel's inputs and outputs, never from what
the current implementation happens to do, so a later kernel for the
same job reads against the same yardstick:

- ``histogram`` (bin-count): reads ``n`` int32 bin indices and writes
  ``k`` float32 counts: ``4n + 4k`` bytes;
- ``gf2_rank``: reads ``n_mats`` 32x32 bit matrices (32 uint32 rows
  each) and writes one int32 rank each: ``128 n_mats + 4 n_mats`` bytes.

Neither does bf16 or int8 arithmetic, the only operations the chip has a
published peak for, so both are bounded by bytes over the HBM peak
(``bench/peaks.json``). The share is that least time over the kernel's
device time in the trace.
"""
from __future__ import annotations

import json
import os
import re
from typing import Iterable, Optional, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
_SHAPE = re.compile(r"\b(?:pred|[subf]\d+|bf16)\[([\d,]*)\]")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def histogram_bytes(n: int, k: int) -> int:
    return 4 * n + 4 * k


def gf2_rank_bytes(n_mats: int) -> int:
    return 128 * n_mats + 4 * n_mats


def shapes(hlo: str) -> Tuple[Tuple[int, ...], ...]:
    """Element counts of the array shapes in an HLO instruction's text,
    in order: the result first, then the operands."""
    out = []
    for dims in _SHAPE.findall(hlo):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n)
    return tuple(out)


def kernel_bytes(kernel: str, hlo: str) -> Optional[int]:
    """Bytes the algorithm of one call needs, from the call's HLO text
    (``None`` when the text does not give the shapes)."""
    s = shapes(hlo)
    if kernel == "histogram" and len(s) >= 2:
        return histogram_bytes(n=s[1], k=s[0])
    if kernel == "gf2_rank" and len(s) >= 1:
        return gf2_rank_bytes(n_mats=s[0])
    return None


def roofline_share(kernel: str, events: Iterable[Tuple[str, int, int, str]],
                   peak_bytes_per_s: float) -> Optional[float]:
    """Percent of the roofline over the kernel's calls: the least time
    (bytes over peak) summed over calls, over their device time. Calls
    are the ops named ``<kernel>`` or ``<kernel>.<n>`` that are custom
    calls; ``None`` when there is none, or one without shapes."""
    least = spent = 0.0
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    for name, a, b, hlo in events:
        if not pat.match(name) or "custom-call" not in hlo:
            continue
        nbytes = kernel_bytes(kernel, hlo)
        if nbytes is None:
            return None
        least += nbytes / peak_bytes_per_s
        spent += (b - a) / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
