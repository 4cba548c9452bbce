"""Profiler trace -> the intervals that the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two things, on the profiler's one clock (nanoseconds):

- ``host``: the harness's own spans (``jax.profiler.TraceAnnotation``):
  ``traced`` (the traced block of rounds), ``submit``, ``poll`` (one
  round), ``stitch``;
- ``devices``: per device, every operation the device ran, as
  ``(name, start, end, hlo)``: the op's name (``fusion.12``) and its HLO
  text where the trace gives it (shapes, custom-call target).

The reductions below (busy union, what it covers of a span, idle gaps)
are the arithmetic the metric readers share. ``Trace.to_json``/``from_json``
keep a small recorded trace for the tests.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Dict, List, Tuple

import numpy as np

HOST_SPANS = ("traced", "submit", "poll", "stitch")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_HLO_NAME = re.compile(r"^%([^\s=]+) = ")


def _op_name(text: str):
    """``(name, hlo)`` of a device op event, whose name on a TPU is the
    op's HLO text (``%fusion.12 = f32[30] fusion(...)``)."""
    m = _HLO_NAME.match(text)
    return (m.group(1), text) if m else (text, "")


@dataclasses.dataclass
class Trace:
    host: List[Tuple[str, int, int]]
    devices: Dict[int, List[Tuple[str, int, int, str]]]

    # -- construction ------------------------------------------------------

    @classmethod
    def load(cls, xplane_path: str) -> "Trace":
        """Read a profiler ``.xplane.pb``. Device operations are the
        events of each accelerator plane's ``XLA Ops`` line; on a host
        without accelerators, the XLA CPU client's op events (the ones
        carrying an ``hlo_op`` stat), by their ``device_ordinal``."""
        from jax.profiler import ProfileData
        data = ProfileData.from_file(xplane_path)
        host, devices = [], {}
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                ops = devices.setdefault(int(m.group(2)), [])
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        name, hlo = _op_name(ev.name)
                        ops.append((name, int(ev.start_ns), int(ev.end_ns),
                                    hlo))
                continue
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.end_ns)))
                    elif line.name.startswith("tf_XLAPjRtCpuClient"):
                        st = dict(ev.stats)
                        if "hlo_op" in st:
                            devices.setdefault(
                                int(st.get("device_ordinal", 0)), []
                            ).append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns), ""))
        host.sort(key=lambda s: s[1])
        for ops in devices.values():
            ops.sort(key=lambda o: o[1])
        return cls(host, devices)

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"host": self.host,
                       "devices": {str(k): v
                                   for k, v in self.devices.items()}}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls([tuple(s) for s in d["host"]],
                   {int(k): [tuple(o) for o in v]
                    for k, v in d["devices"].items()})

    # -- reductions --------------------------------------------------------

    def spans(self, name: str) -> List[Tuple[int, int]]:
        return [(a, b) for n, a, b in self.host if n == name]

    @property
    def window(self) -> Tuple[int, int]:
        """The traced window: the harness's ``traced`` span."""
        w = self.spans("traced")
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} traced spans, not 1")
        return w[0]

    def busy(self, device: int) -> "Intervals":
        """Union of the device's op intervals, clipped to the window."""
        lo, hi = self.window
        return union((max(a, lo), min(b, hi))
                     for _, a, b, _ in self.devices.get(device, ())
                     if b > lo and a < hi)

    def busy_all(self) -> "Intervals":
        """Union over every device: time in which any device ran an op."""
        lo, hi = self.window
        return union((max(a, lo), min(b, hi))
                     for ops in self.devices.values()
                     for _, a, b, _ in ops if b > lo and a < hi)


def union(intervals) -> "Intervals":
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return Intervals(out)


class Intervals:
    """Disjoint sorted intervals, with what is covered of any range in
    logarithmic time."""

    def __init__(self, pairs):
        arr = np.asarray(pairs, np.int64).reshape(-1, 2)
        self.starts, self.ends = arr[:, 0], arr[:, 1]
        self._cum = np.concatenate([[0], np.cumsum(self.ends - self.starts)])

    def __len__(self):
        return len(self.starts)

    def total(self) -> int:
        return int(self._cum[-1])

    def covered(self, lo: int, hi: int) -> int:
        """Nanoseconds of ``[lo, hi)`` that the intervals cover."""
        if hi <= lo or not len(self):
            return 0
        i = int(np.searchsorted(self.ends, lo, side="right"))
        j = int(np.searchsorted(self.starts, hi, side="left"))
        if j <= i:
            return 0
        tot = int(self._cum[j] - self._cum[i])
        tot -= max(0, lo - int(self.starts[i]))
        tot -= max(0, int(self.ends[j - 1]) - hi)
        return tot

    def gaps(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        """The idle intervals of ``[lo, hi)`` between these intervals."""
        out, t = [], lo
        for a, b in zip(self.starts.tolist(), self.ends.tolist()):
            if a > t:
                out.append((t, min(a, hi)))
            t = max(t, b)
        if t < hi:
            out.append((t, hi))
        return [(a, b) for a, b in out if b > a]


def self_times(ops, lo: int, hi: int) -> Dict[str, int]:
    """Nanoseconds each op ran in ``[lo, hi)`` less the time of the ops
    nested in it (a loop or a conditional holds the ops of its body),
    summed by op text."""
    out: Dict[str, int] = {}
    stack: List[list] = []          # [key, end, self ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0) + max(item[2], 0)

    for name, a, b, hlo in sorted(ops, key=lambda o: (o[1], -o[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= b - a
        stack.append([hlo or name, b, b - a])
    while stack:
        close(stack.pop())
    return out
