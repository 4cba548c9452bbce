"""Battery definitions: SmallCrush (10), Crush (96), BigCrush (106).

Mirrors TestU01's structure: a battery is an ordered list of ENTRIES, each a
fixed parameterization of one of the ten test kernels (stats/tests.py).
Crush/BigCrush re-use the same kernels at more/larger parameter points —
exactly how TestU01's batteries relate (paper §3.1). ``scale`` lets the same
battery run laptop-sized (CI) or pod-sized.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List

from repro.stats import backends as B

# relative per-word cost weights (scan-heavy kernels cost more per word)
KERNEL_WEIGHT = {
    "birthday": 1.0, "collision": 1.0, "gap": 1.2, "poker": 1.0,
    "coupon": 6.0, "maxoft": 1.0, "weight": 0.6, "rank": 8.0,
    "hamcorr": 0.6, "serial2d": 0.8, "pairstream": 0.6,
}

# Historical discriminating power per kernel, seeded from the known-bad
# generators (rng/generators.py: RANDU and MINSTD shift their 31-bit state
# left, so bit 0 is constant — the bit-level kernels annihilate them:
# weight/rank give p = 0, hamcorr p ~ 1e-27, while the distributional
# kernels barely notice at CI scales). The adaptive schedule policy ranks
# jobs by DISCRIMINATION/cost, so a cheap killer like `weight` lands in
# round one and a bad generator is failed long before `coupon` or `rank`
# would have been dispatched. Static by design — the table is part of the
# battery definition, not of any one run's history (DESIGN.md §3).
DISCRIMINATION = {
    "weight": 1.0, "rank": 1.0, "hamcorr": 0.8,
    "birthday": 0.3, "serial2d": 0.3, "collision": 0.2,
    "gap": 0.15, "maxoft": 0.15, "poker": 0.1, "coupon": 0.05,
    # pairstream is a machinery check (seam disjointness), not a quality
    # test — any signal at all is a hard failure, so it screens first
    "pairstream": 1.0,
}


def discrimination(entry: "TestEntry") -> float:
    """Discriminating power of a battery entry (0 when kname is unknown —
    synthetic/test entries schedule by cost alone)."""
    return DISCRIMINATION.get(entry.kname, 0.0)


@dataclasses.dataclass(frozen=True)
class TestEntry:
    index: int                  # position in the pool's job table
    name: str
    kernel: Callable            # bits -> (stat, p)
    n_words: int                # uint32 words consumed
    cost: float                 # scheduler cost estimate
    kname: str = ""             # kernel family (enables re-parameterization)
    params: tuple = ()          # sorted (key, value) kernel kwargs
    group: int = -1             # original battery test index (== index
    #                             unless this entry is a sub-job)
    part: int = 0               # sub-job position within its group
    n_parts: int = 1            # group size (1 = not decomposed)
    backend: str = "reference"  # kernel backend the callable is bound to
    #                             (stats/backends.py registry)
    fallback: str = ""          # why the callable is the reference although
    #                             backend is "accelerated" ("" = it is not)

    def __post_init__(self):
        if self.group < 0:
            object.__setattr__(self, "group", self.index)

    @property
    def impl(self) -> str:
        """The implementation the callable really runs."""
        return "reference" if self.fallback else self.backend


def routing_report(entries: List[TestEntry]) -> List[str]:
    """One line per kernel family: how many of its entries run each
    implementation, and the reasons for any reference fallback under
    the accelerated backend."""
    fams: dict = {}
    for e in entries:
        f = fams.setdefault(e.kname or e.name, {"n": {}, "why": set()})
        f["n"][e.impl] = f["n"].get(e.impl, 0) + 1
        if e.fallback:
            f["why"].add(e.fallback)
    return [f"{k}: " + ", ".join(f"{n} {impl}"
                                 for impl, n in sorted(f["n"].items()))
            + (f" ({'; '.join(sorted(f['why']))})" if f["why"] else "")
            for k, f in fams.items()]


_WORDS = {
    "birthday": lambda k: k.get("n", 4096),
    "collision": lambda k: k.get("n", 65536),
    "gap": lambda k: k.get("n", 65536),
    "poker": lambda k: k.get("n", 32768) * 5,
    "coupon": lambda k: k.get("n", 65536),
    "maxoft": lambda k: k.get("n", 16384) * k.get("t", 8),
    "weight": lambda k: k.get("n", 65536),
    "rank": lambda k: k.get("n_mats", 1024) * 32,
    "hamcorr": lambda k: k.get("n", 65536),
    "serial2d": lambda k: k.get("n", 65536) * 2,
    "pairstream": lambda k: k.get("n", 32768) * 2,
}


def _mk(index, kname, scale, backend="reference", **kw):
    fn, fallback = B.route(kname, backend, **kw)
    words = _WORDS[kname](kw)
    name = kname + ("" if not kw else "_" + "_".join(
        f"{a}{v}" for a, v in sorted(kw.items())))
    return TestEntry(index, name, functools.partial(fn, **kw), words,
                     words * KERNEL_WEIGHT[kname] * scale,
                     kname=kname, params=tuple(sorted(kw.items())),
                     backend=backend, fallback=fallback)


_BASE = [  # SmallCrush: one instance of each kernel (explicit params so
    # `scale` applies; kernel defaults restated)
    ("birthday", dict(n=4096, tbits=30)), ("collision", dict(n=65536, kbits=26)),
    ("gap", dict(n=65536, beta=0.125)), ("poker", dict(n=32768)),
    ("coupon", dict(n=65536, d=8)), ("maxoft", dict(n=16384, t=8)),
    ("weight", dict(n=65536)), ("rank", dict(n_mats=1024)),
    ("hamcorr", dict(n=65536)), ("serial2d", dict(n=65536, d=64)),
]

# Crush/BigCrush parameter grids (per kernel). Sizes scale with `scale`.
_VARIANTS = {
    # (n, tbits) pairs keep lambda = n^3/4k in 2..128 (Poisson regime)
    "birthday": [dict(n=1024, tbits=26), dict(n=2048, tbits=28),
                 dict(n=2048, tbits=30), dict(n=4096, tbits=30),
                 dict(n=8192, tbits=30), dict(n=4096, tbits=28),
                 dict(n=1024, tbits=24), dict(n=2048, tbits=26),
                 dict(n=2048, tbits=24)],
    "collision": [dict(n=n, kbits=k) for n in (32768, 65536, 131072)
                  for k in (24, 26, 28)],
    "gap": [dict(n=n, beta=b) for n in (32768, 65536, 131072)
            for b in (0.0625, 0.125, 0.25)],
    "poker": [dict(n=n) for n in (16384, 32768, 65536, 131072)],
    "coupon": [dict(n=n, d=d) for n in (32768, 65536) for d in (4, 8, 16)],
    "maxoft": [dict(n=n, t=t) for n in (8192, 16384, 32768)
               for t in (4, 8, 16)],
    "weight": [dict(n=n) for n in (32768, 65536, 131072, 262144)],
    "rank": [dict(n_mats=m) for m in (512, 1024, 2048, 4096)],
    "hamcorr": [dict(n=n) for n in (32768, 65536, 131072, 262144)],
    "serial2d": [dict(n=n, d=d) for n in (32768, 65536, 131072)
                 for d in (16, 64, 128)],
}


def _scaled(kw, kname, scale):
    import math
    kw = dict(kw)
    orig_n = kw.get("n", 0)
    for key in ("n", "n_mats"):
        if key in kw:
            kw[key] = max(int(kw[key] * scale), 256)
    if kname == "birthday" and "n" in kw:
        # keep the Poisson rate lambda = n^3/4k invariant under scaling;
        # if tbits clamps, re-solve n from the target lambda instead
        lam0 = orig_n ** 3 / (4.0 * (1 << kw.get("tbits", 30)))
        tb = kw.get("tbits", 30) + round(3 * math.log2(max(scale, 1e-9)))
        kw["tbits"] = min(max(tb, 16), 30)
        n = int(round((lam0 * 4 * (1 << kw["tbits"])) ** (1 / 3)))
        # Poisson validity needs lambda << n; when tbits clamps hard the
        # re-solved n can leave lambda ~ n (the duplicate-spacing count
        # stops being Poisson and every generator skews p -> 1). Cap n at
        # sqrt(k)/2 so lambda = n^3/4k <= n/16 always holds.
        n = min(n, int(math.sqrt(1 << kw["tbits"]) / 2))
        kw["n"] = max(n, 128)
    if kname == "collision" and "n" in kw:
        # keep lambda = n^2/2k invariant (collision count regime)
        kb = kw.get("kbits", 26) + round(2 * math.log2(max(scale, 1e-9)))
        kw["kbits"] = min(max(kb, 14), 30)
    return kw


# The stream-seam battery (campaign subsystem, DESIGN.md §8): four
# pairstream variants over ONE shared block size, so every entry reads
# the same 2n-word window and the campaign can align all of them on the
# same adjacent-stream seam (rng.generators.seam_offsets). Modes probe
# different failure shapes of the offset machinery: float correlation,
# bit-level correlation, exact duplication, off-by-k seams.
_PAIRSTREAM = [
    ("pairstream", dict(n=32768, mode="corr")),
    ("pairstream", dict(n=32768, mode="hamcorr")),
    ("pairstream", dict(n=32768, mode="match")),
    ("pairstream", dict(n=32768, mode="shift")),
]


def build_battery(name: str, scale: float = 1.0,
                  backend: str = "reference") -> List[TestEntry]:
    """Battery job table. ``backend`` selects the kernel implementation
    family-wide (stats/backends.py): "reference", "accelerated", or
    "auto" (resolved here, so the table records a concrete backend)."""
    backend = B.resolve(backend)
    if name == "smallcrush":
        combos = [(k, _scaled(kw, k, scale)) for k, kw in _BASE]
    elif name == "pairstream":
        combos = [(k, _scaled(kw, k, scale)) for k, kw in _PAIRSTREAM]
    elif name in ("crush", "bigcrush"):
        target = 96 if name == "crush" else 106
        combos = []
        pools = {k: list(v) for k, v in _VARIANTS.items()}
        order = list(_VARIANTS)
        i = 0
        while len(combos) < target:
            k = order[i % len(order)]
            if pools[k]:
                combos.append((k, _scaled(pools[k].pop(0), k, scale)))
            i += 1
            if i > 10 * target:                  # pools exhausted -> rescale
                for k2 in order:
                    pools[k2] = [dict(kw, n=int(kw.get("n", 65536) * 2))
                                 if "n" in kw else kw
                                 for kw in _VARIANTS[k2]]
        combos = combos[:target]
    else:
        raise KeyError(name)
    return [_mk(i, k, scale, backend=backend, **kw)
            for i, (k, kw) in enumerate(combos)]


def max_words(entries: List[TestEntry]) -> int:
    """Widest bit-block any entry consumes; 0 for an empty table (an
    elastic replan of nothing must not raise)."""
    return max((e.n_words for e in entries), default=0)


def split_entry(entry: TestEntry, n_parts: int,
                start_index: int = 0) -> List[TestEntry]:
    """Over-decomposition: split one test into ``n_parts`` sub-jobs.

    Each sub-job is the same kernel re-parameterized lambda-invariantly at
    1/n_parts of the sample size (via ``_scaled``, so Poisson-regime tests
    keep their calibration) and draws its own disjoint generator sub-stream
    (see ``pool.stream_table``). The stitcher later folds the group's
    sub-p-values back into one verdict (Stouffer/Fisher combine).

    Parameter floors can keep a part from shrinking to 1/n_parts. The
    split then uses the most parts (at most ``n_parts``) that together
    read no more words than the test, and returns the entry unsplit when
    even two parts would: a split that adds work mitigates nothing.
    """
    if not entry.kname:
        return [dataclasses.replace(entry, index=start_index)]
    while n_parts > 1:
        sub_kw = _scaled(dict(entry.params), entry.kname, 1.0 / n_parts)
        sub_words = _WORDS[entry.kname](sub_kw)
        if sub_words * n_parts <= entry.n_words:
            break
        n_parts -= 1                                # floors bind: fewer parts
    if n_parts <= 1:
        return [dataclasses.replace(entry, index=start_index)]
    fn, fallback = B.route(entry.kname, entry.backend or "reference",
                           **sub_kw)
    sub_cost = entry.cost * (sub_words / max(entry.n_words, 1))
    return [
        TestEntry(start_index + p,
                  f"{entry.name}[{p + 1}/{n_parts}]",
                  functools.partial(fn, **sub_kw), sub_words, sub_cost,
                  kname=entry.kname, params=tuple(sorted(sub_kw.items())),
                  group=entry.group, part=p, n_parts=n_parts,
                  backend=entry.backend, fallback=fallback)
        for p in range(n_parts)
    ]
