"""The BitSource layer: pluggable generators + external bitstreams.

The paper's HTCondor pool never cared where the bits came from — it
shipped an executable, a battery and a stream of numbers. This module is
that indifference made explicit: every layer above (pool, api, campaign,
serve, launch) consumes an abstract **BitSource** instead of a name in a
closed generator dict, so the same adaptive batteries screen

  ``GeneratorSource``  an in-repo (or runtime-registered) generator — a
                       lane of the compiled ``lax.switch`` the pool's
                       jitted round program dispatches over; and
  ``CapturedSource``   bits we did NOT generate: a memory-mapped
                       ``.npy`` / raw-u32 capture (nonce dumps,
                       hardware-RNG output, a rival library's stream),
                       sharded by stream, entering the device program as
                       a prefetched host buffer rather than a switch
                       lane (``pool.gather_captured_bits``).

The generator registry is a PLUGIN surface (the ``register_policy`` /
``stats.backends.register`` discipline): ``register_generator`` appends
a block function under a stable, monotonically-assigned ``gen_id``, so
out-of-repo generators join newly-traced switches without invalidating
executables compiled before they existed (``PoolSession._runner`` keeps
per-switch-width slots). Ids are assignment-order stable: a restarted
process that re-registers the same generators in the same order (the
``--register`` CLI surface) resumes any checkpoint or ledger that named
them.

Offset convention (the ONE canonical spelling): ``offset=None`` means
"no offset — trace the offset-free path"; any integer or traced value
means "read words ``[offset, offset + n)``". Sources that cannot seek
(``counter_based=False``) raise the typed ``OffsetNotSupportedError``
from the single ``require_offsetable`` gate — every layer funnels its
refusal through here instead of re-implementing the check.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import numpy as np

from repro.common.compat import x64


class OffsetNotSupportedError(ValueError):
    """A non-zero stream offset was requested from a source that cannot
    seek (``counter_based=False``) — e.g. ``mwc``'s lag-1 carry chain
    has no cheap jump-ahead. Subclasses ``ValueError`` so pre-BitSource
    callers that caught the untyped refusal keep working."""


class CapturedBitsError(ValueError):
    """A ``CapturedSource`` read ran past the captured material (stream
    shard index or word range out of bounds) — the finite-file analogue
    of a generator's inexhaustible (seed, stream) sequence."""


def require_offsetable(source: "BitSource", offset,
                       where: str = "stream offset") -> None:
    """The single offset-capability gate: raise the typed
    ``OffsetNotSupportedError`` when ``offset`` is a non-zero Python int
    and ``source`` is not counter-based. ``None`` (the canonical
    "no offset" spelling) and 0 always pass."""
    if offset is None or not int(offset):
        return
    if not source.counter_based:
        raise OffsetNotSupportedError(
            f"source {source.name!r} is not offset-continuable "
            f"(counter_based=False); it cannot take a non-zero "
            f"{where}")


# ---------------------------------------------------------------------------
# the generator plugin registry


@dataclasses.dataclass(frozen=True)
class RegisteredGenerator:
    """One registry row: the block function, its stable switch lane id,
    and its declared offset capability."""
    name: str
    gen_id: int
    block_fn: Callable
    counter_based: bool


_REGISTRY: Dict[str, RegisteredGenerator] = {}

# live views, shared BY OBJECT with rng.generators for back-compat:
# mutated in place by register/unregister so imported references stay
# current after dynamic registration
GENERATORS: Dict[str, Callable] = {}
GEN_IDS: Dict[str, int] = {}


def _ensure_builtins() -> None:
    """Populate the registry with the in-repo generators on first use.

    Built-ins register as a side effect of importing
    ``repro.rng.generators``; a caller that reaches the registry
    through this module alone (``capture_generator`` in a fresh
    process, an external ``--register`` hook) must see the same nine
    lanes at the same ids, so every registry read bootstraps them
    lazily. The import is a no-op once ``rng.generators`` is loaded."""
    if not _REGISTRY:
        import repro.rng.generators  # noqa: F401 (registers built-ins)


def register_generator(name: str, block_fn: Callable, *,
                       counter_based: bool) -> RegisteredGenerator:
    """Add a generator to the plugin registry under the next stable id.

    ``block_fn(seed, stream, n[, offset]) -> uint32[n]`` must be
    traceable inside the battery's jitted programs. ``counter_based``
    is a REQUIRED declaration (RPA403): ``True`` promises exact
    continuation — ``block(n=2k) == block(n=k) ++ block(n=k, offset=k)``
    — which is what stream offsets, over-decomposition and campaign
    sub-stream grids rely on. Duplicate names are a hard error (a
    silent overwrite would re-key every checkpoint and cache digest
    that named the original). Ids are assigned in registration order
    and never reused, so a restarted daemon that re-registers the same
    generators in the same order resumes its checkpoints and ledgers."""
    _ensure_builtins()
    if name in _REGISTRY:
        raise ValueError(
            f"generator {name!r} is already registered (gen_id="
            f"{_REGISTRY[name].gen_id}); duplicate registration is a "
            f"hard error — unregister_generator first if this is a "
            f"deliberate replacement")
    row = RegisteredGenerator(name, len(_REGISTRY), block_fn,
                              bool(counter_based))
    _REGISTRY[name] = row
    GENERATORS[name] = block_fn
    GEN_IDS[name] = row.gen_id
    return row


def unregister_generator(name: str) -> None:
    """Remove the MOST RECENTLY registered generator (test teardown /
    deliberate replacement). Only the last id may be retired — ids are
    stable by construction, so popping from the middle would renumber
    every later lane and silently re-key their checkpoints."""
    if name not in _REGISTRY:
        raise KeyError(f"generator {name!r} is not registered")
    if _REGISTRY[name].gen_id != len(_REGISTRY) - 1:
        raise ValueError(
            f"generator {name!r} (gen_id={_REGISTRY[name].gen_id}) is "
            f"not the most recently registered; ids are stable — only "
            f"the last lane may be retired")
    del _REGISTRY[name]
    del GENERATORS[name]
    del GEN_IDS[name]


def registry_size() -> int:
    """Current switch width: the number of registered generators."""
    _ensure_builtins()
    return len(_REGISTRY)


def counter_based_names() -> Tuple[str, ...]:
    """Names of the offset-continuable generators, in id order — the
    DERIVED successor of the retired static ``COUNTER_BASED`` tuple."""
    _ensure_builtins()
    return tuple(r.name for r in _REGISTRY.values() if r.counter_based)


def get_generator(name: str) -> RegisteredGenerator:
    """The registry row for ``name`` (KeyError with the known set and a
    re-registration hint — an external generator must be re-registered
    before a checkpoint or ledger that names it can resume)."""
    _ensure_builtins()
    row = _REGISTRY.get(name)
    if row is None:
        raise KeyError(
            f"unknown generator {name!r}; known: {sorted(_REGISTRY)} "
            f"(an external generator must be re-registered via "
            f"register_generator before resuming work that names it)")
    return row


def switch_block(gen_id, seed, stream, n, offset=None):
    """lax.switch-able: uint32[n] block from registered lane #gen_id.

    The folded successor of ``gen_block_by_id`` with ONE offset
    convention: ``offset=None`` (canonical "no offset") traces exactly
    the offset-free branches — the classic battery hot path; anything
    else is routed as a runtime offset to every counter-based branch.
    A non-counter-based branch under an offset folds it into the stream
    id (a RESEEDED stream, not a sub-stream) purely so the switch
    traces uniformly — offset use is refused upstream by the single
    ``require_offsetable`` gate, never silently served here. The branch
    list snapshots the registry at TRACE time: generators registered
    later join the next trace (``PoolSession`` keys runners by switch
    width, so existing executables are neither used for the new lane
    nor retraced for the old ones)."""
    rows = list(_REGISTRY.values())
    if offset is None:
        fns = [functools.partial(r.block_fn, seed, stream, n)
               for r in rows]
        return jax.lax.switch(gen_id, fns)

    def _offset_fn(row):
        if row.counter_based:
            return functools.partial(row.block_fn, seed, stream, n,
                                     offset)
        u64 = functools.partial(jax.numpy.asarray, dtype=jax.numpy.uint64)
        return lambda: row.block_fn(
            seed, u64(stream) + (u64(offset) << u64(32)), n)
    return jax.lax.switch(gen_id, [_offset_fn(r) for r in rows])


# ---------------------------------------------------------------------------
# sources


class BitSource:
    """The abstract bit-supply seam every upper layer consumes.

    Contract: ``block(seed, stream, n, offset=None) -> uint32[n]`` (a
    fresh, order-independent stream per (seed, stream) pair; ``offset``
    reads words ``[offset, offset + n)`` when ``counter_based``),
    ``name`` (the short reporting key), ``uid()`` (stable identity a
    checkpoint/ledger stores and cross-checks on resume), ``digest()``
    (content identity a cache key folds in — for captured bits this is
    the FILE content, so a re-captured file misses), ``captured``
    (True routes dispatch through the prefetched-buffer path instead of
    a switch lane)."""

    name: str = ""
    counter_based: bool = False
    captured: bool = False

    def block(self, seed, stream, n, offset=None):
        """uint32[n] — words ``[offset or 0, (offset or 0) + n)`` of the
        (seed, stream) sequence."""
        raise NotImplementedError

    def uid(self) -> str:
        """Stable identity string for checkpoints/ledgers (resume
        cross-check): same source -> same uid, across processes."""
        raise NotImplementedError

    def digest(self) -> str:
        """Content identity for cache keys. Equals the pre-BitSource
        generator name for generator sources (digest stability), and a
        content hash for captured bits."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class GeneratorSource(BitSource):
    """A registered generator as a BitSource — the compiled-switch
    family. Frozen and hashable on the name alone: the registry row is
    looked up live, so a source built before ``register_generator``
    grew the registry still dispatches correctly."""
    name: str

    def __post_init__(self):
        get_generator(self.name)            # validate early, KeyError

    @property
    def gen_id(self) -> int:
        """The stable switch lane id (registry assignment order)."""
        return get_generator(self.name).gen_id

    @property
    def counter_based(self) -> bool:
        """The registry's declared offset capability for this name."""
        return get_generator(self.name).counter_based

    @property
    def captured(self) -> bool:
        """Generator sources dispatch through the compiled switch."""
        return False

    def block(self, seed, stream, n, offset=None):
        """The registered block function (traceable; ``offset=None``
        keeps the offset-free trace)."""
        fn = get_generator(self.name).block_fn
        if offset is None:
            return fn(seed, stream, n)
        return fn(seed, stream, n, offset)

    def uid(self) -> str:
        """``gen:<name>`` — algorithmic identity."""
        return f"gen:{self.name}"

    def digest(self) -> str:
        """The bare name: bitwise-compatible with every cache digest
        minted before the BitSource layer existed."""
        return self.name


class CapturedSource(BitSource):
    """External bits from a memory-mapped file, sharded by stream.

    Formats: ``.npy`` (uint32; 1-D = one stream, 2-D = (streams,
    words-per-stream)) or raw little-endian u32 (``fmt="u32"``, one
    stream). The file is mapped, never loaded: a million-word capture
    costs pages, not RAM. ``seed`` is accepted and ignored (the bits
    are what they are); ``counter_based`` is True — an offset is just a
    different read position — so captured cells take campaign
    sub-stream offsets. Reads past the captured material raise the
    typed ``CapturedBitsError`` naming the stream shard.

    ``digest()`` hashes the FILE CONTENT (cached per (size, mtime)):
    two captures of the same hardware at different times are different
    cells, and a byte-modified copy MISSES every cache entry the
    original earned."""

    def __init__(self, path: str, fmt: Optional[str] = None):
        self.path = os.path.abspath(path)
        if fmt is None:
            fmt = "npy" if self.path.endswith(".npy") else "u32"
        if fmt not in ("npy", "u32"):
            raise ValueError(f"unknown captured format {fmt!r}; "
                             f"known: ('npy', 'u32')")
        self.fmt = fmt
        if fmt == "npy":
            arr = np.load(self.path, mmap_mode="r")
        else:
            arr = np.memmap(self.path, dtype="<u4", mode="r")
        if arr.dtype != np.uint32:
            raise ValueError(
                f"captured file {path} holds {arr.dtype}, expected "
                f"uint32 words")
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        if arr.ndim != 2:
            raise ValueError(
                f"captured file {path} has shape {arr.shape}; expected "
                f"1-D words or 2-D (streams, words)")
        self._arr = arr
        self.n_streams, self.stride = map(int, arr.shape)
        self.name = f"cap:{os.path.splitext(os.path.basename(path))[0]}"
        self._digest: Optional[str] = None

    counter_based = True
    captured = True

    def __eq__(self, other):
        return (isinstance(other, CapturedSource)
                and (self.path, self.fmt) == (other.path, other.fmt))

    def __hash__(self):
        return hash((self.path, self.fmt))

    def __repr__(self):
        return (f"CapturedSource({self.path!r}, fmt={self.fmt!r}, "
                f"streams={self.n_streams}, stride={self.stride})")

    def block(self, seed, stream, n, offset=None):
        """Words ``[offset, offset + n)`` of stream shard ``stream`` —
        a host-side mmap read (the pool prefetches these into the
        device program; they never pass through a switch lane)."""
        del seed                        # captured bits have no seed
        s, off, n = int(stream), int(offset or 0), int(n)
        if not 0 <= s < self.n_streams:
            raise CapturedBitsError(
                f"{self.name}: stream {s} out of range — the capture "
                f"holds {self.n_streams} stream shard(s)")
        if off < 0 or off + n > self.stride:
            raise CapturedBitsError(
                f"{self.name}: stream {s} read [{off}, {off + n}) "
                f"exceeds the captured {self.stride} word(s) per "
                f"stream — capture more bits or shrink the battery")
        return np.asarray(self._arr[s, off:off + n], np.uint32)

    def uid(self) -> str:
        """``cap:<stem>:<digest16>`` — identity INCLUDING content, so a
        checkpoint resumed against a re-captured file is refused."""
        return f"{self.name}:{self.digest()[:16]}"

    def digest(self) -> str:
        """sha256 of the raw file bytes (cached per (size, mtime))."""
        stat = os.stat(self.path)
        tag = (stat.st_size, stat.st_mtime_ns)
        if self._digest is None or self._digest_tag != tag:
            h = hashlib.sha256()
            with open(self.path, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
            self._digest = h.hexdigest()
            self._digest_tag = tag
        return self._digest


def resolve_source(spec: Union[BitSource, str]) -> BitSource:
    """One source from its declarative spelling: a ``BitSource`` passes
    through; ``"name"`` -> ``GeneratorSource``; ``"file:path[:fmt]"``
    -> ``CapturedSource`` (the CLI's ``--source`` grammar)."""
    if isinstance(spec, BitSource):
        return spec
    if not isinstance(spec, str):
        raise TypeError(f"source spec must be a BitSource or str, "
                        f"got {type(spec).__name__}")
    if spec.startswith("file:"):
        rest = spec[len("file:"):]
        path, sep, fmt = rest.rpartition(":")
        if not sep or os.sep in fmt or fmt not in ("npy", "u32"):
            path, fmt = rest, None
        return CapturedSource(path, fmt)
    return GeneratorSource(spec)


# ---------------------------------------------------------------------------
# capture helper (the ingest-smoke path)


def capture_generator(name: str, path: str, seed: int, n_streams: int,
                      stride: int, fmt: str = "npy") -> str:
    """Materialize a registered generator's bits as a captured file:
    stream shard s holds words ``[0, stride)`` of the generator's
    (seed, s) sequence — exactly what ``CapturedSource`` serves back,
    so a battery over the capture is bitwise the battery over the
    generator (the ingest-smoke parity assertion). Returns ``path``."""
    row = get_generator(name)
    if n_streams < 1 or stride < 1:
        raise ValueError(f"need n_streams >= 1 and stride >= 1, got "
                         f"{n_streams}, {stride}")
    with x64():
        shards = [np.asarray(row.block_fn(seed, s, stride), np.uint32)
                  for s in range(n_streams)]
    words = np.stack(shards)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if fmt == "npy":
        np.save(path, words)
    elif fmt == "u32":
        if n_streams != 1:
            raise ValueError("raw u32 captures are single-stream; use "
                             "fmt='npy' for sharded captures")
        words.astype("<u4").tofile(path)
    else:
        raise ValueError(f"unknown capture format {fmt!r}")
    return path
