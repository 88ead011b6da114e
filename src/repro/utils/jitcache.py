"""Shape-bucketed compile caches — persistent executables across elastic
rounds.

Elastic FL rounds change the client count ``n`` every round; jitting a
fresh closure per round (the seed behavior of both engines) re-traces and
re-compiles the whole fusion program each time, which is exactly the
per-round launch overhead the paper's adaptive aggregator is meant to
avoid. The fix has two halves:

  * **bucketing** — round ``n`` up to the next power of two and zero-pad
    the weights, so every round with ``n`` in ``(B/2, B]`` shares ONE
    executable (padded rows carry weight 0 and contribute nothing to any
    reducible fusion);
  * **caching** — key compiled executables by (fusion, bucket, P, dtype,
    path) and reuse them for as long as the process lives, instead of
    rebuilding ``shard_map``/``jax.jit`` closures per ``fuse()`` call.

``trace_count()`` is a global monotone counter bumped every time one of
our cached builders is (re-)traced; tests assert it stays flat across
same-bucket rounds. ``CompiledCache`` also accounts compile seconds,
which feeds ``RoundReport.phase_seconds["compile"]`` and the Planner's
reuse term (warm engines are costed below cold ones).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from pathlib import Path
from typing import Callable, Dict, Hashable, Tuple

import jax

from repro.utils import spans

# the checkout root (this file is src/repro/utils/jitcache.py)
_CHECKOUT = Path(__file__).resolve().parents[3]


# -- persistent compilation cache ---------------------------------------------


def enable_persistent_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Entry points call this at start-up, never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here; otherwise the cache is ``.jax_cache/`` at the
    checkout root. The path is fixed — never a temp name, pid or time —
    because a directory that moves is never hit again. Every compile is
    kept, however short: the kernels compile in well under JAX's default
    one-second floor."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# -- trace accounting ---------------------------------------------------------

_TRACE_LOCK = threading.Lock()
_TRACE_COUNT = 0


def note_trace() -> None:
    """Called from INSIDE traced function bodies: executes once per trace
    (never on a compiled-cache hit), so the counter measures re-tracing."""
    global _TRACE_COUNT
    with _TRACE_LOCK:
        _TRACE_COUNT += 1


def trace_count() -> int:
    return _TRACE_COUNT


# -- bucketing ----------------------------------------------------------------


def round_up_pow2(n: int, floor: int = 1) -> int:
    n = max(int(n), int(floor))
    return 1 << (n - 1).bit_length()


def bucket_rows(n: int, floor: int = 8) -> int:
    """Client-count bucket: next power of two, with a small floor so tiny
    rounds (1..8 clients) all land in one bucket."""
    return round_up_pow2(n, floor)


def fusion_cache_key(fusion) -> Hashable:
    """Stable cache key for a fusion instance: name + hyperparameters.
    (Server-state fields like FedAvgM's velocity start with ``_`` and are
    not dataclass fields, so they never leak into the key.)"""
    if dataclasses.is_dataclass(fusion):
        fields = tuple(
            (f.name, getattr(fusion, f.name))
            for f in dataclasses.fields(fusion)
        )
        return (fusion.name, fields)
    return (fusion.name,)


# -- compiled-executable cache ------------------------------------------------


@dataclasses.dataclass
class CacheEntry:
    fn: Callable
    compile_seconds: float


class CompiledCache:
    """key -> compiled executable, with hit/miss and compile-time stats.

    Two styles:
      * ``get`` — AOT: the builder's function is jit'd, lowered against
        ShapeDtypeStructs and compiled immediately; the stored callable is
        the compiled executable (exact shapes/dtypes — which bucketing
        guarantees). Compile time is measured precisely.
      * ``get_jitted`` — lazy: stores a ``jax.jit`` object (used for
        ``shard_map`` closures whose sharded lowering wants real device
        inputs); jit's internal cache handles same-shape reuse, and the
        point is to stop rebuilding the closure per call.

    The compile path is SINGLE-FLIGHT per key: when two threads (two
    tenants' concurrent rounds) race the same shape bucket, exactly one
    compiles while the others block on that key's in-flight build and
    then share the finished executable as a hit — ``misses`` counts cold
    compiles actually paid, never duplicated work. Builds for DIFFERENT
    keys still proceed concurrently (the build itself runs outside the
    cache lock). If a build raises, its waiters retry and one of them
    takes over the build instead of caching the failure.
    """

    def __init__(self, name: str = "cache"):
        self.name = name
        self._entries: Dict[Hashable, CacheEntry] = {}
        self._lock = threading.Lock()
        # key -> Event for a build in flight; racers of the same key wait
        # here instead of compiling a duplicate executable
        self._building: Dict[Hashable, threading.Event] = {}
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0

    def __contains__(self, key: Hashable) -> bool:
        with self._lock:
            return key in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(
        self,
        key: Hashable,
        builder: Callable[[], Callable],
        *arg_specs,
    ) -> Tuple[Callable, float]:
        """Return ``(executable, compile_seconds_spent_now)`` — the second
        element is 0.0 on a hit, so callers can report a compile phase.
        ``arg_specs`` are ShapeDtypeStructs OR concrete (possibly sharded,
        committed) example arrays — the latter is what ``shard_map``
        closures need, since their sharded lowering binds to real input
        shardings."""
        done = self._claim(key)
        if done is not None:
            return done
        # Build outside the lock: compiling can take seconds and other
        # shapes' lookups must not serialize behind it. This thread owns
        # the key's in-flight slot; same-key racers wait in _claim.
        try:
            with spans.span("engine.compile", key=str(key)):
                fn = builder()

                def traced(*args):
                    note_trace()
                    return fn(*args)

                t0 = time.perf_counter()
                compiled = jax.jit(traced).lower(*arg_specs).compile()
                dt = time.perf_counter() - t0
            with self._lock:
                self._entries[key] = CacheEntry(
                    fn=compiled, compile_seconds=dt
                )
                self.misses += 1
                self.compile_seconds += dt
        finally:
            self._release(key)
        return compiled, dt

    def _claim(self, key: Hashable):
        """Return the cached ``(fn, 0.0)`` on a hit, else claim the
        key's build slot and return None (the caller must build and then
        ``_release``). A thread racing an in-flight build for the SAME
        key blocks until that build lands and shares it as a hit."""
        while True:
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self.hits += 1
                    return entry.fn, 0.0
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = threading.Event()
                    return None
            # same-key build in flight: wait, then re-check — a failed
            # build wakes us with no entry and we take over the slot
            ev.wait()

    def _release(self, key: Hashable) -> None:
        with self._lock:
            ev = self._building.pop(key, None)
        if ev is not None:
            ev.set()

    def get_jitted(
        self, key: Hashable, builder: Callable[[], Callable]
    ) -> Callable:
        """Cache a ``jax.jit``-wrapped builder output (lazy compile).
        Single-flight per key, like ``get``."""
        done = self._claim(key)
        if done is not None:
            return done[0]
        try:
            with spans.span("engine.compile", key=str(key)):
                fn = builder()

                def traced(*args):
                    note_trace()
                    return fn(*args)

                jitted = jax.jit(traced)
            with self._lock:
                self._entries[key] = CacheEntry(
                    fn=jitted, compile_seconds=0.0
                )
                self.misses += 1
        finally:
            self._release(key)
        return jitted

    def executables(self) -> Dict[Hashable, Callable]:
        """Snapshot of the cached executables by key. ``get`` entries are
        ``jax.stages.Compiled``, whose ``as_text()`` is the compiled HLO
        (a compiled Pallas kernel shows as ``tpu_custom_call``)."""
        with self._lock:
            return {k: e.fn for k, e in self._entries.items()}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
