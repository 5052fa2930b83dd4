"""Compilation caching for the corpus throughput engine — two layers.

1. The PERSISTENT cache: jax's on-disk compilation cache, so the second
   process-lifetime run of any (kernel, geometry, bucket shape) skips the
   XLA compile tax. ONE directory for every caller: JAX_COMPILATION_CACHE_DIR
   when the environment sets it, else the fixed ``<checkout>/.xla-cache``
   (git-ignored). The path is part of the cache key, so it never moves
   with a store or a temp dir. JEPSEN_TPU_NO_COMPILE_CACHE=1 disables it.
   Enabling is idempotent within a process.

2. The IN-PROCESS kernel LRU: one resolved checker callable per
   (kernel, model, bucket-shape) key, with hit/miss accounting surfaced
   through obs metrics (`sched.cache_hits` / `sched.cache_misses`) and
   the bench's `cache_hit_rate` field. The jit caches inside ops/ already
   dedupe by (model, geometry); this layer adds the SHAPE axis the bucket
   scheduler introduces, so the number of distinct compilations per
   kernel is exactly the bucket count — observable, not folklore.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable

from ..obs import get_metrics
from ..ops.limits import limits

_enabled_dir: str | None = None
_enable_lock = threading.Lock()


# The checkout root: the directory holding the package.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """THE persistent compile-cache directory (module docstring); the
    tuned profile and the calibration record live next to it."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".xla-cache"))


def enable_persistent_cache() -> str | None:
    """Point jax's persistent compilation cache at compile_cache_dir().
    Returns the active directory (None when disabled)."""
    global _enabled_dir
    if os.environ.get("JEPSEN_TPU_NO_COMPILE_CACHE"):
        return None
    with _enable_lock:
        if _enabled_dir is None:
            import jax

            cache_dir = compile_cache_dir()
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.5)
            _enabled_dir = cache_dir
        return _enabled_dir


class KernelCache:
    """Thread-safe LRU of resolved checker callables keyed by
    (kernel, model, bucket-shape). Values are built once per key by the
    caller-supplied builder and evicted least-recently-used past
    limits().kernel_cache_entries (evicting the wrapper frees nothing the
    jit caches still hold — the LRU bounds WRAPPER bookkeeping, while the
    persistent cache keeps recompiles of an evicted shape cheap)."""

    def __init__(self, capacity: int | None = None):
        from ..obs.sync import maybe_wrap

        self._capacity = capacity
        self._entries: OrderedDict[tuple, Any] = OrderedDict()
        self._lock = maybe_wrap(
            threading.Lock(), "sched.compile_cache.KernelCache._lock")
        self.hits = 0
        self.misses = 0

    def _cap(self) -> int:
        return self._capacity or limits().kernel_cache_entries

    def get(self, key: tuple, build: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                get_metrics().counter("sched.cache_hits").add(1)
                return self._entries[key]
            self.misses += 1
            get_metrics().counter("sched.cache_misses").add(1)
        value = build()   # build outside the lock: builders jit-trace
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self._cap():
                self._entries.popitem(last=False)
        return value

    def stats(self) -> dict:
        # Under the lock: a concurrent get() mutating hits/misses/
        # entries must not tear the snapshot (the serve daemon reads
        # stats from handler threads while its dispatcher populates).
        with self._lock:
            total = self.hits + self.misses
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries),
                    "hit_rate": (self.hits / total) if total else 0.0}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0


_KERNEL_CACHE = KernelCache()


def kernel_cache() -> KernelCache:
    """The process-wide scheduler kernel LRU."""
    return _KERNEL_CACHE
