"""Bounded cache of compiled pipeline executables (DESIGN.md §12.3).

Every jitted program the pipeline builds per static configuration — the
fused ``run_pipeline_device`` executables, the vmapped TMFG builder
behind ``cluster_batch``, and the device DBHT programs — used to live in
per-module ``functools.lru_cache(maxsize=None)`` closures: a compiled-
executable leak, because XLA re-specializes per (config, shape) and a
long-lived service (the stream scheduler's jit buckets) touches an
unbounded set of both.  This module is the one shared, *bounded* LRU
those call sites register into, with an explicit :func:`clear` for
tests and long-running processes.

Eviction drops the jitted callable, which releases every per-shape XLA
executable compiled under it.  The default bound (64) is far above what
a steady-state service needs — the stream scheduler's power-of-two
bucketing exists precisely to keep the live set small — so eviction
only fires under config churn, where recompiling is the lesser evil.

Observability (DESIGN.md §15.3): the hit/miss/eviction counters, the
live size and the LRU head's idle age are exported through the
``repro.obs`` metrics registry (collector ``jitcache``), each entry
carries a last-hit timestamp (:func:`last_hit_ages` feeds the eviction
gauge), and :func:`reset_stats` zeroes the counters so per-run rates
don't inherit a previous run's history (``clear()`` keeps counters,
matching its pre-§15 contract).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Any, Callable, Dict, Hashable

import jax

from repro.obs import metrics as _obs_metrics

DEFAULT_MAXSIZE = 64

# the lru_caches this module replaces were internally locked; concurrent
# submitters sharing the stream service get the same guarantee here
_lock = threading.RLock()
_cache: "OrderedDict[Hashable, Any]" = OrderedDict()
_maxsize = DEFAULT_MAXSIZE
_stats: Dict[str, int] = {"hits": 0, "misses": 0, "evictions": 0}
# monotonic last-access (create or hit) per live key; evicted with it
_last_hit: Dict[Hashable, float] = {}


def cached(key: Hashable, build: Callable[[], Any]) -> Any:
    """The executable for ``key``, building (and caching) it on miss."""
    with _lock:
        if key in _cache:
            fn = _cache[key]
            _cache.move_to_end(key)
            _stats["hits"] += 1
            _last_hit[key] = time.monotonic()
            return fn
        fn = build()
        _stats["misses"] += 1
        _cache[key] = fn
        _last_hit[key] = time.monotonic()
        while len(_cache) > _maxsize:
            old, _ = _cache.popitem(last=False)
            _last_hit.pop(old, None)
            _stats["evictions"] += 1
        return fn


def contains(key: Hashable) -> bool:
    """Whether ``key`` is live in the cache, without touching LRU order
    or statistics — the pipeline's replay probe for the recompile
    watchdog (DESIGN.md §15.2)."""
    with _lock:
        return key in _cache


def clear() -> None:
    """Drop every cached executable (stats are kept)."""
    with _lock:
        _cache.clear()
        _last_hit.clear()


def reset_stats() -> None:
    """Zero the hit/miss/eviction counters (DESIGN.md §15.3): a
    long-lived process measuring per-run hit rates must not average
    against every run that came before."""
    with _lock:
        for k in _stats:
            _stats[k] = 0


def size() -> int:
    with _lock:
        return len(_cache)


def keys():
    """Snapshot of the cached keys, LRU-first (introspection/tests)."""
    with _lock:
        return list(_cache)


def stats() -> Dict[str, int]:
    """Copy of the hit/miss/eviction counters."""
    with _lock:
        return dict(_stats)


def last_hit_ages() -> Dict[Hashable, float]:
    """Seconds since each live key was last served (LRU-first order) —
    the per-key staleness behind the eviction gauge (DESIGN.md §15.3)."""
    now = time.monotonic()
    with _lock:
        return {k: now - _last_hit[k] for k in _cache}


def oldest_idle_s() -> float:
    """Idle age of the LRU head — the next eviction victim's staleness
    (0.0 when empty)."""
    with _lock:
        if not _cache:
            return 0.0
        head = next(iter(_cache))
        return time.monotonic() - _last_hit[head]


def set_maxsize(n: int) -> int:
    """Set the bound (evicting down to it); returns the previous bound."""
    global _maxsize
    if n < 1:
        raise ValueError(f"maxsize must be >= 1, got {n}")
    with _lock:
        prev, _maxsize = _maxsize, n
        while len(_cache) > _maxsize:
            old, _ = _cache.popitem(last=False)
            _last_hit.pop(old, None)
            _stats["evictions"] += 1
        return prev


def _collect() -> Dict[str, float]:
    with _lock:
        return {
            "jitcache_hits_total": _stats["hits"],
            "jitcache_misses_total": _stats["misses"],
            "jitcache_evictions_total": _stats["evictions"],
            "jitcache_size": len(_cache),
            "jitcache_oldest_idle_seconds": oldest_idle_s(),
        }


_obs_metrics.register_collector("jitcache", _collect)


# the in-checkout home of JAX's persistent compilation cache when
# JAX_COMPILATION_CACHE_DIR is unset: one fixed path, since the path is
# part of what makes a cache entry findable again (.gitignore lists it)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Give JAX's persistent compilation cache a directory; returns it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.  Entry points (``chip_smoke.py``,
    ``benchmarks/run.py``) call this once at start-up; importing the
    library never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
