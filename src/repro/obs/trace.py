"""Span-based device-true tracer + recompile watchdog (DESIGN.md §15.1).

The repo's timing story used to be five scattered ``time.perf_counter()``
dicts, and it shipped a false regression because of it: BENCH_5 "showed"
hub APSP losing to exact when the bench was really timing XLA
compilation (fixed in PR 6), and the staged pipeline's stage splits
measured async dispatch.  This module is the one timing primitive
everything else now routes through:

* :func:`span` — a nestable, thread-safe timing context.  Spans always
  measure (callers read ``sp.duration`` to populate e.g.
  ``ClusterResult.timings``); they are *collected* into the global
  trace buffer only while tracing is enabled (:func:`enable` /
  :func:`tracing`), so the buffer costs nothing in steady state.
* device-true fencing — ``sp.fence(x)`` calls ``jax.block_until_ready``
  on ``x`` when the span was opened with ``fence=True``, so the
  recorded duration covers device *execution*, not dispatch.  A span
  opened with ``fence=False`` never syncs: the fused pipeline's
  zero-extra-sync contract (DESIGN.md §15.1) is pinned by a
  no-``block_until_ready`` test in tests/test_obs.py.
* compile-vs-run separation (DESIGN.md §15.2) — a persistent
  ``jax.monitoring`` listener counts every XLA backend compile and its
  duration.  Each span records the compiles that happened inside it
  (``sp.compiles`` / ``sp.compile_s``; ``sp.run_s`` is the remainder),
  :func:`watch_recompiles` watches a region (the benchmarks' replay
  legs assert ``count == 0``), and :func:`record_recompile` is the
  runtime watchdog's alarm: the pipeline calls it whenever a *replayed*
  (config, shape) executable lowers a new program — the event lands in
  an always-on bounded log surfaced by ``ClusterService.healthz()``.
* the kept ring (DESIGN.md §15.1) — spans opened with ``keep=True``
  (one ``pipeline.fused`` record per fused call, carrying the call's
  phase seconds and loop counters as attributes) are held in a small
  bounded ring even while tracing is off, so a reader can pick out the
  calls of any window afterwards (:func:`kept_spans`).
* opt-in stage marks (DESIGN.md §15.5) — :class:`StageMarks` puts a
  host-clock mark at each stage boundary inside a jitted program.  It is a
  host callback, and JAX never writes a persistent-cache entry for an
  executable with host callbacks, so the pipeline builds marked
  programs only while tracing is enabled.

The listener itself is registered once at import and does work only
when XLA actually compiles, so the whole module is zero-cost on the
steady-state hot path.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax._src import profiler as _jax_profiler

# the jax.monitoring event XLA emits once per backend compilation; its
# duration is the device-true compile cost of that one program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.RLock()
_local = threading.local()          # per-thread active-span stack

_enabled = False
_tracing_depth = 0                  # open tracing() sessions, all threads
_records: List["Span"] = []         # completed spans, append order
_MAX_RECORDS = 65536                # hard cap: tracing never grows unbounded

# spans opened with keep=True, always (tracing on or off), newest last
KEPT_MAX = 1024
_kept: "deque[Span]" = deque(maxlen=KEPT_MAX)

# (stage, host perf_counter) of every stage mark fired, until taken
_marks: List[Tuple[str, float]] = []

# cumulative compile counters (always on; fed by the monitoring listener)
_compile_count = 0
_compile_secs = 0.0

# the runtime recompile watchdog's alarm log: replayed (config, shape)
# executables that lowered a NEW program anyway.  Always on, bounded.
_recompile_log: "deque[Dict[str, Any]]" = deque(maxlen=1024)
_recompile_count = 0


def _on_compile_event(event: str, duration: float, **kwargs) -> None:
    global _compile_count, _compile_secs
    if event != _COMPILE_EVENT:
        return
    with _lock:
        _compile_count += 1
        _compile_secs += duration


_registered = False


def _ensure_listener() -> None:
    global _registered
    if _registered:
        return
    _registered = True
    from jax._src import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile_event)


_ensure_listener()


# ---------------------------------------------------------------------------
# spans (§15.1)
# ---------------------------------------------------------------------------

@dataclass
class Span:
    """One completed (or active) timing span."""

    name: str
    fenced: bool = False
    depth: int = 0
    parent: Optional[str] = None
    thread: int = 0
    start: float = 0.0
    duration: float = 0.0           # wall seconds, fenced when ``fenced``
    compiles: int = 0               # XLA programs compiled inside the span
    compile_s: float = 0.0          # their summed backend-compile seconds
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def run_s(self) -> float:
        """Duration with the span's compile time subtracted — the
        steady-state cost a warm replay would pay (DESIGN.md §15.2)."""
        return max(self.duration - self.compile_s, 0.0)

    def fence(self, x):
        """Block until ``x``'s device computation finishes — but only
        when the span was opened with ``fence=True``; an unfenced span
        adds NO device sync.  Returns ``x`` either way."""
        if self.fenced and x is not None:
            jax.block_until_ready(x)
        return x

    def to_dict(self) -> Dict[str, Any]:
        return dict(kind="span", name=self.name, depth=self.depth,
                    parent=self.parent, thread=self.thread,
                    start=self.start, duration=self.duration,
                    fenced=self.fenced, compiles=self.compiles,
                    compile_s=self.compile_s, run_s=self.run_s,
                    **({"attrs": self.attrs} if self.attrs else {}))


def _profiler_on() -> bool:
    """Whether a ``jax.profiler`` session is collecting right now (JAX
    has no public accessor; ``start_trace`` sets this state)."""
    return _jax_profiler._profile_state.profile_session is not None


def _stack() -> List[Span]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


@contextmanager
def span(name: str, *, fence: bool = False, keep: bool = False, **attrs):
    """Time a region; nestable and thread-safe (each thread keeps its
    own stack).  The span object is yielded so callers can read
    ``sp.duration`` / ``sp.run_s`` afterwards, ``sp.fence(value)``
    device outputs at stage boundaries and add ``sp.attrs``
    (DESIGN.md §15.1).

    Spans always measure; they are appended to the global trace buffer
    only while tracing is :func:`enable`\\ d, and to the kept ring
    whenever ``keep`` is set.  While a ``jax.profiler`` session is on,
    the span is also a ``TraceAnnotation``, so it sits on the device
    trace's own clock."""
    st = _stack()
    sp = Span(name=name, fenced=fence, depth=len(st),
              parent=st[-1].name if st else None,
              thread=threading.get_ident(), attrs=dict(attrs))
    ann = None
    if _profiler_on():
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    with _lock:
        c0, s0 = _compile_count, _compile_secs
    st.append(sp)
    sp.start = time.perf_counter()
    try:
        yield sp
    finally:
        sp.duration = time.perf_counter() - sp.start
        st.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        with _lock:
            # cross-thread compiles can leak into the delta; single-
            # threaded callers (every current caller) see exact counts
            sp.compiles = _compile_count - c0
            sp.compile_s = _compile_secs - s0
            if (_enabled or _tracing_depth) and len(_records) < _MAX_RECORDS:
                _records.append(sp)
            if keep:
                _kept.append(sp)


# ---------------------------------------------------------------------------
# enable/disable + buffer access
# ---------------------------------------------------------------------------

def enable() -> None:
    """Start collecting spans into the trace buffer."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled or _tracing_depth > 0


@contextmanager
def tracing():
    """Scoped :func:`enable` (the usual way to take a trace).

    Sessions are *refcounted*, not save/restored: two threads (or two
    nested regions) may hold overlapping ``tracing()`` sessions and
    collection stays on until the LAST one exits — a save/restore of
    the flag would let the first thread to leave switch tracing off
    under the one still inside (pinned by tests/test_obs.py)."""
    global _tracing_depth
    with _lock:
        _tracing_depth += 1
    try:
        yield
    finally:
        with _lock:
            _tracing_depth -= 1


def spans(name: Optional[str] = None) -> List[Span]:
    """Snapshot of collected spans (optionally filtered by name)."""
    with _lock:
        out = list(_records)
    return out if name is None else [s for s in out if s.name == name]


def kept_spans(name: Optional[str] = None) -> List[Span]:
    """Snapshot of the kept ring (spans opened with ``keep=True``, at
    most :data:`KEPT_MAX`, oldest first), optionally filtered by name.
    Filled whether or not tracing is on: the fused pipeline keeps one
    ``pipeline.fused`` record per call (DESIGN.md §15.1)."""
    with _lock:
        out = list(_kept)
    return out if name is None else [s for s in out if s.name == name]


def clear() -> None:
    """Drop collected spans (the kept ring and the compile counters are
    always-on views; see :func:`watch_recompiles` for windowed
    readings)."""
    with _lock:
        _records.clear()


# ---------------------------------------------------------------------------
# opt-in stage marks inside a jitted program (§15.5)
# ---------------------------------------------------------------------------

def _on_mark(stage: str, x) -> np.ndarray:
    t = time.perf_counter()
    with _lock:
        _marks.append((stage, t))
    return np.full(np.shape(x), -0.0, np.float32)


class StageMarks:
    """Host-clock marks at the stage boundaries of one traced program
    body (DESIGN.md §15.5); a no-op unless ``on``.

    ``marks(stage, xs)`` marks the end of ``stage`` and returns ``xs``,
    the values the next stage reads.  The mark's host callback takes one
    scalar, the sum of the first element of every array of ``xs``, so it
    fires once all of them are computed and nothing large crosses to the
    host.  The callback answers ``-0.0``, and every array of ``xs`` comes
    back with that added (``x + -0.0`` is ``x`` bit for bit; 0 or False
    for integers and booleans), so the next stage reads data that waits
    for the mark.  An optimization barrier cannot order them: XLA drops
    barriers before scheduling.  Under ``vmap`` the callback fires once
    per stage for the whole batch."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, stage: str, xs):
        if not self.on:
            return xs
        leaves = jax.tree.leaves(xs)
        s = sum(jnp.ravel(x)[0].astype(jnp.float32) for x in leaves)
        tok = jax.pure_callback(functools.partial(_on_mark, stage),
                                jax.ShapeDtypeStruct((), jnp.float32), s,
                                vmap_method="expand_dims")
        return jax.tree.map(lambda x: x + tok.astype(x.dtype), xs)


def take_marks() -> List[Tuple[str, float]]:
    """The ``(stage, perf_counter)`` marks fired since the last call,
    oldest first; clears them."""
    with _lock:
        out = list(_marks)
        _marks.clear()
    return out


# ---------------------------------------------------------------------------
# compile counters + the recompile watchdog (§15.2)
# ---------------------------------------------------------------------------

def compile_stats() -> Dict[str, float]:
    """Cumulative process-wide XLA compile counters (always on)."""
    with _lock:
        return {"programs": _compile_count, "compile_s": _compile_secs,
                "recompile_events": _recompile_count}


class _Watch:
    """View over a watched region's compile activity: live while the
    ``with`` block is open, frozen at its deltas once the block exits
    (so compiles that happen *after* the region never leak into a
    reading taken later — e.g. a baseline timed right after a replay
    watch)."""

    def __init__(self):
        with _lock:
            self._c0, self._s0 = _compile_count, _compile_secs
            self._r0 = _recompile_count
        self._end = None                 # (count, secs, recompiles) caps

    def _freeze(self) -> None:
        with _lock:
            self._end = (_compile_count, _compile_secs, _recompile_count)

    def _now(self, i: int):
        if self._end is not None:
            return self._end[i]
        with _lock:
            return (_compile_count, _compile_secs, _recompile_count)[i]

    @property
    def count(self) -> int:
        """XLA programs compiled inside the watched region."""
        return self._now(0) - self._c0

    @property
    def compile_s(self) -> float:
        return self._now(1) - self._s0

    @property
    def recompile_events(self) -> int:
        """Watchdog *alarms* (replayed executables that compiled) inside
        the region — distinct from first-time compiles."""
        return self._now(2) - self._r0


@contextmanager
def watch_recompiles():
    """Watch a region for XLA compilation (DESIGN.md §15.2).

    ``with watch_recompiles() as w: ...`` — afterwards (or live inside)
    ``w.count``/``w.compile_s`` report the programs compiled in the
    region and their device-true compile seconds; the deltas freeze
    when the block exits.  A replay leg at a fixed (config, shape) must
    report ``w.count == 0``; the benchmarks' ``--check-schema`` CI gate
    asserts exactly that."""
    w = _Watch()
    try:
        yield w
    finally:
        w._freeze()


def record_recompile(detail: str = "", **attrs) -> None:
    """The runtime watchdog's alarm (DESIGN.md §15.2): called by the
    pipeline when a REPLAYED (config, shape) executable lowered a new
    XLA program anyway — i.e. the bounded jitcache hit but XLA still
    compiled, which a healthy steady-state service must never see.
    Always recorded (bounded log), independent of tracing."""
    global _recompile_count
    with _lock:
        _recompile_count += 1
        _recompile_log.append(dict(kind="event", name="recompile",
                                   t=time.perf_counter(), detail=detail,
                                   **attrs))


def recompile_events() -> List[Dict[str, Any]]:
    """Snapshot of the watchdog's (bounded) alarm log."""
    with _lock:
        return list(_recompile_log)
