"""`repro.obs` — tracing, metrics and telemetry export (DESIGN.md §15).

The one observability layer for the TMFG-DBHT pipeline:

* :mod:`repro.obs.trace` — span-based device-true tracer (fenced on
  ``jax.block_until_ready`` when asked), compile-vs-run separation and
  the recompile watchdog (§15.1–§15.2).
* :mod:`repro.obs.metrics` — the process-global registry of counters /
  gauges / histograms every subsystem reports into (§15.3).
* :mod:`repro.obs.export` — Prometheus text ``render`` and the
  ``jax.profiler`` deep-dive context (§15.4).
"""

from . import export, metrics, trace
from .export import profile, render
from .metrics import (REGISTRY, Registry, counter, gauge, histogram,
                      register_collector, reset, snapshot)
from .trace import (Span, StageMarks, clear, compile_stats, disable,
                    enable, enabled, kept_spans, record_recompile,
                    recompile_events, span, spans, take_marks, tracing,
                    watch_recompiles)

__all__ = [
    "trace", "metrics", "export",
    "Span", "span", "spans", "kept_spans", "tracing", "enable", "disable",
    "enabled", "clear", "StageMarks", "take_marks", "watch_recompiles",
    "compile_stats", "record_recompile", "recompile_events",
    "REGISTRY", "Registry", "counter", "gauge", "histogram",
    "register_collector", "snapshot", "reset",
    "render", "profile",
]
