"""Telemetry export: Prometheus text and the profiler (§15.4).

Two ways out of the process for what `obs.trace` / `obs.metrics`
collected (DESIGN.md §15.4):

* :func:`render` — the registry in Prometheus text exposition format
  (``# HELP``/``# TYPE`` + samples, histograms as cumulative
  ``_bucket``/``_sum``/``_count``).  Deterministically ordered, so the
  output is golden-testable (tests/test_obs.py) and diffable.
* :func:`profile` — a ``jax.profiler.trace`` context manager for deep
  dives (per-op device timelines in TensorBoard/Perfetto), for when
  span granularity is not enough.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from . import metrics as _metrics
from . import trace as _trace


def render(registry: Optional[_metrics.Registry] = None) -> str:
    """The registry in Prometheus text exposition format."""
    reg = registry if registry is not None else _metrics.REGISTRY
    lines = []
    seen = set()
    by_family = {}
    for m in reg._instruments():
        by_family.setdefault(m.name, m)
    for name in sorted(by_family):
        m = by_family[name]
        help_text = reg.help_text(name)
        if help_text:
            lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {m.kind}")
        seen.add(name)
    # samples, grouped: instrument samples in family order, then
    # collector samples as untyped gauges
    sample_lines = []
    collector_lines = []
    for sname, labels, value in reg.collect():
        family = sname
        for suffix in ("_bucket", "_sum", "_count"):
            if sname.endswith(suffix) and sname[:-len(suffix)] in seen:
                family = sname[:-len(suffix)]
        line = f"{sname}{_metrics._labels_str(labels)} {_num(value)}"
        (sample_lines if family in seen else collector_lines).append(line)
    lines.extend(sample_lines)
    for line in sorted(collector_lines):
        lines.append(line)
    # an empty registry renders as the empty string, not a stray
    # newline — scrapes of a fresh process must be byte-clean (pinned
    # by tests/test_obs.py)
    return "\n".join(lines) + "\n" if lines else ""


def _num(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


@contextmanager
def profile(logdir: str, *, create_perfetto_trace: bool = False):
    """Deep-dive profiler context: wraps ``jax.profiler.trace`` so a
    caller can capture per-op device timelines around any pipeline
    region (DESIGN.md §15.4).  Span tracing is enabled for the region
    as well, so the program's spans land in the deep trace as
    annotations on its own clock; Python call tracing is off, so the
    trace holds the device timeline and those spans, not every Python
    frame."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with _trace.tracing():
        with jax.profiler.trace(
                logdir, create_perfetto_trace=create_perfetto_trace,
                profiler_options=opts):
            yield
