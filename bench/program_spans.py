"""The program's own record of its fused calls, cut to the measured window.

``repro.obs.trace`` keeps one ``pipeline.fused`` span per fused call in a
bounded ring, whether or not tracing is on.  Its attributes are the call's
host phase seconds (``put_s``, ``dispatch_s``, ``device_s``,
``assemble_s``) and its loop counters summed over the problems it solved
(``problems``, ``tmfg_pops``, ``tmfg_inserts``, ``apsp_rounds``,
``hac_rescans``, ``hac_merges``).  A span starts on ``time.perf_counter``,
the clock of the benchmark's own spans, so a call belongs to the window
when it lies inside one of the run's ``bench.call`` spans; the warm-up's
calls never do.  A program that keeps no such ring has no such calls, and
every reading of it is None.
"""

from __future__ import annotations

from typing import List, Optional

CALL_SPAN = "bench.call"
FUSED_SPAN = "pipeline.fused"


def window_calls(ctx) -> List[object]:
    """The ``pipeline.fused`` spans of the calls timed in the window."""
    try:
        from repro.obs import trace
    except ImportError:
        return []
    kept = getattr(trace, "kept_spans", None)
    if kept is None:
        return []
    calls = [(s, e) for name, s, e in ctx.run.spans.events
             if name == CALL_SPAN]
    return [sp for sp in kept(FUSED_SPAN)
            if any(s <= sp.start and sp.start + sp.duration <= e
                   for s, e in calls)]


def ratio(ctx, num: str, den: str) -> Optional[float]:
    """Sum of attribute ``num`` over sum of ``den``, over the window's
    calls that carry both; None where there is none or ``den`` sums to 0."""
    calls = [sp for sp in window_calls(ctx)
             if num in sp.attrs and den in sp.attrs]
    total = sum(sp.attrs[den] for sp in calls)
    if not calls or total <= 0:
        return None
    return sum(sp.attrs[num] for sp in calls) / total
