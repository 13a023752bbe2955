"""The benchmark's own spans, and the reduction of a profiler trace.

Spans are timed on the host clock around calls into a layer of the
program and, while a trace is being taken, also written into it as
``jax.profiler.TraceAnnotation``.  The trace covers only the first
seconds of a window (the program's device loops put about a million op
events into each second of device time, and the profiler takes tens of
microseconds per event to stop), so a span that outlives it never
reaches the trace; idle gaps are named from the host-clock spans instead,
mapped onto the trace's clock by the marker ``bench.trace_start``.

The reduction reads the ``.xplane.pb`` file that ``jax.profiler`` writes
with ``jax.profiler.ProfileData``: device planes are ``/device:TPU:<i>``,
their op events are on the line ``XLA Ops`` and are named by the HLO
instruction's text (``%while.135 = (...) while(...)``; a Pallas kernel is
a custom call named after its wrapper, ``%pearson_pallas.1 = ...``), and
the benchmark's spans are the host events whose names start with
``bench.``.  Ops nest: a while loop's event spans the events of its body.
"""

from __future__ import annotations

import contextlib
import glob
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
MARK = "bench.trace_start"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Spans:
    """Host-clock spans of the benchmark, by name."""

    def __init__(self):
        self.tracing = False
        self.durations: Dict[str, List[float]] = defaultdict(list)
        self.events: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.durations[name].append(t1 - t0)
            self.events.append((name, t0, t1))
            if ann is not None:
                ann.__exit__(None, None, None)


def union_length(intervals: List[Tuple[int, int]]) -> int:
    """Total length covered by half-open (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Trace:
    """What the metrics read from one traced window (times in ns)."""

    window: Tuple[int, int]
    # per device: (name, start, end) of every op inside the window
    ops: List[List[Tuple[str, int, int]]]
    # benchmark spans: (name, start, end)
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    # device 0's Pallas calls: (name, start, end, leading batch of the
    # output, 1 for an unbatched call)
    kernels: List[Tuple[str, int, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        per = [union_length([(s, e) for _, s, e in dev]) for dev in self.ops]
        return sum(per) / len(per) * 1e-9

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per op name, summed over events and averaged
        over the devices."""
        tot: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            for name, s, e in dev:
                tot[name] += (e - s) * 1e-9
        return {k: v / max(len(self.ops), 1) for k, v in tot.items()}

    def kernel(self, kernel: str) -> Tuple[int, float]:
        """(events, device seconds per device) of the ops whose name
        contains ``kernel``."""
        count, secs = 0, 0.0
        for name, s, e in (ev for dev in self.ops for ev in dev):
            if kernel in name:
                count += 1
                secs += (e - s) * 1e-9
        d = max(len(self.ops), 1)
        return count // d, secs / d

    def kernel_batches(self, kernel: str) -> List[Tuple[int, float]]:
        """(batch, device seconds) of each whole call of one Pallas kernel
        inside the window, on device 0."""
        return [(b, (e - s) * 1e-9) for n, s, e, b in self.kernels
                if kernel in n]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The longest gaps of device 0 inside the window, each named by
        the innermost benchmark span that covers most of it."""
        if not self.ops:
            return []
        lo, hi = self.window
        busy = merged([(max(s, lo), min(e, hi)) for _, s, e in self.ops[0]
                       if e > lo and s < hi])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = [sp for sp in self.spans if sp[0] != WINDOW_SPAN]
        out = []
        for s, e in gaps[:top]:
            best, key = "no span", (0, 0)
            for name, ss, se in inner:
                # most of the gap covered, then the innermost span
                k = (min(e, se) - max(s, ss), ss - se)
                if k[0] > 0 and k > key:
                    best, key = name, k
            out.append([best, (e - s) * 1e-9])
        return out

    def top_ops(self, top: int = 10) -> List[List]:
        secs = self.op_seconds()
        return [[k, v] for k, v in sorted(secs.items(),
                                          key=lambda kv: -kv[1])[:top]]


def op_name(hlo_text: str) -> str:
    """``%while.135`` of ``%while.135 = (...) while(...)``."""
    return hlo_text.split(" = ", 1)[0]


_CALL = re.compile(r"^(%\w+_pallas[\w.]*) = \w+\[([\d,]*)\]")


def pallas_call(hlo_text: str):
    """(name, batch) of a Pallas custom call, batch being the leading
    dimension of a rank-3 output (a vmapped kernel) or 1; else None."""
    m = _CALL.match(hlo_text)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims[0] if len(dims) == 3 else 1


def from_profile(pd, n_devices: int, seconds: float,
                 host_spans=(), host_start: float = 0.0) -> Trace:
    """Reduce a ``ProfileData`` (or an object with its shape: ``planes``,
    each with ``name`` and ``lines``, each with ``name`` and ``events``
    having ``name``, ``start_ns`` and ``duration_ns``) to the ``seconds``
    after the marker ``bench.trace_start``.  ``host_spans`` are
    (name, start, end) on the host clock, whose ``host_start`` is the
    marker's time."""
    mark, dev_ops, calls = None, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = plane.name[len(DEVICE_PREFIX):]
            if not idx.isdigit() or int(idx) >= n_devices:
                continue
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, s = ev.name, int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    evs.append((op_name(name), s, e))
                    call = pallas_call(name) if int(idx) == 0 else None
                    if call:
                        calls.append((call[0], s, e, call[1]))
            dev_ops[int(idx)] = evs
        elif mark is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK:
                        mark = int(ev.start_ns)
    if mark is None:
        raise ValueError(f"the trace holds no {MARK} marker")
    lo, hi = mark, mark + round(seconds * 1e9)
    ops = [[(n, max(s, lo), min(e, hi)) for n, s, e in dev_ops[i]
            if e > lo and s < hi] for i in sorted(dev_ops)]
    spans = [(n, lo + round((s - host_start) * 1e9),
              lo + round((e - host_start) * 1e9)) for n, s, e in host_spans]
    kernels = [(n, s, e, b) for n, s, e, b in calls if s >= lo and e <= hi]
    return Trace(window=(lo, hi), ops=ops, spans=spans, kernels=kernels)


def read_dir(logdir: str, n_devices: int, seconds: float, host_spans=(),
             host_start: float = 0.0) -> Optional[Trace]:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not files:
        return None
    return from_profile(ProfileData.from_file(files[-1]), n_devices, seconds,
                        host_spans, host_start)


# ---------------------------------------------------------------------------
# readings shared by the per-layer metric files
# ---------------------------------------------------------------------------

# the custom-call names of the program's Pallas kernels in a v5e trace
PALLAS_KERNELS = ("pearson_pallas", "minplus_pallas", "masked_argmax_pallas",
                  "topk_rows_pallas")


def idle_pct(trace: Optional[Trace]) -> Optional[float]:
    """Share of the traced window in which no op ran on the device."""
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)


def kernel_seconds(trace: Optional[Trace], kernel: str):
    """(events, device seconds) of one kernel, or None where it never ran."""
    if trace is None:
        return None
    events, secs = trace.kernel(kernel)
    return (events, secs) if events and secs > 0 else None
