"""Traffic: one general generator of inputs and arrivals, and the three
entry points of the program that a traffic file can drive.

A traffic file names its ``driver`` and gives its parameters:

* ``cluster``        one problem per ``repro.core.cluster`` call, back to
                     back; each call gets the configuration's matrix with
                     its rows in a fresh order drawn from the seed.
* ``cluster_batch``  ``batch`` consecutive windows of the configuration's
                     return panel per ``repro.core.cluster_batch`` call,
                     back to back; each call starts ``batch`` days after
                     the last.
* ``serve``          an open loop into ``ClusterService``: Poisson
                     arrivals at ``rate_per_s``, one tenant, each request
                     one new tick and then ``submit``; the service's
                     queue is drained whenever it holds one.

The data (the matrix, the panel) comes from the configuration's own
``data.seed``, and ``--seed`` puts its rows (names) in another order and
orders the arrivals: every seed gets the same work in another order, so
runs with different seeds differ no more than two runs of one seed.

Every driver warms up exactly the shapes its traffic reaches, then runs
for ``seconds``; a call started inside the window is finished and counted.
It returns a :class:`Run`: the timings the metrics read, and the answers
of the problems that were solved, for the comparison with the reference.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

import data as data_mod
from tracing import MARK, WINDOW_SPAN, Spans

DRAIN_GRACE_S = 60.0     # how long after the window due requests may take
TENANT = "tenant0"       # the serving traffic's one tenant


@dataclass
class Answer:
    """What the program answered for one problem, and how to rebuild the
    problem's input for the reference."""

    edges: np.ndarray      # (3n-6, 2)
    labels: np.ndarray     # (n,)
    source: tuple          # the driver's key to the problem's input


@dataclass
class Run:
    setup_s: float = 0.0
    seconds: float = 0.0
    # (start, end, problems) of every timed call, on the host clock
    calls: List[tuple] = field(default_factory=list)
    # per request due in the window: due time, resolved time (None if
    # never), failed (shed, degraded or unresolved)
    requests: List[dict] = field(default_factory=list)
    drains: List[int] = field(default_factory=list)
    # seconds from the window's start to when the serving loop stopped
    # waiting for answers
    closed: float = 0.0
    compiles: int = 0
    attempted: int = 0
    failed: int = 0
    answers: List[Answer] = field(default_factory=list)
    shape: Dict[str, int] = field(default_factory=dict)
    spans: Spans = field(default_factory=Spans)
    # the input of a problem, from its ``Answer.source``
    inputs: Optional[Callable] = None
    # (host time of the trace's start marker, seconds traced)
    traced: Optional[tuple] = None


def pipeline_config(config: dict, backend: Optional[str]):
    from repro.core import PipelineConfig

    p = config["pipeline"]
    make = getattr(PipelineConfig, p["constructor"])
    args = dict(p.get("args", {}))
    if backend is not None:
        args["backend"] = backend
    return make(**args)


def answer(res, source) -> Answer:
    return Answer(edges=np.sort(np.asarray(res.tmfg.edges), axis=1),
                  labels=np.asarray(res.labels), source=source)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def series(config: dict) -> np.ndarray:
    """The configuration's (n, L) series."""
    d = config["data"]
    return data_mod.ucr_like(d["n"], d["L"], d["classes"], noise=d["noise"],
                             warp=d["warp"], seed=d["seed"])


def panel(config: dict, seed: int, days: int) -> np.ndarray:
    """The configuration's (n, days) return panel, names in the order of
    ``seed``."""
    d = config["data"]
    P = data_mod.sector_returns(
        d["n"], days, d["sector_weights"], market_vol=d["market_vol"],
        sector_vol=d["sector_vol"], idio_vol=d["idio_vol"],
        tail_df=d["tail_df"], seed=d["seed"])
    return P[permutation(d["n"], seed, -1)]


def permutation(n: int, seed: int, call: int) -> np.ndarray:
    """Row order of timed call ``call`` (>= 0); -1 is the warm-up's."""
    return np.random.default_rng([seed, call + 1]).permutation(n)


def due_times(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Poisson due times in [0, seconds) at ``rate_per_s``.

    Every seed gets the same set of gaps, in its own order: the gaps are
    the exponential distribution's quantiles at (j + 1/2) / N, so the load
    and the request count are the same from seed to seed."""
    rate = float(traffic["rate_per_s"])
    N = max(1, int(round(rate * seconds)))
    q = (np.arange(N) + 0.5) / N
    gaps = np.random.default_rng([seed, 7]).permutation(-np.log1p(-q) / rate)
    t = np.cumsum(gaps) - gaps[0]
    return t[t < seconds]


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

class Profiler:
    """A profiler trace of the first ``seconds`` of a window: started with
    the window and stopped ``seconds`` later (or when the window ends
    first).  A batch driver's thread waits inside one long call then, so
    a timer thread stops it; the serving loop, whose thread keeps the
    device busy, stops it itself between drains (``use_timer = False``):
    stopping it from another thread while that loop ran never returned."""

    def __init__(self, logdir: str, seconds: float):
        self.logdir, self.seconds = logdir, seconds
        self.lock = threading.Lock()
        self.start_at = self.stop_at = None
        self.use_timer, self.timer = True, None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        # no Python call tracing; host events only from annotations
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(MARK):
            self.start_at = time.perf_counter()
        if self.use_timer:
            self.timer = threading.Timer(self.seconds, self.stop)
            self.timer.start()

    def due(self) -> bool:
        return time.perf_counter() - self.start_at >= self.seconds

    def stop(self):
        import jax

        with self.lock:
            if self.stop_at is not None:
                return
            self.stop_at = time.perf_counter()
        jax.profiler.stop_trace()

    def close(self) -> tuple:
        if self.timer is not None:
            self.timer.cancel()
        self.stop()
        if self.timer is not None:
            self.timer.join()
        return self.start_at, self.stop_at - self.start_at


@contextlib.contextmanager
def window(run: Run, trace: Optional[Profiler]):
    """The measured window: counts the programs compiled inside it and,
    given a ``Profiler``, traces its first seconds."""
    from repro.obs import trace as obs_trace

    if trace is not None:
        trace.start()
        run.spans.tracing = True
    try:
        with obs_trace.watch_recompiles() as w:
            with run.spans.span(WINDOW_SPAN):
                yield
    finally:
        if trace is not None:
            run.spans.tracing = False
            run.traced = trace.close()
    run.compiles = w.count


# ---------------------------------------------------------------------------
# the batch drivers
# ---------------------------------------------------------------------------

class _Compiled(Exception):
    pass


def compile_without_running(call: Callable[[], object]):
    """Compile, and do not run, the first program that ``call`` asks the
    program's executable cache (``repro.core.jitcache``) for: its key, its
    build and its input are the program's own, and the compiled program
    stays in that cache for the timed calls.  A ``call`` that asks the
    cache for nothing simply runs once."""
    from repro.core import jitcache

    cached = jitcache.cached

    def compile_only(key, build):
        fn = cached(key, build)

        def lower_and_stop(*args, **kwargs):
            fn.lower(*args, **kwargs).compile()
            raise _Compiled

        return lower_and_stop

    jitcache.cached = compile_only
    try:
        call()
    except _Compiled:
        pass
    finally:
        jitcache.cached = cached


def _window_loop(run: Run, seconds: float, one: Callable[[int], int],
                 trace: Optional[Profiler]):
    """Call ``one(call_index)`` back to back from the window's start until
    ``seconds`` have passed; ``one`` returns the problems it solved."""
    with window(run, trace):
        t0 = time.perf_counter()
        c = 0
        while time.perf_counter() - t0 < seconds:
            s = time.perf_counter()
            with run.spans.span("bench.call"):
                solved = one(c)
            run.calls.append((s - t0, time.perf_counter() - t0, solved))
            c += 1
    run.attempted = sum(p for _, _, p in run.calls)


def drive_cluster(config, traffic, seed, seconds, backend, start,
                  trace=None) -> Run:
    from repro.core import cluster

    run = Run(seconds=seconds)
    X = series(config)
    k = int(config["k"])
    pcfg = pipeline_config(config, backend)
    n, L = X.shape
    run.shape = dict(n=n, L=L, B=1)
    # warm-up: the call's program compiled (or loaded from the compile
    # cache), not run: a whole call at this size takes tens of seconds
    compile_without_running(
        lambda: cluster(X[permutation(n, seed, -1)], k=k, config=pcfg))
    run.setup_s = time.perf_counter() - start

    def one(c):
        src = ("perm", c)
        res = cluster(X[permutation(n, seed, c)], k=k, config=pcfg)
        run.answers.append(answer(res, src))
        return 1

    _window_loop(run, seconds, one, trace)
    run.inputs = lambda src: X[permutation(n, seed, src[1])]
    return run


def _windows(P: np.ndarray, start: int, batch: int, W: int) -> np.ndarray:
    from numpy.lib.stride_tricks import sliding_window_view

    v = sliding_window_view(P[:, start:start + batch + W - 1], W, axis=1)
    return np.ascontiguousarray(np.moveaxis(v, 1, 0))      # (batch, n, W)


def drive_cluster_batch(config, traffic, seed, seconds, backend, start,
                        trace=None) -> Run:
    from repro.core import cluster_batch

    run = Run(seconds=seconds)
    B, W = int(traffic["batch"]), int(config["data"]["window"])
    k = int(config["k"])
    # enough days for ``calls_per_panel`` distinct calls; later calls wrap
    days = W + B * int(traffic["calls_per_panel"]) + B
    P = panel(config, seed, days)
    n = P.shape[0]
    starts = days - W - B + 1
    pcfg = pipeline_config(config, backend)
    run.shape = dict(n=n, L=W, B=B)

    def first_day(c):
        return (B + c * B) % starts

    cluster_batch(_windows(P, 0, B, W), k=k, config=pcfg)     # warm-up
    run.setup_s = time.perf_counter() - start

    def one(c):
        Xb = _windows(P, first_day(c), B, W)
        res = cluster_batch(Xb, k=k, config=pcfg)
        for b in range(B):
            run.answers.append(answer(res[b], ("window", first_day(c) + b)))
        return B

    _window_loop(run, seconds, one, trace)
    run.inputs = lambda src: P[:, src[1]:src[1] + W]
    return run


# ---------------------------------------------------------------------------
# the open loop into the service
# ---------------------------------------------------------------------------

def drive_serve(config, traffic, seed, seconds, backend, start,
                trace=None) -> Run:
    from repro.stream.admission import AdmissionConfig
    from repro.stream.service import ClusterService

    run = Run(seconds=seconds)
    W, k = int(config["data"]["window"]), int(config["k"])
    svc_cfg = config["service"]
    due = due_times(traffic, seed, seconds)
    sizes = range(1, int(svc_cfg["max_batch"]) + 1)
    days = W + sum(sizes) + len(due) + 1
    P = panel(config, seed, days)
    n = P.shape[0]
    run.shape = dict(n=n, L=W, B=1)
    svc = ClusterService(n=n, window=W, k=k,
                         config=pipeline_config(config, backend),
                         max_batch=int(svc_cfg["max_batch"]),
                         admission=AdmissionConfig())
    for t in range(W):
        svc.tick(P[:, t])
    day = W
    # warm-up: every micro-batch size the queue can reach, once (a batch
    # smaller than its bucket slices its outputs: programs of their own)
    for b in sizes:
        for _ in range(b):
            svc.tick(P[:, day])
            day += 1
            svc.submit()
        svc.drain()
    day -= 1                                # the last tick pushed
    run.setup_s = time.perf_counter() - start

    tickets: Dict[int, object] = {}
    recs: List[dict] = []
    spans = run.spans
    if trace is not None:
        trace.use_timer = False
    with window(run, trace):
        t0 = time.perf_counter()
        i, deadline = 0, seconds + DRAIN_GRACE_S
        while True:
            now = time.perf_counter() - t0
            while i < len(due) and due[i] <= now:
                day += 1
                svc.tick(P[:, day])
                with spans.span("bench.submit"):
                    t = svc.submit(tenant=TENANT)
                recs.append(dict(due=float(due[i]), day=day, done=None,
                                 failed=False))
                tickets[i] = t
                i += 1
            if svc.admission.queue:
                with spans.span("bench.drain"):
                    resolved = svc.drain()
                run.drains.append(len(resolved))
            if trace is not None and trace.due():
                trace.stop()
            now = time.perf_counter() - t0
            for j, t in list(tickets.items()):
                if t.done:
                    recs[j]["done"] = now
                    recs[j]["failed"] = bool(t.degraded or t.result is None)
                    if not recs[j]["failed"]:
                        run.answers.append(answer(t.result,
                                                  ("day", recs[j]["day"])))
                    del tickets[j]
            if i >= len(due) and not tickets:
                break
            if now > deadline:
                break
            if i < len(due) and not svc.admission.queue:
                with spans.span("bench.wait"):
                    time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
        run.closed = now
    for r in recs:
        r["failed"] = r["failed"] or r["done"] is None
    run.requests = recs
    run.attempted = len(recs)
    run.failed = sum(r["failed"] for r in recs)
    # the window of request d ends on day d (the tick it brought)
    run.inputs = lambda src: P[:, src[1] - W + 1:src[1] + 1]
    return run


DRIVERS = {"cluster": drive_cluster, "cluster_batch": drive_cluster_batch,
           "serve": drive_serve}
