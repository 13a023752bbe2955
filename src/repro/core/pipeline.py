"""End-to-end TMFG-DBHT clustering pipeline (the paper's full system).

``cluster()`` reproduces the paper's OPT-TDBHT path by default:
Pearson similarity (fused kernel) → LAZY(heap-equivalent) TMFG with the
up-front top-K candidate table → hub-approximate APSP → DBHT dendrogram.

Every stage is switchable to reproduce the paper's other variants; the
stage knobs live in one frozen, hashable :class:`PipelineConfig`
(core/config.py, DESIGN.md §12.1) — the loose
``method/prefix/topk/apsp_method/...`` kwargs are kept as a deprecated
shim that resolves through the same funnel:

  PAR-TDBHT-P   -> PipelineConfig.par(P)        (method="orig")
  CORR-TDBHT    -> PipelineConfig.corr()
  HEAP-TDBHT    -> PipelineConfig.heap()
  OPT-TDBHT     -> PipelineConfig.opt()         (default)

Execution (DESIGN.md §12.2): by default the whole pipeline — similarity,
TMFG construction, edge lengths, APSP, the device DBHT tree stage and
the nested HAC — runs as ONE jitted device program
(:func:`run_pipeline_device`) with a single device→host transfer at the
end, so a request pays one dispatch instead of three dispatch+sync
round-trips.  ``fused=False`` restores the staged path (one jit per
stage with a host sync between them) as the timing/debug mode
(DESIGN.md §12.4): it reports per-stage ``timings`` where the fused
path reports ``total`` only, and it is the only path for
``dbht_impl="host"`` and ``reuse_tmfg=``.

``cluster_batch()`` is the throughput entry point (DESIGN.md §7.4): a
batch of B datasets/similarity matrices is clustered data-parallel with
the batch axis sharded over the mesh from dist/sharding.py (the fused
program is vmapped over the batch; one device→host transfer returns the
batch's outputs).  On one device it degrades to the vmapped
single-device program, identical to a loop of ``cluster()`` calls
(pinned by tests/test_pipeline.py and tests/test_fused.py).
"""

from __future__ import annotations

import functools
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as dist_sh
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
import repro.core.apsp as apsp_mod
import repro.core.dbht as dbht_mod
import repro.core.jitcache as jitcache
from .config import PipelineConfig, VARIANTS  # noqa: F401  (re-export)
from .tmfg import TMFGResult, adjacency_from_weights, build_tmfg


def _observe_stage(stage: str, seconds: float) -> None:
    """Per-stage latency into the process-global registry (DESIGN.md
    §15.3); the staged path's spans feed it, so `ClusterService.stats()`
    exports the same numbers `ClusterResult.timings` reports, and so do
    the fused path's stage marks when they are on (§15.5)."""
    obs_metrics.histogram("pipeline_stage_seconds",
                          "per-stage latency (fenced staged spans, or "
                          "the fused path's stage marks)",
                          stage=stage).observe(seconds)


def _observe_total(path: str, seconds: float) -> None:
    obs_metrics.histogram("pipeline_total_seconds",
                          "end-to-end cluster()/cluster_batch() latency",
                          path=path).observe(seconds)


# the fused path's host phases (DESIGN.md §15.5), in call order, and the
# stage marks of the TMFG bodies when built under tracing
PHASES = ("put", "dispatch", "device", "assemble")
STAGES = ("similarity", "tmfg", "apsp", "dbht", "hac")


@contextmanager
def _phase(call: obs_trace.Span, phase: str):
    """One host phase of a fused call: a span under ``pipeline.fused``,
    its seconds on the call's record (``<phase>_s``) and in the registry
    histogram ``pipeline_phase_seconds{phase=...}``."""
    with obs_trace.span(f"pipeline.{phase}") as sp:
        yield sp
    call.attrs[f"{phase}_s"] = sp.duration
    obs_metrics.histogram("pipeline_phase_seconds",
                          "fused-path host phase latency",
                          phase=phase).observe(sp.duration)


def loop_counters(n: int, *, apsp_rounds, hac_rescans, tm=None,
                  sparse=None) -> Dict[str, jax.Array]:
    """The fused program's loop counters for one problem (DESIGN.md
    §15.5), keyed by their registry names less ``_total``: the lazy
    TMFG's pops and inserts (when the filter is a TMFG), the APSP
    rounds, the HAC rescans and merges, and the approx scan's lookups
    (``sparse``, a ``SparseCounters``)."""
    c = {}
    if tm is not None:
        c["tmfg_pops"] = tm.pops
        c["tmfg_inserts"] = jnp.int32(n - 4)
    c["apsp_rounds"] = jnp.asarray(apsp_rounds, jnp.int32)
    c["hac_rescans"] = hac_rescans
    c["hac_merges"] = jnp.int32(n - 1)
    if sparse is not None:
        c["approx_lookups"] = sparse.lookups
        c["approx_fallbacks"] = sparse.fallbacks
        c["approx_pair_lookups"] = sparse.pair_lookups
        c["approx_pair_misses"] = sparse.pair_misses
    return c


def _record_counters(counters, problems: int) -> Dict[str, float]:
    """Sum a call's counters (host copies, pads already cut away) into
    the registry; returns the sums, with ``problems``."""
    sums = {"problems": float(problems)}
    obs_metrics.counter("pipeline_problems_total").inc(problems)
    for name, v in (counters or {}).items():
        sums[name] = float(np.sum(np.asarray(v)))
        obs_metrics.counter(f"{name}_total").inc(sums[name])
    return sums


def _approx_timings(sums: Dict[str, float]) -> Dict[str, float]:
    """The approx scan's diagnostics as ``timings`` keys (§13.3)."""
    if "approx_lookups" not in sums:
        return {}
    fb = sums["approx_fallbacks"]
    return {"sim_fallbacks": fb,
            "sim_fallback_rate": fb / max(sums["approx_lookups"], 1.0),
            "sim_pair_misses": sums["approx_pair_misses"]}


def _stage_seconds(marks) -> Dict[str, float]:
    """Seconds per stage from one call's marks: each stage runs from the
    previous mark (``start`` for the first) to its own, the latest where
    several devices fired one."""
    ends: Dict[str, float] = {}
    for stage, t in marks:
        ends[stage] = max(t, ends.get(stage, t))
    if not all(s in ends for s in ("start",) + STAGES):
        return {}
    prev = min(t for stage, t in marks if stage == "start")
    out = {}
    for stage in STAGES:
        out[stage] = ends[stage] - prev
        prev = ends[stage]
    return out


@dataclass
class ClusterResult:
    labels: np.ndarray
    linkage: np.ndarray
    tmfg: object
    dbht: object
    edge_sum: float
    timings: Dict[str, float] = field(default_factory=dict)
    # True when the TMFG was carried over from an earlier window
    # (cluster(reuse_tmfg=...)) rather than built on this similarity —
    # the stream warm-start cache keys its drift anchoring on this
    reused_tmfg: bool = False
    # True when the fused program overflowed its slot-grid caps (§17.3)
    # and this result comes from the staged rerun: the fused answer was
    # not used, and a caller that checks the fused path can see it
    overflow: bool = False

    def labels_at(self, k: int) -> np.ndarray:
        return self.dbht.labels(k)


def resolve_variant(variant: Optional[str], *, method: str = "lazy",
                    prefix: int = 10, topk: int = 64,
                    apsp_method: str = "hub"):
    """Deprecated kwarg-era shim: (method, prefix, topk, apsp_method)
    for a named variant — or the caller-supplied values untouched when
    ``variant`` is None.  New code should build a
    :class:`PipelineConfig` instead; this delegates to the same
    :meth:`PipelineConfig.resolve` funnel so both surfaces agree."""
    cfg = PipelineConfig.resolve(variant, method=method, prefix=prefix,
                                 topk=topk, apsp_method=apsp_method)
    return cfg.method, cfg.prefix, cfg.topk, cfg.apsp_method


def similarity_from_timeseries(X, *, backend: str = "auto") -> jnp.ndarray:
    """Pearson correlation similarity matrix from row time series."""
    return ops.pearson(jnp.asarray(X), backend=backend)


# ---------------------------------------------------------------------------
# the fused one-jit device program (DESIGN.md §12.2)
# ---------------------------------------------------------------------------

class DeviceOutputs(NamedTuple):
    """Everything the fused pipeline leaves on device: the TMFG arrays
    plus the DBHT stage outputs, one pytree = one host transfer.
    Batched runs carry a leading batch axis on every leaf.

    ``hubs`` and ``overflow`` exist only on the fused sparse/approx
    program (DESIGN.md §17) and default to ``None`` — an empty pytree
    subtree.  ``counters`` is every fused program's dict of per-problem
    loop counters (:func:`loop_counters`, DESIGN.md §15.5), scalars that
    ride the one transfer."""

    tmfg: TMFGResult          # fixed-shape TMFG arrays
    direction: jax.Array      # (B_,) bubble-tree edge directions ([0] unused)
    conv_mask: jax.Array      # (B_,) converging-bubble indicator
    cluster_of: jax.Array     # (n,) coarse cluster id per vertex
    bubble_of: jax.Array      # (n,) fine bubble assignment per vertex
    apsp: jax.Array           # (n, n) distances — (h, n) hub factor on
    linkage: jax.Array        # the sparse tail; (n-1, 4) dendrogram
    hubs: Optional[jax.Array] = None      # (h,) hub ids (sparse tail)
    overflow: Optional[jax.Array] = None  # bool: slot-grid caps exceeded
    counters: Optional[dict] = None       # loop counters (loop_counters)


def _fused_one(cfg: PipelineConfig, have_S: bool, marks: bool = False):
    """The traceable single-matrix pipeline body for ``cfg``.

    Composes exactly the stages the staged path runs — ops.pearson,
    build_tmfg, apsp.edge_lengths + apsp, the device DBHT core and the
    nested HAC — so fused and staged outputs are identical (the §12.2
    parity contract, pinned by tests/test_fused.py).  Each stage is a
    named scope; with ``marks`` each stage boundary is also a host-clock
    mark (DESIGN.md §15.5)."""

    def one(arr):
        n = arr.shape[0]
        mark = obs_trace.StageMarks(marks)
        arr = mark("start", arr)
        with jax.named_scope("similarity"):
            S = arr if have_S else ops.pearson(arr, backend=cfg.backend)
            if cfg.clean == "rmt":
                # §18.2: eigenvalue clipping changes ONLY the similarity
                # input; T is the (static) window length of the series
                from repro.filters import rmt as rmt_mod  # lazy: no cycle
                S = rmt_mod.clean(S, arr.shape[-1])
        S = mark("similarity", S)
        with jax.named_scope("tmfg"):
            tm = build_tmfg(S, method=cfg.method, prefix=cfg.prefix,
                            topk=cfg.topk)
        S, tm = mark("tmfg", (S, tm))
        with jax.named_scope("apsp"):
            W = apsp_mod.edge_lengths(n, tm.edges, S)
            D, rounds = apsp_mod.apsp_rounds(
                W, method=cfg.apsp_method, n_hubs=cfg.apsp_hubs,
                rounds=cfg.apsp_rounds, backend=cfg.backend)
        S, tm, D = mark("apsp", (S, tm, D))
        core = dbht_mod._dbht_device_core(
            S, tm.edges, tm.bubble_parent, tm.bubble_tri, tm.bubble_verts,
            tm.home_bubble, D, backend=cfg.backend, mark=mark)
        Z = mark("hac", core["Z"])
        return DeviceOutputs(
            tmfg=tm, direction=core["direction"], conv_mask=core["conv_mask"],
            cluster_of=core["cluster_of"], bubble_of=core["bubble_of"],
            apsp=core["D"], linkage=Z,
            counters=loop_counters(n, tm=tm, apsp_rounds=rounds,
                                   hac_rescans=core["hac_rescans"]))

    return one


def _needs_approx_body(cfg: PipelineConfig) -> bool:
    """Configs whose fused form is the sparse/approx program
    (core/fused_approx.py, DESIGN.md §17) instead of the dense body.
    Non-TMFG filters never route here: their sparse APSP runs inside
    the §18.4 generic tail on the filter's own edge list."""
    return cfg.filter == "tmfg" and (cfg.similarity == "topk"
                                     or cfg.apsp_method == "sparse")


def _fused_filter_one(cfg: PipelineConfig, have_S: bool):
    """The traceable single-matrix body for a non-TMFG filter
    (DESIGN.md §18): similarity (+ optional §18.2 RMT cleaning) → the
    device filter builder → the §18.4 edge-list tail.  The staged path
    runs the same jitted stage functions, so fused and staged agree
    bitwise exactly as on the TMFG path (§12.2)."""
    from repro import filters as filt  # lazy: no import cycle

    def one(arr):
        with jax.named_scope("similarity"):
            S = arr if have_S else ops.pearson(arr, backend=cfg.backend)
            if cfg.clean == "rmt":
                S = filt.rmt.clean(S, arr.shape[-1])
        with jax.named_scope("tmfg"):
            fg = filt.build_filter(S, cfg)
        core = filt.filter_tail(S, fg, apsp_method=cfg.apsp_method,
                                apsp_hubs=cfg.apsp_hubs,
                                apsp_rounds=cfg.apsp_rounds,
                                backend=cfg.backend)
        return DeviceOutputs(
            tmfg=fg, direction=core["direction"],
            conv_mask=core["conv_mask"], cluster_of=core["cluster_of"],
            bubble_of=core["bubble_of"], apsp=core["D"], linkage=core["Z"],
            counters=loop_counters(arr.shape[0],
                                   apsp_rounds=core["apsp_rounds"],
                                   hac_rescans=core["hac_rescans"]))

    return one


def _fused_approx_one(cfg: PipelineConfig, have_S: bool, n: int, caps,
                      marks: bool):
    """The §17 body wrapped into the :class:`DeviceOutputs` pytree."""
    from repro.core import fused_approx as fa  # lazy: keeps import light

    raw = fa.fused_one(cfg, have_S, n, caps=caps, marks=marks)

    def one(arr):
        core = raw(arr)
        return DeviceOutputs(
            tmfg=core["tmfg"], direction=core["direction"],
            conv_mask=core["conv_mask"], cluster_of=core["cluster_of"],
            bubble_of=core["bubble_of"], apsp=core["D"], linkage=core["Z"],
            hubs=core["hubs"], overflow=core["overflow"],
            counters=core["counters"])

    return one


def run_pipeline_device(X_or_S, config: PipelineConfig, *,
                        is_similarity: Optional[bool] = None,
                        batched: Optional[bool] = None,
                        caps=None, mesh=None) -> DeviceOutputs:
    """The whole pipeline as ONE jitted device program (DESIGN.md §12.2).

    ``X_or_S`` is a time-series matrix ``(n, L)``, a similarity matrix
    ``(n, n)``, or the batched ``(B, ...)`` form of either;
    ``is_similarity`` disambiguates (default: square trailing dims mean
    similarity) and ``batched`` defaults to ``ndim == 3``.  The
    executable is specialized per ``(config, input kind, shape)`` and
    held in the bounded shared cache (core/jitcache.py, DESIGN.md
    §12.3), so a serving loop replaying one config+shape compiles
    exactly once (the recompile guard in tests/test_fused.py).

    ``similarity="topk"`` and ``apsp_method="sparse"`` configs lower to
    the fused sparse/approx program (core/fused_approx.py, DESIGN.md
    §17) — same contract, no (n, n) array in the jaxpr; ``caps``
    overrides its ``(c_cap, m_cap)`` nested-HAC slot grid.  ``mesh``
    routes one matrix through the multi-device funnel
    (:func:`repro.core.distributed.run_pipeline_sharded`); a batch runs
    data-parallel over the mesh's data axes, one ``shard_map`` slice
    per device (XLA cannot partition a Pallas kernel, so the batch is
    split by hand, or every device runs the whole batch when B does not
    divide the mesh).

    While tracing is on (``obs.trace.enable``/``tracing``) when the
    program is built, a TMFG body (dense or approx) also takes a
    host-clock mark at each stage boundary (DESIGN.md §15.5).

    Returns :class:`DeviceOutputs` — device arrays, NO host transfer:
    callers choose what crosses the boundary (``cluster`` transfers
    everything once; the stream scheduler's pad entries never do).
    """
    if config.dbht_impl != "device":
        raise ValueError(
            "run_pipeline_device IS the device program; "
            "config.dbht_impl='host' has no fused form — use "
            "cluster(..., fused=False) for the numpy oracle")
    if config.filter == "pmfg":
        raise ValueError(
            "filter='pmfg' has no fused form: greedy planarity-checked "
            "insertion is the host-orchestrated reference (DESIGN.md "
            "§18.3) — use cluster(..., fused=False)")
    arr = jnp.asarray(X_or_S, jnp.float32)
    if batched is None:
        batched = arr.ndim == 3
    if mesh is not None and not batched:
        from repro.core import distributed as dist_mod  # lazy: no cycle
        return dist_mod.run_pipeline_sharded(
            X_or_S, config, mesh, is_similarity=is_similarity, caps=caps)
    if config.clean == "rmt" and (is_similarity or (
            is_similarity is None and arr.shape[-1] == arr.shape[-2])):
        raise ValueError(
            "clean='rmt' needs the raw series X: the Marchenko–Pastur "
            "bulk edge comes from the (n, T) window shape (DESIGN.md "
            "§18.2) — a precomputed similarity has no T")
    if is_similarity is None:
        is_similarity = arr.shape[-1] == arr.shape[-2]
        if is_similarity and not bool(
                jnp.all(jnp.abs(arr - jnp.swapaxes(arr, -1, -2)) <= 1e-5)):
            # guard the inference: a square TIME-SERIES matrix silently
            # misread as similarity would cluster garbage.  The check
            # costs one device reduction + sync, paid only on this
            # inference path — cluster()/cluster_batch() (and any
            # latency-sensitive caller) pass is_similarity explicitly
            raise ValueError(
                f"square input {arr.shape} is not symmetric, so it is "
                f"ambiguous: pass is_similarity= explicitly")

    # stage marks are host callbacks, and JAX writes no persistent-cache
    # entry for a program with one: only a program built while tracing
    # is on carries them, under a key of its own (DESIGN.md §15.5)
    marks = config.filter == "tmfg" and obs_trace.enabled()

    def build():
        if config.filter != "tmfg":
            one = _fused_filter_one(config, is_similarity)
        elif _needs_approx_body(config):
            one = _fused_approx_one(config, is_similarity,
                                    int(arr.shape[-2]), caps, marks)
        else:
            one = _fused_one(config, is_similarity, marks)
        if not batched:
            return jax.jit(one)
        if mesh is None:
            return jax.jit(jax.vmap(one))
        spec = dist_sh.batch_specs(mesh, arr)
        return jax.jit(jax.shard_map(jax.vmap(one), mesh=mesh,
                                     in_specs=spec, out_specs=P(*spec[:1]),
                                     check_vma=False))

    key = ("fused", config, is_similarity, batched, arr.shape, caps, mesh,
           marks)
    # the runtime recompile watchdog (DESIGN.md §15.2): a key already in
    # the executable cache is a REPLAY — if XLA compiles a new program
    # under it anyway, that is the BENCH_5 failure mode happening in
    # production, and it is alarmed, not silently paid
    replay = jitcache.contains(key)
    fn = jitcache.cached(key, build)
    before = obs_trace.compile_stats()["programs"]
    out = fn(arr)
    if replay and obs_trace.compile_stats()["programs"] > before:
        obs_trace.record_recompile(
            detail="replayed fused executable lowered a new program",
            shape=str(arr.shape), batched=batched)
    return out


def _fused_run(call: obs_trace.Span, arr, cfg: PipelineConfig,
               have_S: bool, batched: bool, mesh,
               B_out: Optional[int]) -> Optional[DeviceOutputs]:
    """The dispatch and device phases of a fused call (DESIGN.md §15.5):
    host copies of the program's outputs (the first ``B_out`` entries of
    a batch), or None when the program overflowed its slot-grid caps
    (§17.3).  Records on ``call`` whether the dispatch compiled, and the
    stage seconds when the program carries stage marks."""
    obs_trace.take_marks()                  # drop marks of earlier calls
    with _phase(call, "dispatch") as sp_dispatch:
        out = run_pipeline_device(arr, cfg, is_similarity=have_S,
                                  batched=batched, mesh=mesh)
    call.attrs["compiled"] = sp_dispatch.compiles > 0
    with _phase(call, "device"):
        if B_out is not None:
            # sliced to B_out first so pad entries of a bucketed
            # micro-batch never cross the boundary
            out = jax.tree.map(lambda a: a[:B_out], out)
        host = jax.device_get(out)
        overflow = host.overflow is not None and bool(
            np.any(np.asarray(host.overflow)))
    stages = _stage_seconds(obs_trace.take_marks())
    if stages:
        call.attrs["stages"] = stages
        for stage, secs in stages.items():
            _observe_stage(stage, secs)
    return None if overflow else host


def _result_from_fused(host: DeviceOutputs, b: Optional[int] = None,
                       k: Optional[int] = None,
                       timings: Optional[Dict[str, float]] = None
                       ) -> ClusterResult:
    """ClusterResult from (host copies of) one fused-pipeline output.

    The DBHT half delegates to ``dbht._result_from_device`` so the
    unpacking convention (converging ids from the fixed-point mask, the
    ``direction[1:]`` slice) lives in exactly one place."""
    pick = (lambda a: a) if b is None else (lambda a, b=b: a[b])
    tm = jax.tree.map(pick, host.tmfg)
    res = dbht_mod._result_from_device(
        dict(direction=host.direction, conv_mask=host.conv_mask,
             cluster_of=host.cluster_of, bubble_of=host.bubble_of,
             D=host.apsp, Z=host.linkage), b)
    if host.hubs is not None:
        res.hubs = np.asarray(pick(host.hubs))
    kk = k if k is not None else len(res.converging)
    return ClusterResult(
        labels=res.labels(kk), linkage=res.linkage, tmfg=tm, dbht=res,
        edge_sum=float(tm.edge_sum), timings=timings or {})


def clear_compiled() -> None:
    """Drop every cached pipeline executable (core/jitcache.clear)."""
    jitcache.clear()


# ---------------------------------------------------------------------------
# single-matrix entry point
# ---------------------------------------------------------------------------

def cluster(X=None, *, S=None, moments=None, k: Optional[int] = None,
            config: Optional[PipelineConfig] = None,
            method: Optional[str] = None, prefix: Optional[int] = None,
            topk: Optional[int] = None, apsp_method: Optional[str] = None,
            backend: Optional[str] = None,
            variant: Optional[str] = None, reuse_tmfg=None,
            dbht_impl: Optional[str] = None, fused: Optional[bool] = None,
            mesh=None, collect_timings: bool = False) -> ClusterResult:
    """Cluster time series X (n, L) — or a precomputed similarity S — with
    TMFG-DBHT.  ``k`` cuts the dendrogram into k flat clusters (defaults to
    the number of converging bubbles).

    ``config`` is the preferred way to select the stage configuration
    (one :class:`PipelineConfig`); the loose
    ``method/prefix/topk/apsp_method/backend/variant/dbht_impl`` kwargs
    are a deprecated shim resolved through the same funnel (defaults —
    lazy/10/64/hub/auto/device — come from the dataclass; combining
    them with ``config=`` is rejected, use ``config.replace(...)``).

    ``mesh`` routes the fused program through the multi-device funnel
    (``repro.core.distributed.run_pipeline_sharded``); the staged path
    (``fused=False``) is single-device and ignores it.

    ``fused`` selects the execution plan: the default (None) runs the
    whole pipeline as ONE jitted device program + one transfer
    (DESIGN.md §12.2) whenever possible (``dbht_impl="device"`` and no
    ``reuse_tmfg``), and reports a ``total``-only timing;
    ``fused=False`` forces the staged path — one jit per stage with a
    host sync between them — which preserves the per-stage
    ``similarity/tmfg/dbht+apsp`` timings (the timing/debug mode,
    DESIGN.md §12.4).

    Streaming hooks (DESIGN.md §10): ``moments`` takes a
    ``repro.stream.window.WindowState`` and derives S from the rolling
    co-moments in O(n²) instead of the O(n²L) Pearson pass;
    ``reuse_tmfg`` skips TMFG construction and reruns only the DBHT
    stage on a previous window's graph (the warm-start path — caller
    asserts the similarity delta is small enough for the topology to
    still apply)."""
    cfg = PipelineConfig.resolve(
        variant, config, method=method, prefix=prefix, topk=topk,
        apsp_method=apsp_method, backend=backend, dbht_impl=dbht_impl)

    if cfg.clean == "rmt" and X is None:
        raise ValueError(
            "clean='rmt' needs the raw series X: the Marchenko–Pastur "
            "bulk edge comes from the (n, T) window shape (DESIGN.md "
            "§18.2) — pass X, not S/moments")
    if cfg.filter != "tmfg" and reuse_tmfg is not None:
        raise ValueError(
            f"reuse_tmfg is the TMFG warm-start splice (DESIGN.md §10); "
            f"filter={cfg.filter!r} rebuilds its graph per window")

    can_fuse = (cfg.dbht_impl == "device" and reuse_tmfg is None
                and cfg.filter != "pmfg")
    if fused is None:
        fused = can_fuse
    elif fused and not can_fuse:
        raise ValueError(
            "fused=True requires dbht_impl='device', no reuse_tmfg and a "
            "device-buildable filter (the staged path is the host-oracle/"
            "warm-start mode and the only path for the host-orchestrated "
            "filter='pmfg', DESIGN.md §18.3; fused=False also remains the "
            "per-stage-timings mode, DESIGN.md §12.4)")

    if fused:
        # fence=False: the fused path's one device_get IS its sync —
        # the spans add no block_until_ready (the §15.1 zero-cost
        # contract, pinned by tests/test_obs.py), and the device phase
        # is device-true anyway because the transfer waits for the program
        with obs_trace.span("pipeline.fused", fence=False, keep=True,
                            batch=1) as sp:
            with _phase(sp, "put"):
                if S is not None:
                    arr, have_S = jnp.asarray(S, jnp.float32), True
                elif moments is not None:
                    from repro.stream.window import window_similarity
                    arr, have_S = window_similarity(moments), True
                else:
                    assert X is not None, "need X, S or moments"
                    arr = jnp.asarray(np.asarray(X), jnp.float32)
                    have_S = False
            host = _fused_run(sp, arr, cfg, have_S, False, mesh, None)
            if host is not None:
                with _phase(sp, "assemble"):
                    sums = _record_counters(host.counters, 1)
                    res = _result_from_fused(host, k=k)
                sp.attrs.update(sums)
        if host is None:
            # the partition exceeded the fused slot-grid caps (§17.3):
            # the staged sparse tail sizes its programs per cluster, so
            # it is correct at any partition — rerun there, and say so
            res = cluster(X, S=S, moments=moments, k=k, config=cfg,
                          fused=False, collect_timings=collect_timings)
            res.overflow = True
            return res
        _observe_total("fused", sp.duration)
        if collect_timings:
            res.timings = {"total": sp.duration, **_approx_timings(sums)}
        return res

    # ---- staged path: per-stage jits + syncs (DESIGN.md §12.4) ----------
    if cfg.filter != "tmfg":
        return _cluster_filtered_staged(X=X, S=S, moments=moments, k=k,
                                        cfg=cfg,
                                        collect_timings=collect_timings)
    approx = cfg.similarity == "topk"
    if approx and reuse_tmfg is not None and S is None and moments is None:
        raise ValueError(
            "similarity='topk' with reuse_tmfg needs S= or moments=: the "
            "warm-start splice reruns DBHT on the window's similarities, "
            "which only exist materialized (DESIGN.md §13)")
    timings = {}
    table = counters = None
    # each stage is one fenced span (DESIGN.md §15.1): ``sp.fence``
    # block_until_ready's the stage's device outputs at the boundary,
    # so the recorded splits measure device work, not async dispatch —
    # and they sum to ``total`` (pinned by tests/test_pipeline.py)
    with obs_trace.span("pipeline.similarity", fence=True) as sp_sim:
        if S is None and moments is not None:
            from repro.stream.window import window_similarity  # no cycle
            S = sp_sim.fence(window_similarity(moments))
        elif S is None and not approx:
            assert X is not None, "need X, S or moments"
            S = similarity_from_timeseries(np.asarray(X),
                                           backend=cfg.backend)
            if cfg.clean == "rmt":
                # same jitted clean the fused body composes (§18.2), so
                # fused==staged stays bitwise on the TMFG+rmt path
                from repro.filters import rmt as rmt_mod  # no cycle
                S = rmt_mod.clean(S, np.asarray(X).shape[-1])
            S = sp_sim.fence(S)
        elif S is not None:
            S = jnp.asarray(S, dtype=jnp.float32)
        if approx and reuse_tmfg is None:
            # sparse-similarity stage (DESIGN.md §13.2): an (n, sim_k)
            # candidate table instead of the (n, n) matrix — cut from S
            # when one is already materialized (stream windows), else
            # streamed straight from the series without ever building S
            from repro.approx import knn as approx_knn  # no import cycle
            if S is not None:
                kk = min(cfg.sim_k, S.shape[0] - 1)
                table, Zn = approx_knn.topk_from_similarity(S, kk), None
            else:
                assert X is not None, "need X, S or moments"
                X_j = jnp.asarray(np.asarray(X), jnp.float32)
                kk = min(cfg.sim_k, X_j.shape[0] - 1)
                table, Zn = approx_knn.topk_pearson_and_z(
                    X_j, kk, backend=cfg.backend)
            table = sp_sim.fence(table)
    timings["similarity"] = sp_sim.duration

    with obs_trace.span("pipeline.tmfg", fence=True) as sp_tmfg:
        w_edges = None
        if reuse_tmfg is not None:
            tm = reuse_tmfg
        elif approx and cfg.method == "lazy":
            # the sparse gain scan (DESIGN.md §13.3); the recorded
            # per-edge weights become the weighted adjacency the DBHT
            # stage gathers from, so S is never needed downstream either
            from repro.approx import sparse_tmfg as approx_tmfg
            tm, w_edges, counters = approx_tmfg.build_tmfg_sparse(
                table, Xn=Zn, S=S)
            tm = sp_tmfg.fence(tm)
            if S is None and cfg.apsp_method != "sparse":
                # the sparse APSP tail consumes w_edges directly
                # (DESIGN.md §14.3); other methods need the adjacency
                S = adjacency_from_weights(
                    tm.edges.shape[0] // 3 + 2, tm.edges, w_edges)
        elif approx:
            # non-lazy methods scan whole similarity rows per round;
            # they run on the DENSIFIED sparsification (missing entries
            # floored below the Pearson range) — exact at sim_k = n-1,
            # O(n²) again (lazy is the memory-saving path; §13.3)
            from repro.approx import knn as approx_knn
            S = approx_knn.densify(table, n=table.indices.shape[0])
            tm = build_tmfg(S, method=cfg.method, prefix=cfg.prefix,
                            topk=cfg.topk)
            tm = sp_tmfg.fence(tm)
        else:
            tm = build_tmfg(S, method=cfg.method, prefix=cfg.prefix,
                            topk=cfg.topk)
            tm = sp_tmfg.fence(tm)
    timings["tmfg"] = sp_tmfg.duration

    with obs_trace.span("pipeline.dbht+apsp", fence=True) as sp_dbht:
        res = dbht_mod.dbht(S, tm, config=cfg, impl=cfg.dbht_impl,
                            edge_weights=w_edges)
        sp_dbht.fence(res.linkage)
    timings["dbht+apsp"] = sp_dbht.duration
    timings["total"] = sum(timings.values())
    for stage in ("similarity", "tmfg", "dbht+apsp"):
        _observe_stage(stage, timings[stage])
    _observe_total("staged", timings["total"])
    if approx and counters is not None:
        # fallback/recall diagnostics of the sparse construction
        # (DESIGN.md §13.3) ride the timings dict AND the registry
        # (§15.3) — the counters are tiny scalars already materialized
        # behind the tmfg fence
        lk, fb = int(counters.lookups), int(counters.fallbacks)
        pm = int(counters.pair_misses)
        obs_metrics.counter("approx_lookups_total").inc(lk)
        obs_metrics.counter("approx_fallbacks_total").inc(fb)
        obs_metrics.counter("approx_pair_misses_total").inc(pm)
        if collect_timings:
            timings["sim_fallbacks"] = float(fb)
            timings["sim_fallback_rate"] = fb / max(lk, 1)
            timings["sim_pair_misses"] = float(pm)

    kk = k if k is not None else len(res.converging)
    labels = res.labels(kk)
    out = ClusterResult(labels=labels, linkage=res.linkage, tmfg=tm,
                        dbht=res, edge_sum=float(tm.edge_sum),
                        timings=timings if collect_timings else {},
                        reused_tmfg=reuse_tmfg is not None)
    return out


# ---------------------------------------------------------------------------
# non-TMFG filters, staged (DESIGN.md §18)
# ---------------------------------------------------------------------------

def _filtered_result(core_host, fg_host, *, b=None, k=None, timings=None,
                     ) -> ClusterResult:
    """ClusterResult from host copies of one §18.4 tail output +
    :class:`repro.filters.FilterGraph` (entry ``b`` of a batch, or the
    single matrix when ``b`` is None) — the same
    ``dbht._result_from_device`` unpacking the fused path uses."""
    pick = (lambda a: a) if b is None else (lambda a, b=b: a[b])
    res = dbht_mod._result_from_device(core_host, b)
    fg = jax.tree.map(pick, fg_host)
    kk = k if k is not None else len(res.converging)
    return ClusterResult(
        labels=res.labels(kk), linkage=res.linkage, tmfg=fg, dbht=res,
        edge_sum=float(fg.edge_sum), timings=timings or {})


def _cluster_filtered_staged(*, X, S, moments, k, cfg,
                             collect_timings) -> ClusterResult:
    """Staged (per-stage jit + fenced sync) path for a non-TMFG filter:
    the same ``similarity``/``tmfg``/``dbht+apsp`` span structure as the
    TMFG path — the "tmfg" span times the filter build — running the
    SAME jitted stage functions the fused body composes, so fused and
    staged agree bitwise (§12.2 extended to the §18 filter matrix)."""
    from repro import filters as filt  # lazy: no import cycle

    timings: Dict[str, float] = {}
    with obs_trace.span("pipeline.similarity", fence=True) as sp_sim:
        if S is None and moments is not None:
            from repro.stream.window import window_similarity  # no cycle
            S = sp_sim.fence(window_similarity(moments))
        elif S is None:
            assert X is not None, "need X, S or moments"
            Xh = np.asarray(X)
            S = similarity_from_timeseries(Xh, backend=cfg.backend)
            if cfg.clean == "rmt":
                S = filt.rmt.clean(S, Xh.shape[-1])
            S = sp_sim.fence(S)
        else:
            S = jnp.asarray(S, dtype=jnp.float32)
    timings["similarity"] = sp_sim.duration

    with obs_trace.span("pipeline.tmfg", fence=True) as sp_f:
        fg = sp_f.fence(filt.build_filter(S, cfg))
    timings["tmfg"] = sp_f.duration

    with obs_trace.span("pipeline.dbht+apsp", fence=True) as sp_tail:
        core = filt.filter_tail(S, fg, apsp_method=cfg.apsp_method,
                                apsp_hubs=cfg.apsp_hubs,
                                apsp_rounds=cfg.apsp_rounds,
                                backend=cfg.backend)
        sp_tail.fence(core["Z"])
    timings["dbht+apsp"] = sp_tail.duration
    timings["total"] = sum(timings.values())
    for stage in ("similarity", "tmfg", "dbht+apsp"):
        _observe_stage(stage, timings[stage])
    _observe_total("staged", timings["total"])

    return _filtered_result(jax.device_get(core), jax.device_get(fg), k=k,
                            timings=timings if collect_timings else None)


def _batched_filter_build(cfg: PipelineConfig, S_b):
    """Vmapped filter build for a staged batch, jitted per (filter
    knobs, shape) in the shared bounded executable cache — pmfg loops
    its host builder per entry and stacks the fixed-shape results."""
    from repro import filters as filt  # lazy: no import cycle

    if cfg.filter == "pmfg":
        fgs = [filt.build_pmfg(S_b[b]) for b in range(S_b.shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *fgs)
    fn = jitcache.cached(
        ("filter_build", cfg.filter, cfg.ag_m, cfg.backend, S_b.shape),
        lambda: jax.jit(jax.vmap(lambda s: filt.build_filter(s, cfg))))
    return fn(S_b)


def _cluster_filtered_batch_staged(arr, have_S: bool, *, k, cfg, B_out,
                                   collect_timings) -> "BatchClusterResult":
    """Staged batch path for a non-TMFG filter: vmapped stage programs
    with the usual fenced spans; entry ``b`` equals ``cluster(X[b])``."""
    from repro import filters as filt  # lazy: no import cycle

    B = arr.shape[0]
    timings: Dict[str, float] = {}
    with obs_trace.span("pipeline.similarity", fence=True,
                        batch=B) as sp_sim:
        if have_S:
            S_b = arr
        else:
            S_b = _batched_similarity(arr, cfg.backend)
            if cfg.clean == "rmt":
                T = int(arr.shape[-1])
                rmt_b = jitcache.cached(
                    ("rmt_clean_b", T, S_b.shape),
                    lambda: jax.jit(jax.vmap(
                        lambda s: filt.rmt.clean(s, T))))
                S_b = rmt_b(S_b)
            S_b = sp_sim.fence(S_b)
    timings["similarity"] = sp_sim.duration

    with obs_trace.span("pipeline.tmfg", fence=True, batch=B) as sp_f:
        fg_b = sp_f.fence(_batched_filter_build(cfg, S_b))
    timings["tmfg"] = sp_f.duration

    with obs_trace.span("pipeline.dbht+apsp", fence=True,
                        batch=B) as sp_tail:
        tail_b = jitcache.cached(
            ("filter_tail_b", cfg.apsp_method, cfg.apsp_hubs,
             cfg.apsp_rounds, cfg.backend, S_b.shape, fg_b.edges.shape),
            lambda: jax.jit(jax.vmap(
                lambda s, fg: filt.filter_tail(
                    s, fg, apsp_method=cfg.apsp_method,
                    apsp_hubs=cfg.apsp_hubs, apsp_rounds=cfg.apsp_rounds,
                    backend=cfg.backend))))
        core_b = tail_b(S_b, fg_b)
        sp_tail.fence(core_b["Z"])
        # ONE transfer, sliced to B_out first (pad entries stay on device)
        core_host = jax.device_get(
            jax.tree.map(lambda a: a[:B_out], core_b))
        fg_host = jax.device_get(jax.tree.map(lambda a: a[:B_out], fg_b))
    timings["dbht+apsp"] = sp_tail.duration
    timings["total"] = sum(timings.values())
    for stage in ("similarity", "tmfg", "dbht+apsp"):
        _observe_stage(stage, timings[stage])
    _observe_total("staged", timings["total"])

    per = {s: timings[s] / B
           for s in ("similarity", "tmfg", "dbht+apsp", "total")}
    results = [
        _filtered_result(core_host, fg_host, b=b, k=k,
                         timings=dict(per) if collect_timings else None)
        for b in range(B_out)]
    return BatchClusterResult(
        labels=np.stack([r.labels for r in results]), results=results,
        timings=timings if collect_timings else {})


# ---------------------------------------------------------------------------
# batched, data-parallel clustering
# ---------------------------------------------------------------------------

@dataclass
class BatchClusterResult:
    """Results for a batch of B clustered matrices.

    ``labels`` stacks the flat cluster assignments (B, n); ``results``
    holds the full per-matrix :class:`ClusterResult` objects.
    """

    labels: np.ndarray                     # (B, n)
    results: List[ClusterResult]
    timings: Dict[str, float] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, b: int) -> ClusterResult:
        return self.results[b]

    def __iter__(self):
        return iter(self.results)


@functools.partial(jax.jit, static_argnums=1)
def _batched_similarity(X: jnp.ndarray, backend: str = "auto") -> jnp.ndarray:
    """(B, n, L) -> (B, n, n) Pearson, vmapped over the batch axis.

    Per-item math is exactly ``cluster()``'s similarity stage
    (ops.pearson with the same backend), so a batch entry equals the
    single-matrix pipeline's similarity bit for bit (GSPMD splits the
    batched work over the data axis for free when the input carries a
    batch sharding)."""
    return jax.vmap(lambda x: ops.pearson(x, backend=backend))(X)


def _batched_tmfg(method: str, prefix: int, topk: int, shape=None):
    """Jitted vmapped TMFG build per static config AND batch shape,
    held in the shared bounded executable cache (DESIGN.md §12.3) so
    repeated ``cluster_batch`` calls (the throughput use case) compile
    once per (method, prefix, topk, batch shape) without the old
    unbounded lru_cache's compiled-executable leak — shape in the key
    means evicting an entry actually frees its compiled code."""
    return jitcache.cached(
        ("batched_tmfg", method, prefix, topk, shape),
        lambda: jax.jit(jax.vmap(
            lambda s: build_tmfg(s, method=method, prefix=prefix,
                                 topk=topk))))


def _batched_approx_tables(arr, have_S: bool, kk: int, backend: str):
    """Vmapped candidate-table stage for a batch (DESIGN.md §13.2):
    (B, n, L) series → per-item (n, kk) tables plus the standardized
    series (the sparse build's exact-value source), or (B, n, n)
    similarities → tables alone.  Jitted per (kind, kk, shape) in the
    shared bounded executable cache, like every staged batch program."""
    from repro.approx import knn as approx_knn  # lazy: no import cycle

    if have_S:
        fn = jitcache.cached(
            ("approx_topk_s", kk, arr.shape),
            lambda: jax.jit(jax.vmap(
                lambda s: approx_knn._topk_from_similarity(s, kk))))
        v, i = fn(arr)
        return approx_knn.TopKTable(values=v, indices=i), None

    fn = jitcache.cached(
        ("approx_topk_x", kk, backend, arr.shape),
        lambda: jax.jit(jax.vmap(
            lambda x: approx_knn._topk_and_z(x, kk, backend, 128, 128))))
    v, i, zn = fn(arr)
    return approx_knn.TopKTable(values=v, indices=i), zn


def _batched_sparse_tmfg(from_x: bool, table, src):
    """Vmapped sparse lazy TMFG (DESIGN.md §13.3), jitted per
    (source kind, shapes) in the shared bounded executable cache."""
    from repro.approx import sparse_tmfg as approx_tmfg

    fn = jitcache.cached(
        ("approx_tmfg", from_x, table.indices.shape, src.shape),
        lambda: jax.jit(jax.vmap(
            lambda tv, ti, s: approx_tmfg.sparse_lazy_tmfg(
                tv, ti, s, from_x=from_x))))
    return fn(table.values, table.indices, jnp.asarray(src, jnp.float32))


def cluster_batch(X=None, *, S=None, k: Optional[int] = None,
                  config: Optional[PipelineConfig] = None,
                  method: Optional[str] = None, prefix: Optional[int] = None,
                  topk: Optional[int] = None,
                  apsp_method: Optional[str] = None,
                  backend: Optional[str] = None,
                  variant: Optional[str] = None, mesh=None,
                  limit: Optional[int] = None,
                  dbht_impl: Optional[str] = None,
                  fused: Optional[bool] = None,
                  collect_timings: bool = False) -> BatchClusterResult:
    """Cluster a batch of datasets X (B, n, L) — or precomputed similarity
    matrices S (B, n, n) — data-parallel across devices.

    By default (``fused=None`` with the default ``dbht_impl="device"``)
    the ENTIRE batch pipeline — similarity, TMFG, APSP, the DBHT tree
    stage and the nested HAC — is one vmapped jitted program
    (:func:`run_pipeline_device`) with the batch axis sharded over
    ``mesh`` (defaults to a 1-D mesh over all local devices when B
    divides the device count; falls back to single-device execution
    otherwise, so CPU CI takes the same code path) and a single
    device→host transfer of the batch's outputs.  ``fused=False``
    restores the staged path — per-stage jits with a host sync between
    them, per-stage timings preserved (DESIGN.md §12.4) — and is the
    only path for ``dbht_impl="host"`` (the per-matrix numpy reference
    walk).

    ``limit`` materializes host-side results only for the first ``limit``
    entries: the stream scheduler (DESIGN.md §10.2) pads batches up to a
    bucket size so the jitted device program is reused, and the pad
    entries must not pay host-side DBHT work (they cost device FLOPs
    only — their outputs are never transferred).

    Returns a :class:`BatchClusterResult`; entry ``b`` is identical to
    ``cluster(X[b], ...)``.
    """
    cfg = PipelineConfig.resolve(
        variant, config, method=method, prefix=prefix, topk=topk,
        apsp_method=apsp_method, backend=backend, dbht_impl=dbht_impl)

    if cfg.clean == "rmt" and X is None:
        raise ValueError(
            "clean='rmt' needs the raw series X: the Marchenko–Pastur "
            "bulk edge comes from the (n, T) window shape (DESIGN.md "
            "§18.2) — pass X, not S")

    can_fuse = cfg.dbht_impl == "device" and cfg.filter != "pmfg"
    if fused is None:
        fused = can_fuse
    elif fused and not can_fuse:
        raise ValueError(
            "fused=True requires dbht_impl='device' and a device-buildable "
            "filter (the staged path is the host-oracle mode and the only "
            "path for the host-orchestrated filter='pmfg', DESIGN.md "
            "§18.3; fused=False also remains the per-stage-timings mode, "
            "DESIGN.md §12.4)")

    timings: Dict[str, float] = {}
    assert X is not None or S is not None, "need X or S"
    have_S = S is not None
    shape = np.shape(S if have_S else X)
    assert len(shape) == 3, f"batched input must be 3-D, got {shape}"
    assert limit is None or limit >= 1, f"limit must be >= 1, got {limit}"
    B = shape[0]
    B_out = B if limit is None else min(limit, B)

    def put(mesh):
        arr = jnp.asarray(S if have_S else X, dtype=jnp.float32)
        # place the batch over the mesh's data axes when it divides
        # them; otherwise stay on the default device (single-device
        # fallback)
        n_dev = len(jax.devices())
        if mesh is None and n_dev > 1 and B % n_dev == 0:
            mesh = dist_sh.data_mesh()
        if mesh is not None:
            arr = jax.device_put(arr, dist_sh.batch_shardings(mesh, arr))
        return arr, mesh

    if fused:
        # unfenced spans (§15.1): the sliced device_get is the one sync
        with obs_trace.span("pipeline.fused", fence=False, keep=True,
                            batch=B) as sp:
            with _phase(sp, "put"):
                arr, mesh = put(mesh)
            host = _fused_run(sp, arr, cfg, have_S, True, mesh, B_out)
            if host is not None:
                with _phase(sp, "assemble"):
                    sums = _record_counters(host.counters, B_out)
                    results = [_result_from_fused(host, b=b, k=k)
                               for b in range(B_out)]
                    labels = np.stack([r.labels for r in results])
                sp.attrs.update(sums)
        if host is None:
            # any entry past the fused slot-grid caps (§17.3) sends the
            # whole batch to the staged path (per-cluster-sized programs)
            out = cluster_batch(X, S=S, k=k, config=cfg, mesh=mesh,
                                limit=limit, fused=False,
                                collect_timings=collect_timings)
            for r in out.results:
                r.overflow = True
            return out
        total = sp.duration
        _observe_total("fused", total)
        if collect_timings:
            # the batch-summed approx diagnostics (§13.3), and the call's
            # time spread evenly over its B entries
            timings.update(_approx_timings(sums))
            timings["total"] = total
            for r in results:
                r.timings = {"total": total / B}
        return BatchClusterResult(labels=labels, results=results,
                                  timings=timings)

    arr, mesh = put(mesh)

    # ---- staged path (DESIGN.md §12.4) ----------------------------------
    # same fenced-span structure as single-matrix cluster() (§15.1):
    # stage splits are device-true and sum to "total"
    if cfg.filter != "tmfg":
        return _cluster_filtered_batch_staged(
            arr, have_S, k=k, cfg=cfg, B_out=B_out,
            collect_timings=collect_timings)
    approx = cfg.similarity == "topk"
    with obs_trace.span("pipeline.similarity", fence=True,
                        batch=B) as sp_sim:
        table_b = src_b = None
        if approx:
            kk = min(cfg.sim_k, arr.shape[1] - 1)
            table_b, src_b = _batched_approx_tables(arr, have_S, kk,
                                                    cfg.backend)
            table_b = sp_sim.fence(table_b)
            S_b = arr if have_S else None
        elif have_S:
            S_b = arr
        else:
            S_b = _batched_similarity(arr, cfg.backend)
            if cfg.clean == "rmt":
                # same vmapped jitted clean as the filter batch path
                # (§18.2): fused==staged stays bitwise on TMFG+rmt
                from repro.filters import rmt as rmt_mod  # no cycle
                T = int(arr.shape[-1])
                rmt_b = jitcache.cached(
                    ("rmt_clean_b", T, S_b.shape),
                    lambda: jax.jit(jax.vmap(
                        lambda s: rmt_mod.clean(s, T))))
                S_b = rmt_b(S_b)
            S_b = sp_sim.fence(S_b)
    timings["similarity"] = sp_sim.duration

    with obs_trace.span("pipeline.tmfg", fence=True, batch=B) as sp_tmfg:
        counters_b = w_b = None
        if approx and cfg.method == "lazy":
            # vmapped sparse gain scan (DESIGN.md §13.3); when built from
            # X the per-edge weights scatter into the weighted adjacency
            # so the batch never materializes a (B, n, n) similarity —
            # and for the sparse APSP tail they are consumed directly
            # (§14.6)
            tm_b, w_b, counters_b = _batched_sparse_tmfg(
                not have_S, table_b, S_b if have_S else src_b)
            tm_b = sp_tmfg.fence(tm_b)
            if S_b is None and cfg.apsp_method != "sparse":
                n = arr.shape[1]
                adj = jitcache.cached(
                    ("approx_adj", tm_b.edges.shape),
                    lambda: jax.jit(jax.vmap(
                        lambda e, w: adjacency_from_weights(n, e, w))))
                S_b = adj(tm_b.edges, w_b)
        elif approx:
            from repro.approx import knn as approx_knn  # no import cycle
            n = arr.shape[1]
            dense = jitcache.cached(
                ("approx_densify", table_b.indices.shape),
                lambda: jax.jit(jax.vmap(
                    lambda v, i: approx_knn._densify(v, i, n))))
            S_b = dense(table_b.values, table_b.indices)
            tm_b = sp_tmfg.fence(
                _batched_tmfg(cfg.method, cfg.prefix, cfg.topk,
                              S_b.shape)(S_b))
        else:
            tm_b = sp_tmfg.fence(
                _batched_tmfg(cfg.method, cfg.prefix, cfg.topk,
                              S_b.shape)(S_b))
    timings["tmfg"] = sp_tmfg.duration

    with obs_trace.span("pipeline.dbht+apsp", fence=True,
                        batch=B) as sp_dbht:
        t0 = time.perf_counter()
        if cfg.dbht_impl == "device":
            # the whole DBHT stage for the batch is ONE vmapped jitted
            # program plus one device→host transfer (DESIGN.md §11.4)
            dbs = dbht_mod.dbht_batch(S_b, tm_b, config=cfg, limit=B_out,
                                      edge_weights=w_b)
            t_dbht = time.perf_counter() - t0
        else:
            dbs, t_dbht = None, 0.0
            # S_b is None only on the sparse-tail approx path, where the
            # per-edge weights stand in for the similarity (§14.6)
            S_host = None if S_b is None else np.asarray(S_b[:B_out])
            w_host = None if w_b is None else np.asarray(w_b[:B_out])
        # ONE transfer, not B x leaves — sliced to B_out first so pad
        # entries of a bucketed micro-batch never cross the boundary
        tm_host = jax.device_get(jax.tree.map(lambda a: a[:B_out], tm_b))
        results: List[ClusterResult] = []
        for b in range(B_out):
            t_b = time.perf_counter()
            tm = jax.tree.map(lambda a, b=b: a[b], tm_host)
            if dbs is not None:
                res = dbs[b]
            else:
                res = dbht_mod.dbht(
                    None if S_host is None else S_host[b], tm, config=cfg,
                    impl="host",
                    edge_weights=None if w_host is None else w_host[b])
            kk = k if k is not None else len(res.converging)
            # per-result timings: the batched device stages (and the
            # batched device DBHT) amortize evenly over the B entries;
            # the host-side DBHT walk, when selected, is measured per b
            per = {"similarity": timings["similarity"] / B,
                   "tmfg": timings["tmfg"] / B,
                   "dbht+apsp": (t_dbht / B + (time.perf_counter() - t_b)
                                 if dbs is not None
                                 else time.perf_counter() - t_b)}
            per["total"] = sum(per.values())
            results.append(ClusterResult(
                labels=res.labels(kk), linkage=res.linkage, tmfg=tm,
                dbht=res, edge_sum=float(tm.edge_sum),
                timings=per if collect_timings else {}))
    timings["dbht+apsp"] = sp_dbht.duration
    timings["total"] = sum(timings.values())
    for stage in ("similarity", "tmfg", "dbht+apsp"):
        _observe_stage(stage, timings[stage])
    _observe_total("staged", timings["total"])
    if approx and counters_b is not None:
        # batch-summed fallback/recall diagnostics (DESIGN.md §13.3)
        # feed the registry unconditionally and — when asked — ride the
        # timings dict, added after "total" so they never count as wall
        # time
        lk = float(np.sum(np.asarray(counters_b.lookups)))
        fb = float(np.sum(np.asarray(counters_b.fallbacks)))
        pm = float(np.sum(np.asarray(counters_b.pair_misses)))
        obs_metrics.counter("approx_lookups_total").inc(lk)
        obs_metrics.counter("approx_fallbacks_total").inc(fb)
        obs_metrics.counter("approx_pair_misses_total").inc(pm)
        if collect_timings:
            timings["sim_fallbacks"] = fb
            timings["sim_fallback_rate"] = fb / max(lk, 1.0)
            timings["sim_pair_misses"] = pm

    return BatchClusterResult(
        labels=np.stack([r.labels for r in results]), results=results,
        timings=timings if collect_timings else {})
