"""Bring-up smoke run: the clustering pipeline end to end on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(c) below
    python chip_smoke.py --chips 4    # four chips: the sharded paths only
    python chip_smoke.py --rehearse   # CPU rehearsal, toy sizes, interpret
                                      # kernels; never prints the ok line

One chip, through the entry points a user calls:
  (a) reference — ``cluster()`` with ``PipelineConfig.opt()`` and
      ``.approx(sim_k=64)`` on ``make_dataset(2000, 96, 6, seed)``,
      against the float32 numpy reference: ``core/tmfg_ref.py`` for the
      TMFG (edge sum and edges shared) and, for the labels (ARI), the
      same config with the host DBHT oracle (``dbht_impl="host"``) run on
      the numpy similarity matrix;
  (b) paper size — Crop (n=19,412, L=46, k=24) through ``cluster()``
      with ``.opt()`` (dense S, 1.5 GB) and ``.approx(sim_k=64)``:
      structural invariants, and no staged rerun behind the fused path;
  (c) serving — a ``ClusterService(n=500, window=252, k=10)`` with
      admission control answers a few submit+drain requests; no ticket
      is degraded and the labels equal ``cluster()`` on the same window.

Four chips (``--chips 4``): the row-panel sharded top-K scan
(``cluster(..., mesh=data_mesh(4))`` at Crop size) and the
data-parallel ``cluster_batch`` (B=4, n=2,000), each against one chip,
one call each (compile included): four chips cost four times as much.

Every line but the last is one JSON record: the kernels' resolved
backends, then per phase its checks, ``compile_s``, warm ``run_s``
(``cluster()`` returns host arrays, so each timing ends after the
device finished) and ``peak_bytes_in_use``.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU.  A failed check raises: the exit code is then non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0

# Limits of phase (a) against the float32 numpy reference, set at its input
# (make_dataset(2000, 96, 6, seed=0)) from two CPU readings each: the f32
# pipeline, and a control whose similarity has both dot operands rounded to
# bf16 (what one default-precision MXU pass computes).  Every limit but the
# approx edge sum must reject the control.
#
# OPT builds the same lazy TMFG as tmfg_ref.tmfg_lazy, so only the
# rounding of S separates them: edge sum -1.71e-6 relative (control
# +3.48e-5), edges shared 1.0 (control 0.771).
OPT_EDGE_SUM_RTOL = 1e-5
OPT_MIN_EDGES_SHARED = 0.99
# The approx build sees only 64 candidates per vertex, so it misses some
# partners the exact gains would pick: edge sum -6.85e-4 (control
# -8.91e-4), edges shared 0.789 (control 0.641).  The edge-sum limit
# bounds that loss of quality (a tenth of the ~1% spread between the
# paper's TMFG variants, fig. 7); it is no check of precision.
APPROX_EDGE_SUM_RTOL = 2e-3
APPROX_MIN_EDGES_SHARED = 0.75
# The host oracle runs the same config with the host DBHT walk on the
# numpy S, so it shares no similarity with the run under test: ARI 1.0
# for both configs (control 0.816 OPT, 0.815 approx).  0.99 admits a
# handful of vertices moved by a near-tie.
MIN_ARI_VS_HOST = 0.99


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def emit(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def once(fn):
    """One call: (result, its wall seconds and the compiles inside it).
    ``cluster()`` returns host arrays, so the wall time ends after the
    device finished."""
    from repro.obs import trace as obs_trace

    with obs_trace.watch_recompiles() as w:
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
    return out, dict(compile_s=w.compile_s, programs=w.count, wall_s=wall)


def timed(fn):
    """One cold call (compiles) and one warm call."""
    _, cold = once(fn)
    out, warm = once(fn)
    return out, dict(compile_s=cold["compile_s"], programs=cold["programs"],
                     cold_s=cold["wall_s"], run_s=warm["wall_s"],
                     warm_programs=warm["programs"])


def structure(res, n: int) -> dict:
    """What the TMFG-DBHT invariants are checked on: edge count, distinct
    in-range non-loop edges, label count, linkage shape and finiteness."""
    e = np.sort(np.asarray(res.tmfg.edges), axis=1)
    return dict(
        edges=int(e.shape[0]), distinct_edges=len(np.unique(e, axis=0)),
        edges_valid=bool(np.all(e[:, 0] != e[:, 1]) and e.min() >= 0
                         and e.max() < n),
        labels=len(np.unique(res.labels)),
        linkage_ok=bool(res.linkage.shape == (n - 1, 4)
                        and np.all(np.isfinite(res.linkage))),
        overflow=bool(res.overflow))


def check_structure(s: dict, n: int, k: int) -> None:
    """A planar maximal graph (3n-6 distinct edges, no self-loops), k
    labels, a finite linkage, and the fused answer (no staged rerun)."""
    check(s["edges"] == s["distinct_edges"] == 3 * n - 6,
          f"{s['edges']} edges ({s['distinct_edges']} distinct), want 3n-6")
    check(s["edges_valid"], "self-loop or out-of-range edge")
    check(s["labels"] == k, f"{s['labels']} labels, want {k}")
    check(s["linkage_ok"], "linkage not (n-1, 4) and finite")
    check(not s["overflow"], "fused program overflowed; staged rerun used")


def _edges(e) -> set:
    return set(map(tuple, np.sort(np.asarray(e), axis=1).tolist()))


def phase_reference(dev, backend: str, n: int, L: int, k: int) -> None:
    from repro.core import PipelineConfig, cluster, tmfg_ref
    from repro.core.ari import ari
    from repro.data.timeseries import make_dataset

    X, y = make_dataset(n, L, k, seed=SEED)
    S = np.corrcoef(X).astype(np.float32)
    t0 = time.perf_counter()
    ref = tmfg_ref.tmfg_lazy(S)
    ref_s = time.perf_counter() - t0
    for name, cfg, rtol, min_shared in (
            ("opt", PipelineConfig.opt(backend=backend), OPT_EDGE_SUM_RTOL,
             OPT_MIN_EDGES_SHARED),
            ("approx", PipelineConfig.approx(sim_k=64, backend=backend),
             APPROX_EDGE_SUM_RTOL, APPROX_MIN_EDGES_SHARED)):
        res, t = timed(lambda cfg=cfg: cluster(X, k=k, config=cfg))
        host = cluster(S=S, k=k, config=cfg.replace(dbht_impl="host"))
        rel = (res.edge_sum - ref.edge_sum) / ref.edge_sum
        a = ari(host.labels, res.labels)
        shared = len(_edges(res.tmfg.edges) & _edges(ref.edges)) / (3 * n - 6)
        emit(phase=f"a/reference/{name}", n=n, L=L, k=k, **t,
             edge_sum=res.edge_sum, ref_edge_sum=ref.edge_sum,
             edge_sum_rel_delta=rel, edge_sum_rtol=rtol,
             edges_shared_with_ref=shared, min_edges_shared=min_shared,
             ari_vs_host=a, min_ari=MIN_ARI_VS_HOST,
             ari_vs_truth=ari(y, res.labels), ref_tmfg_s=ref_s,
             overflow=bool(res.overflow), peak_bytes_in_use=peak_bytes(dev))
        check(not res.overflow, f"{name}: staged rerun hid the fused path")
        check(abs(rel) <= rtol, f"{name}: edge sum off by {rel:.3g}")
        check(shared >= min_shared,
              f"{name}: {shared:.4f} of the edges shared with tmfg_ref")
        check(a >= MIN_ARI_VS_HOST, f"{name}: ARI vs host oracle {a:.4f}")


def phase_paper_size(dev, backend: str, scale: float) -> None:
    from repro.core import PipelineConfig, cluster
    from repro.core.ari import ari
    from repro.data.timeseries import make_ucr_like

    name, X, y, k = make_ucr_like("Crop", scale=scale, seed=SEED)
    n, L = X.shape
    for cname, cfg in (("opt", PipelineConfig.opt(backend=backend)),
                       ("approx",
                        PipelineConfig.approx(sim_k=64, backend=backend))):
        res, t = timed(lambda cfg=cfg: cluster(X, k=k, config=cfg))
        st = structure(res, n)
        emit(phase=f"b/{name}/{cname}", n=n, L=L, k=k, **t, **st,
             edge_sum=res.edge_sum, ari_vs_truth=ari(y, res.labels),
             peak_bytes_in_use=peak_bytes(dev))
        check_structure(st, n, k)


def phase_serving(dev, backend: str, n: int, window: int, k: int) -> None:
    from repro.core import cluster
    from repro.data.timeseries import make_dataset
    from repro.stream.admission import AdmissionConfig
    from repro.stream.service import ClusterService

    step, requests = 21, 3
    X, _ = make_dataset(n, window + step * (requests - 1), k, seed=SEED)
    svc = ClusterService(n=n, window=window, k=k, variant="opt",
                         backend=backend, admission=AdmissionConfig())
    t_ticks = 0
    for r in range(requests):
        lo, hi = (0, window) if r == 0 else (t_ticks, t_ticks + step)
        for t in range(lo, hi):
            svc.tick(X[:, t])
        t_ticks = hi
        S = svc.similarity()
        t0 = time.perf_counter()
        ticket = svc.submit()
        svc.drain()
        wall = time.perf_counter() - t0
        check(ticket.done, f"request {r} unresolved")
        check(ticket.outcome == "admitted" and not ticket.degraded,
              f"request {r}: outcome={ticket.outcome} mode={ticket.mode} "
              f"degraded={ticket.degraded}")
        want = cluster(S=S, k=k, config=svc.cfg)
        same = bool(np.array_equal(ticket.result.labels, want.labels))
        emit(phase=f"c/serve/request{r}", n=n, window=window, k=k,
             ticks=t_ticks, submit_drain_s=wall, degraded=ticket.degraded,
             labels_equal_cluster=same, peak_bytes_in_use=peak_bytes(dev))
        check(same, f"request {r}: served labels differ from cluster()")
    st = svc.admission.stats()
    emit(phase="c/serve/admission", **st)
    check(st["degraded_total"] == 0 and st["shed_total"] == 0,
          "admission degraded or shed a request")
    check(st["breaker_state"] == 0, "breaker left closed state")


def phase_four_chips(backend: str, scale: float, n_batch: int) -> None:
    import jax

    from repro.approx import knn
    from repro.core import PipelineConfig, cluster, cluster_batch
    from repro.core.pipeline import run_pipeline_device
    from repro.data.timeseries import make_dataset, make_ucr_like
    from repro.dist import sharding as dist_sh

    mesh = dist_sh.data_mesh(4)
    cfg = PipelineConfig.approx(sim_k=64, backend=backend)
    name, X, _, k = make_ucr_like("Crop", scale=scale, seed=SEED)
    n = X.shape[0]

    one = knn.topk_pearson(X, 64, backend=backend)
    v4, i4, _ = dist_sh.topk_pearson_sharded(X, 64, mesh, backend=backend)
    same_v = bool(np.array_equal(np.asarray(one.values), np.asarray(v4)))
    same_i = bool(np.array_equal(np.asarray(one.indices), np.asarray(i4)))
    r1, t1 = once(lambda: cluster(X, k=k, config=cfg))
    r4, t4 = once(lambda: cluster(X, k=k, config=cfg, mesh=mesh))
    same_lab = bool(np.array_equal(r1.labels, r4.labels))
    emit(phase=f"4chip/{name}/approx-sharded", n=n, k=k,
         table_values_equal=same_v, table_indices_equal=same_i,
         labels_equal=same_lab, one_chip=t1, four_chips=t4,
         table_sharding=str(v4.sharding),
         overflow=[bool(r1.overflow), bool(r4.overflow)])
    check(same_v and same_i, "sharded top-K table differs from one chip")
    check(same_lab, "sharded labels differ from one chip")
    check(not (r1.overflow or r4.overflow), "staged rerun hid the fused path")

    kb = 6
    Xb = np.stack([make_dataset(n_batch, 96, kb, seed=SEED + b)[0]
                   for b in range(4)])
    ocfg = PipelineConfig.opt(backend=backend)
    rb, tb = once(lambda: cluster_batch(Xb, k=kb, config=ocfg))
    out = run_pipeline_device(
        jax.device_put(Xb, dist_sh.batch_shardings(mesh, Xb)), ocfg,
        is_similarity=False, batched=True, mesh=mesh)
    singles = [cluster(Xb[b], k=kb, config=ocfg) for b in range(4)]
    equal = [bool(np.array_equal(rb.labels[b], singles[b].labels))
             for b in range(4)]
    emit(phase="4chip/batch/opt", B=4, n=n_batch, k=kb, **tb,
         labels_equal_per_matrix=equal,
         linkage_sharding=str(out.linkage.sharding),
         edges_sharding=str(out.tmfg.edges.sharding))
    check(all(equal), "batched labels differ from the one-chip results")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at toy sizes with interpret-mode "
                         "kernels; never prints the ok line")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import jitcache

    cache_dir = jitcache.use_persistent_cache()
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    dev = devs[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devs))
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {device}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1

    backend = "interpret" if args.rehearse else "auto"
    b = ops._resolve(backend)
    # every kernel dispatches through ops._resolve; sparse_relax has one
    # XLA form on every platform (no Mosaic lane gather)
    resolved = dict(pearson=b, topk=b, masked_argmax=b, minplus=b,
                    sparse_relax="xla (one form everywhere)")
    emit(phase="setup", device=device, compile_cache=cache_dir,
         jax=jax.__version__, backend=backend, resolved=resolved)
    if not args.rehearse:
        check(b == "pallas", f"kernels resolved to {b}, want pallas")

    t0 = time.perf_counter()
    if args.chips == 4:
        scale, n_batch = (0.01, 48) if args.rehearse else (1.0, 2000)
        phase_four_chips(backend, scale, n_batch)
    elif args.rehearse:
        phase_reference(dev, backend, 120, 48, 4)
        phase_paper_size(dev, backend, 0.01)
        phase_serving(dev, backend, 40, 30, 4)
    else:
        phase_reference(dev, backend, 2000, 96, 6)
        phase_paper_size(dev, backend, 1.0)
        phase_serving(dev, backend, 500, 252, 10)
    emit(phase="done", wall_s=time.perf_counter() - t0,
         peak_bytes_in_use=peak_bytes(dev))
    if args.rehearse:
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
