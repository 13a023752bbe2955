"""Sparse-similarity scaling: dense (n, n) Pearson vs the streaming
top-K table (DESIGN.md §13), and the fused end-to-end approx path
(DESIGN.md §17).

Per n, the similarity rows answer:

  * wall time — the dense similarity stage (``ops.pearson``) against
    the blocked top-K table (``ops.topk``) and the sketch→rescore pool
    path (``project.candidate_pools`` + ``knn.rescore_pools``, the
    FLOPs lever: O(n²·d + n·P·L) vs O(n²·L)).
  * peak live bytes — what each similarity representation leaves alive
    for the TMFG stage, measured with ``jax.live_arrays``.  The
    acceptance bar (ISSUE 5): at n ≥ 2000 the topk path's bytes are
    STRICTLY lower than dense — enforced with an assert, so a
    regression fails ``run.py --strict``.

The fused rows (ISSUE 9) time the WHOLE ``PipelineConfig.approx``
pipeline fused (ONE jitted device program, core/fused_approx.py)
against the staged per-stage path on identical inputs, reporting
``fused_speedup``; at full scale a ≥10k-row joins them — the regime
the sparse path exists for.  The sharded row runs a forced-4-device
CPU subprocess (the tests/test_property.py harness pattern) timing
``topk_pearson_sharded`` against the single-device scan, reporting
``scaling_4cpu_sim`` and the child's warm-replay recompile count
(pinned to 0 by ``--check-schema``); it is a CPU simulation, never a
device number.

An end-to-end quality row at modest n reports the quality triplet
(ARI agreement, edge recall, edge-sum ratio) of ``sim_k=64`` via the
``quality.compare_to_dense`` harness.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

import jax

import time

from repro.approx import knn, project, quality
from repro.data.timeseries import make_dataset
from repro.kernels import ops
from repro.obs import trace as obs_trace
from .common import emit, measured, stage_cost as _stage

SIM_K = 64
SKETCH_DIM = 32
POOL = 128

# the fused-vs-staged dataset regime: 16 well-separated processes at
# noise 0.5 — converging-bubble counts stay within the §17.3 slot-grid
# caps here, so the fused program answers without the overflow rerun
FUSED_KC = 16
FUSED_NOISE = 0.5

_SHARDED_BENCH = textwrap.dedent("""
    import json, os, time
    import numpy as np, jax
    n = int(os.environ["BENCH_SHARDED_N"]); K = 64
    assert len(jax.devices()) == 4
    from repro.dist import sharding as sh
    from repro.kernels.topk import topk_pearson_jnp
    from repro.data.timeseries import make_dataset
    from repro.obs import trace as obs_trace
    mesh = sh.data_mesh(4)
    X = make_dataset(n, 96, 16, noise=0.5, seed=3)[0].astype(np.float32)
    f1 = jax.jit(lambda x: topk_pearson_jnp(x, K))
    f4 = jax.jit(lambda x: sh.topk_pearson_sharded(x, K, mesh))
    with obs_trace.watch_recompiles() as w:
        v1, i1 = jax.block_until_ready(f1(X))
        v4, i4, _ = jax.block_until_ready(f4(X))
    def best(f, reps=3):
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(X))
            b = min(b, time.perf_counter() - t0)
        return b
    with obs_trace.watch_recompiles() as wr:
        t1, t4 = best(f1), best(f4)
    exact = bool(np.array_equal(np.asarray(v1), np.asarray(v4))
                 and np.array_equal(np.asarray(i1), np.asarray(i4)))
    print(json.dumps(dict(t1=t1, t4=t4, compile_s=w.compile_s,
                          replay=wr.count, exact=exact)))
""")


def _fused_rows(scale: float):
    """Fused vs staged ``PipelineConfig.approx`` end to end (ISSUE 9).

    Same data, same config, same answer (the property suite pins label/
    linkage identity); the only difference is ONE jitted device program
    against the staged host-orchestrated stages.  At full scale the
    10k row joins — the first bench row in the regime the sparse tail
    was built for.
    """
    from repro.core.config import PipelineConfig
    from repro.core.pipeline import cluster

    rows = []
    n_bases = (2000, 4000, 10000) if scale >= 1.0 else (2000, 4000)
    for n_base in n_bases:
        n = max(64, int(round(n_base * scale)))
        reps = 3 if n <= 3000 else (2 if n <= 6000 else 1)
        X = make_dataset(n, 64, FUSED_KC, noise=FUSED_NOISE, seed=3)[0]
        cfg = PipelineConfig.approx(sim_k=min(SIM_K, n - 1),
                                    apsp_method="sparse")
        mf = measured(lambda: cluster(X, k=FUSED_KC, config=cfg).labels,
                      repeats=reps, warmup=1)
        ms = measured(
            lambda: cluster(X, k=FUSED_KC, config=cfg, fused=False).labels,
            repeats=reps, warmup=1)
        speedup = ms["run_s"] / max(mf["run_s"], 1e-9)
        if n >= 2000:
            # loose on purpose (the bench_apsp precedent): both paths
            # share the dominant lazy gain scan, so at n ≥ 2000 the
            # fused margin is host-sync savings — real but smaller
            # than single-shared-core jitter (consecutive warm calls
            # of the SAME executable swing ±10% here).  The band is
            # what catches the actual regression class: the §17.3
            # overflow double-pay ran fused ≈ 2x staged before the
            # n-adaptive c_cap fix, and any reappearance trips this
            # immediately while honest noise never does.
            assert mf["run_s"] < ms["run_s"] * 1.15, (
                f"fused approx must stay at/below staged at n={n}: "
                f"{mf['run_s']:.3f}s vs {ms['run_s']:.3f}s — is the "
                f"slot grid overflowing into the staged rerun?")
        rows.append(dict(
            name=f"approx/fused-vs-staged/n{n}",
            us_per_call=f"{mf['run_s'] * 1e6:.0f}",
            derived=f"fused_speedup={speedup:.2f}x",
            t_fused=f"{mf['run_s']:.4f}", t_staged=f"{ms['run_s']:.4f}",
            compile_s=f"{mf['compile_s'] + ms['compile_s']:.3f}",
            run_s=f"{mf['run_s']:.4f}",
            replay_recompiles=mf["replay_recompiles"]
            + ms["replay_recompiles"],
        ))
    return rows


def _sharded_row(scale: float):
    """4-device forced-host sharded top-K vs the single-device scan.

    Runs in a subprocess (XLA device count is fixed at import), mirrors
    the tests/test_property.py harness.  The child is pinned to the CPU
    (``JAX_PLATFORMS=cpu``): the parent holds the accelerator, so this
    row is a CPU simulation of four devices on every host, and says so
    in its name.  A failed child fails the section.
    """
    n = max(2048, int(round(8192 * scale)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["BENCH_SHARDED_N"] = str(n)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_BENCH],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded CPU-simulation child failed: "
                           f"{proc.stderr[-400:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["exact"], "sharded table must equal single-device"
    ratio = payload["t1"] / max(payload["t4"], 1e-9)
    return dict(
        name=f"approx/topk-sharded-4cpu-sim/n{n}",
        us_per_call=f"{payload['t4'] * 1e6:.0f}",
        derived=f"scaling_4cpu_sim={ratio:.2f}x",
        t_1dev=f"{payload['t1']:.4f}",
        compile_s=f"{payload['compile_s']:.3f}",
        run_s=f"{payload['t4']:.4f}",
        replay_recompiles=payload["replay"],
    )


def run(scale: float = 1.0):
    rows = []
    for n_base in (500, 1000, 2000):
        n = max(16, int(round(n_base * scale)))
        L = 96
        k = min(SIM_K, n - 1)
        X = make_dataset(n, L, 4, noise=0.6, seed=0)[0]

        t_dense, b_dense, c_dense = _stage(
            lambda: ops.pearson(X, backend="auto"))
        t_topk, b_topk, c_topk = _stage(
            lambda: tuple(knn.topk_pearson(X, k)))
        pool = min(POOL, n - 1)
        t_pool, _, c_pool = _stage(lambda: tuple(knn.rescore_pools(
            X, project.candidate_pools(X, pool, dim=SKETCH_DIM), k)))

        if n_base >= 2000 and n >= 2000:
            # the ISSUE 5 acceptance bar, enforced where the scale
            # actually reaches the regime
            assert b_topk < b_dense, (
                f"topk similarity must hold strictly less live memory "
                f"than dense at n={n}: {b_topk} >= {b_dense}")
        rows.append(dict(
            name=f"approx/similarity/n{n}",
            us_per_call=f"{t_topk * 1e6:.0f}",
            derived=f"mem_dense_over_topk="
                    f"{b_dense / max(b_topk, 1):.1f}x",
            t_dense=f"{t_dense:.4f}", t_topk=f"{t_topk:.4f}",
            t_pool=f"{t_pool:.4f}",
            compile_s=f"{c_dense + c_topk + c_pool:.3f}",
            run_s=f"{t_topk:.4f}",
            bytes_dense=b_dense, bytes_topk=b_topk,
        ))

    rows.extend(_fused_rows(scale))
    rows.append(_sharded_row(scale))

    # end-to-end quality at modest n (the e2e memory-scaling rows —
    # the sparse APSP+DBHT tail that removed the §13.5 dense boundary —
    # live in bench_sparse_apsp, DESIGN.md §14)
    n = max(24, int(round(240 * scale)))
    X = make_dataset(n, 64, 4, noise=0.6, seed=1)[0]
    with obs_trace.watch_recompiles() as w:
        t0 = time.perf_counter()
        rep = quality.compare_to_dense(X, sim_k=min(SIM_K, n - 1), k=4)
        wall = time.perf_counter() - t0
    rows.append(dict(
        name=f"approx/e2e-quality/n{n}",
        us_per_call="",
        derived=f"ari={rep['ari']:.3f}",
        compile_s=f"{w.compile_s:.3f}",
        run_s=f"{max(wall - w.compile_s, 0.0):.3f}",
        edge_recall=f"{rep['edge_recall']:.3f}",
        edge_sum_ratio=f"{rep['edge_sum_ratio']:.4f}",
    ))
    return emit(rows, ["name", "us_per_call", "derived", "t_dense",
                       "t_topk", "t_pool", "t_fused", "t_staged",
                       "t_1dev", "compile_s", "run_s",
                       "replay_recompiles", "bytes_dense", "bytes_topk",
                       "edge_recall", "edge_sum_ratio"])


if __name__ == "__main__":
    run()
