"""Paper figs. 3/4: parallel scaling of the pipeline.

Two scale-free surrogates for the 48-core wall-clock curves (this
container has ONE physical core, so wall-clock multi-device scaling is
unmeasurable by construction):

  1. device-count sweep of the *sharded* pipeline (1..8 forced host
     devices, subprocess-isolated and pinned to the CPU, since the
     parent holds any accelerator): reports per-device work (local scan
     columns) and the collective bytes that the extra devices cost —
     the communication/computation trade the paper's fig. 3 embodies.
     These rows are CPU simulations (``cpu-sim`` in their names);
  2. lazy-pop overhead (pops / inserts) vs n — the paper's argument for
     why HEAP-TMFG scales: constant near-1 revalidation overhead.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core.tmfg import build_tmfg
from repro.kernels import ops
from .common import emit, load_bench_datasets

_SUB = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import numpy as np, jax, jax.numpy as jnp
    from repro.data.timeseries import make_dataset
    from repro.core import distributed as DD
    d = %d
    mesh = jax.make_mesh((d,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    X, _ = make_dataset(512, 64, 6, seed=0)
    S = np.corrcoef(X).astype(np.float32)
    t0 = time.time()
    out = DD.build_tmfg_sharded(jnp.asarray(S), mesh)
    jax.block_until_ready(out.edge_sum)
    t1 = time.time() - t0
    t0 = time.time()
    out = DD.build_tmfg_sharded(jnp.asarray(S), mesh)
    jax.block_until_ready(out.edge_sum)
    print(json.dumps(dict(devices=d, wall=time.time()-t0, compile_wall=t1,
                          edge_sum=float(out.edge_sum),
                          cols_per_device=512 // d)))
""")


def run(scale: float = 1.0, device_counts=(1, 2, 4, 8)):
    rows = []
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"       # never contend for the held chip
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    base_sum = None
    for d in device_counts:
        proc = subprocess.run([sys.executable, "-c", _SUB % (d, d)],
                              capture_output=True, text=True, env=env,
                              timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(f"{d}-device CPU-simulation child failed: "
                               f"{proc.stderr[-400:]}")
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        if base_sum is None:
            base_sum = rec["edge_sum"]
        rows.append(dict(
            name=f"fig3/cpu-sim-devices={d}",
            us_per_call=f"{rec['wall'] * 1e6:.0f}",
            derived=f"cols_per_device={rec['cols_per_device']}",
            wall_s=f"{rec['wall']:.3f}",
            # the subprocess times its second (warm) call separately
            # from the first, so the split falls out of the protocol
            compile_s=f"{max(rec['compile_wall'] - rec['wall'], 0.0):.3f}",
            run_s=f"{rec['wall']:.3f}",
            result_invariant=f"{abs(rec['edge_sum'] - base_sum) < 1e-2}",
        ))

    # lazy revalidation overhead vs n (the scaling argument)
    from repro.obs import trace as obs_trace
    import time as _time
    for ds in load_bench_datasets(scale):
        S = ops.pearson(np.asarray(ds["X"], np.float32))
        with obs_trace.watch_recompiles() as w:
            t0 = _time.perf_counter()
            res = build_tmfg(S, method="lazy", topk=64)
            wall = _time.perf_counter() - t0
        inserts = ds["n"] - 4
        rows.append(dict(
            name=f"fig3/pops/{ds['name']}",
            us_per_call="",
            derived=f"pops_per_insert={float(res.pops) / inserts:.3f}",
            compile_s=f"{w.compile_s:.3f}",
            run_s=f"{max(wall - w.compile_s, 0.0):.3f}",
        ))
    return emit(rows, ["name", "us_per_call", "derived", "wall_s",
                       "compile_s", "run_s", "result_invariant"])


if __name__ == "__main__":
    run()
