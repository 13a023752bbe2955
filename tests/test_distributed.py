"""Multi-device equivalence of the sharded clustering pipeline.

Runs in a subprocess with --xla_force_host_platform_device_count=8 so the
main test process keeps its single-device view (see conftest.py).
"""

import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8
    from repro.data.timeseries import make_dataset
    from repro.core.tmfg import build_tmfg
    from repro.core import distributed as DD, apsp as A

    mesh = jax.make_mesh((8,), ("data",))
    X, _ = make_dataset(64, 48, 4, seed=5)
    S = np.corrcoef(X).astype(np.float32)

    Sp = DD.pearson_sharded(jnp.asarray(X), mesh)
    np.testing.assert_allclose(np.asarray(Sp), S, atol=3e-5)

    ref = jax.tree.map(np.asarray, build_tmfg(jnp.asarray(S), method="lazy"))
    for coll in ("batched", "per-element"):
        got = jax.tree.map(np.asarray, DD.build_tmfg_sharded(
            jnp.asarray(S), mesh, collectives=coll))
        assert (ref.insert_order == got.insert_order).all(), coll
        np.testing.assert_allclose(ref.edge_sum, got.edge_sum, rtol=1e-4)

    W = A.edge_lengths(64, jnp.asarray(ref.edges), jnp.asarray(S))
    D_ref = np.asarray(A.apsp_hub(W, n_hubs=8, rounds=16))
    D_sh = np.asarray(DD.apsp_hub_sharded(W, mesh, n_hubs=8, rounds=16))
    np.testing.assert_allclose(D_sh, D_ref, atol=1e-5)
    print("SHARDED-OK")
""")


def test_sharded_pipeline_equivalence():
    import os

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-OK" in proc.stdout


SCRIPT_BATCH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 4
    from repro.data.timeseries import make_dataset
    from repro.core.pipeline import cluster, cluster_batch
    from repro.dist import sharding as sh
    from repro.kernels import ref

    # standalone sharded kernel wrappers vs their oracles
    mesh = sh.data_mesh()
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(32, 24)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(sh.pearson_shardmap(X, mesh)),
                               np.asarray(ref.pearson_ref(X)), atol=3e-6)
    Sq = jnp.asarray(rng.normal(size=(32, 32)).astype(np.float32))
    mask = jnp.zeros((32,), bool).at[jnp.asarray([1, 5])].set(True)
    mv, mi = sh.masked_argmax_shardmap(Sq, mask, mesh)
    rv, ri = ref.masked_argmax_ref(Sq, mask)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(rv))
    assert (np.asarray(mi) == np.asarray(ri)).all()
    A = jnp.asarray(rng.uniform(0, 5, size=(32, 32)).astype(np.float32))
    Bm = jnp.asarray(rng.uniform(0, 5, size=(32, 32)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(sh.minplus_shardmap(A, Bm, mesh)),
                               np.asarray(ref.minplus_ref(A, Bm)), atol=1e-6)

    Xb = np.stack([make_dataset(n=48, L=40, k=3, noise=0.7, seed=s)[0]
                   for s in range(4)])
    bres = cluster_batch(Xb, k=3, variant="opt")
    for b in range(4):
        single = cluster(Xb[b], k=3, variant="opt")
        assert (single.labels == bres.labels[b]).all(), b

    # dense sharded funnel below HUB_MIN_N (exact APSP, replicated):
    # same labels as one device, and a replay compiles nothing
    from repro.obs.trace import watch_recompiles
    sharded = cluster(Xb[0], k=3, variant="opt", mesh=mesh)
    assert (sharded.labels == bres.labels[0]).all()
    with watch_recompiles() as w:
        again = cluster(Xb[0], k=3, variant="opt", mesh=mesh)
    assert w.count == 0, w.count
    assert (again.labels == sharded.labels).all()
    print("BATCH-OK")
""")


def test_cluster_batch_multi_device_equivalence():
    """cluster_batch with the batch sharded over 4 devices produces the
    same labels as the single-device loop (DESIGN.md §7.4), and the
    standalone sharded kernel wrappers match their single-device
    oracles."""
    import os

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT_BATCH], capture_output=True,
        text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "BATCH-OK" in proc.stdout
