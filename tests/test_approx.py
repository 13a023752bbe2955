"""repro.approx — the sparse-similarity TMFG path (DESIGN.md §13).

Pins of ISSUE 5's acceptance criteria:
  * exactness at full K — ``similarity="topk"`` with ``sim_k = n-1`` is
    label- AND linkage-BITWISE-identical to the dense staged path for
    every named variant, from X and from S, batched and unbatched, down
    to degenerate n=4/n=5;
  * the memory contract — the similarity+TMFG program of the approx
    path contains NO (n, n) buffer (jaxpr shape check; since ISSUE 9
    the whole fused ``.approx()`` program carries the same guarantee —
    tests/test_property.py pins it end to end);
  * the quality floor — ARI ≥ 0.9 of the dense path's ARI on the
    synthetic regime data at sim_k = 32;
  * the wiring — config validation, content-key/batching-key inclusion,
    the fused end-to-end approx path (ISSUE 9 retired the staged-only
    §13.5 rejection), and the stream service running an approx config
    end to end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import clustered_similarity, regime_batch
from repro.approx import knn, project, quality
from repro.approx.sparse_tmfg import build_tmfg_sparse, sparse_lazy_tmfg
from repro.core.ari import ari
from repro.core.config import PipelineConfig
from repro.core.pipeline import (VARIANTS, cluster, cluster_batch,
                                 run_pipeline_device)
from repro.data.timeseries import make_dataset
from repro.kernels.ref import pearson_ref, standardize_rows


def _approx_cfg(variant: str, sim_k: int) -> PipelineConfig:
    return PipelineConfig.variant(variant).replace(similarity="topk",
                                                   sim_k=sim_k)


def _assert_bitwise(dense, approx, msg=""):
    """Full-K exactness is a BITWISE pin (stronger than the fused-path
    label/linkage tolerance): same staged plan, same operand values."""
    np.testing.assert_array_equal(dense.labels, approx.labels, err_msg=msg)
    np.testing.assert_array_equal(dense.linkage, approx.linkage,
                                  err_msg=msg)
    assert dense.edge_sum == approx.edge_sum, msg


# ---------------------------------------------------------------------------
# exactness at full K (the §13.3 contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_full_k_bitwise_identical_all_variants(variant):
    n = 48
    _, X, _ = clustered_similarity(n, k=3, seed=5)
    d = cluster(X, k=3, config=PipelineConfig.variant(variant), fused=False)
    a = cluster(X, k=3, config=_approx_cfg(variant, n - 1))
    _assert_bitwise(d, a, msg=variant)


@pytest.mark.parametrize("variant", ["opt", "heap", "par-10"])
def test_full_k_bitwise_identical_from_similarity(variant):
    """The from-S source (the streaming-window path) hits the same
    values through gathers instead of matvec rescoring."""
    n = 40
    S, _, _ = clustered_similarity(n, k=3, seed=2)
    d = cluster(S=S, k=3, config=PipelineConfig.variant(variant),
                fused=False)
    a = cluster(S=S, k=3, config=_approx_cfg(variant, n - 1))
    _assert_bitwise(d, a, msg=variant)


@pytest.mark.parametrize("B", [2, 3])
def test_full_k_bitwise_identical_batched(B):
    """Batch shapes: every entry of the vmapped sparse path equals the
    dense staged batch entry AND the single-matrix approx run."""
    n = 48
    Xs = regime_batch(B, n, stack=False)
    cfga = _approx_cfg("opt", n - 1)
    ba = cluster_batch(np.stack(Xs), k=3, config=cfga)
    bd = cluster_batch(np.stack(Xs), k=3, config=PipelineConfig.opt(),
                       fused=False)
    for b in range(B):
        _assert_bitwise(bd[b], ba[b], msg=f"entry {b}")
        single = cluster(Xs[b], k=3, config=cfga)
        np.testing.assert_array_equal(single.labels, ba.labels[b])
        np.testing.assert_array_equal(single.linkage, ba[b].linkage)


@pytest.mark.parametrize("n", [4, 5])
def test_full_k_degenerate_small_n(n):
    X, _ = make_dataset(n, 24, 2, noise=0.7, seed=n)
    d = cluster(X, config=PipelineConfig.opt(), fused=False)
    a = cluster(X, config=PipelineConfig.approx(sim_k=n - 1))
    np.testing.assert_array_equal(d.labels, a.labels)
    np.testing.assert_array_equal(
        d.linkage[:, [0, 1, 3]], a.linkage[:, [0, 1, 3]])
    np.testing.assert_allclose(d.linkage[:, 2], a.linkage[:, 2],
                               rtol=1e-6, atol=1e-6)


def test_sim_k_clamped_to_n_minus_1():
    """sim_k beyond n-1 (one config served many n) clamps to full K —
    and is therefore exact."""
    n = 32
    _, X, _ = clustered_similarity(n, k=2, seed=1)
    d = cluster(X, k=2, config=PipelineConfig.opt(), fused=False)
    a = cluster(X, k=2, config=PipelineConfig.approx(sim_k=10_000))
    _assert_bitwise(d, a)


# ---------------------------------------------------------------------------
# the memory contract: no (n, n) buffer before the DBHT boundary (§13.5)
# ---------------------------------------------------------------------------

def _jaxpr_text(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


def test_similarity_and_tmfg_never_materialize_dense_square():
    """The jaxpr of the approx path's similarity+TMFG program — the
    exact stages whose dense forms allocate S — contains no (n, n)
    array for ANY dtype.  (The DBHT/APSP stage still runs on dense
    (n, n) length/distance matrices: the documented §13.5 boundary.)"""
    n, L, K = 256, 48, 32
    X = jax.random.normal(jax.random.PRNGKey(0), (n, L), jnp.float32)

    def sim_and_tmfg(x):
        table = knn.topk_pearson(x, K, bm=64)
        zn = standardize_rows(x)
        return sparse_lazy_tmfg(table.values, table.indices, zn,
                                from_x=True)

    text = _jaxpr_text(sim_and_tmfg, X)
    assert f"[{n},{n}]" not in text, \
        "approx similarity+TMFG program allocates an (n, n) buffer"
    # positive control: the dense program trips the same detector
    from repro.core.tmfg import build_tmfg
    from repro.kernels import ops
    dense_text = _jaxpr_text(
        lambda x: build_tmfg(ops.pearson(x, backend="jnp")), X)
    assert f"f32[{n},{n}]" in dense_text


def test_full_sparse_pipeline_never_materializes_dense_square():
    """ISSUE 6: with ``apsp_method="sparse"`` the CONTRACT extends past
    the §13.5 boundary — every device program of the staged `.approx()`
    pipeline (similarity+TMFG above, then hub factorization, the (bm, n)
    panel sweep, and the per-cluster HAC blocks) is free of (n, n)
    buffers for any dtype.  The dense tail's own program is the positive
    control: the same detector trips on it."""
    from repro.core import apsp as apsp_mod
    from repro.core import sparse_dbht
    from repro.kernels.sparse_apsp import csr_from_edges

    n, h, bm = 256, 16, 64
    E = 3 * n - 6
    e = jnp.zeros((E, 2), jnp.int32)
    w = jnp.ones((E,), jnp.float32)

    # stage: hub factorization over the CSR edges — O(h·n + E) live
    text = _jaxpr_text(
        lambda e, w: apsp_mod.hub_factor_sparse(
            csr_from_edges(n, e, w), n_hubs=h), e, w)
    assert f"[{n},{n}]" not in text, "hub factorization allocates (n, n)"

    # stage: the D~ panel sweep — (bm, n) slabs, (C, C) reductions
    B, C = n - 3, 8
    fn = sparse_dbht._panel_fn(h, n, bm, B, C)
    text = _jaxpr_text(
        fn, jnp.zeros((h, n)), jnp.zeros((2 * E,), jnp.int32),
        jnp.zeros((2 * E,), jnp.int32), jnp.zeros((2 * E,)),
        jnp.zeros((B, 4), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((n,), jnp.int32), 0)
    assert f"[{n},{n}]" not in text, "panel sweep allocates (n, n)"
    assert f"f32[{bm},{n}]" in text          # the panel IS there

    # stage: a per-cluster HAC block at m_pad < n — (m_pad, m_pad) only
    m_pad, e_pad = 64, 32
    cfn = sparse_dbht._cluster_hac_fn(h, m_pad, e_pad, "jnp")
    text = _jaxpr_text(
        cfn, jnp.zeros((h, m_pad)), jnp.ones((m_pad,), bool),
        jnp.zeros((e_pad,), jnp.int32), jnp.zeros((e_pad,), jnp.int32),
        jnp.zeros((e_pad,)), jnp.zeros((m_pad,), jnp.int32),
        jnp.float32(1.0))
    assert f"[{n},{n}]" not in text, "cluster HAC allocates (n, n)"

    # positive control: the dense APSP tail on the same n trips it
    dense_text = _jaxpr_text(
        lambda W: apsp_mod.apsp_hub(W, n_hubs=h),
        jnp.zeros((n, n), jnp.float32))
    assert f"f32[{n},{n}]" in dense_text


def test_topk_kernel_peak_is_one_panel():
    """The streaming kernel's jaxpr holds (bm, n) panels, never (n, n)."""
    n, bm = 256, 64
    X = jax.random.normal(jax.random.PRNGKey(1), (n, 40), jnp.float32)
    from repro.kernels.topk import topk_pearson_jnp
    text = _jaxpr_text(lambda x: topk_pearson_jnp(x, 32, bm=bm), X)
    assert f"[{n},{n}]" not in text
    assert f"f32[{bm},{n}]" in text          # the panel IS there


# ---------------------------------------------------------------------------
# the kernel table: exactness and tie order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,L,k", [(30, 40, 7), (48, 64, 47), (130, 33, 16)])
def test_topk_table_matches_dense_topk(n, L, k):
    """ops.topk (jnp) == lax.top_k of the dense matrix — indices exact
    (including tie order), values bitwise: the scan's panels and the
    dense matrix are one compiled arithmetic (``ref.pearson_rows``)."""
    rng = np.random.default_rng(n)
    X = rng.normal(size=(n, L)).astype(np.float32)
    S = pearson_ref(jnp.asarray(X))
    Sd = jnp.where(jnp.eye(n, dtype=bool), -jnp.inf, S)
    wv, wi = jax.lax.top_k(Sd, k)
    t = knn.topk_pearson(X, k, bm=32)
    np.testing.assert_array_equal(np.asarray(t.indices), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(t.values), np.asarray(wv))
    ts = knn.topk_from_similarity(S, k)
    np.testing.assert_array_equal(np.asarray(ts.indices), np.asarray(wi))


def test_rescore_pools_tie_order_is_index_ascending():
    """Regression (review): rescoring used to break exact-value ties by
    POOL position.  The TopKTable contract is (value desc, index asc);
    duplicated rows + shuffled pools manufacture bitwise ties, and the
    returned rows must honor the ordering.  (Cross-checking indices
    against ``topk_pearson`` bitwise is NOT valid here: the batched
    einsum's gathers round pair values position-dependently by ~1 ulp,
    so only within-table ties are exact.)"""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(7, 24)).astype(np.float32)
    X = np.concatenate([X, X], axis=0)                # exact ties
    n = X.shape[0]
    pools = np.stack([rng.permutation(
        np.delete(np.arange(n), i)) for i in range(n)])   # shuffled, full
    re = knn.rescore_pools(X, pools, 6)
    v, i = np.asarray(re.values), np.asarray(re.indices)
    assert (v[:, :-1] >= v[:, 1:]).all()              # value descending
    ties = v[:, :-1] == v[:, 1:]
    assert ties.any()                                 # the setup worked
    assert (i[:, :-1][ties] < i[:, 1:][ties]).all()   # ties: index asc


def test_sketch_pools_and_rescoring():
    """Sketch pools: seeded-deterministic, self-free; exact rescoring of
    a full-width pool reproduces the exact table."""
    n = 60
    _, X, _ = clustered_similarity(n, k=3, seed=7)
    p1 = project.candidate_pools(X, 16, dim=32, seed=3)
    p2 = project.candidate_pools(X, 16, dim=32, seed=3)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    assert not np.any(np.asarray(p1) ==
                      np.arange(n)[:, None])          # no self-candidates
    full_pool = project.candidate_pools(X, n - 1, dim=32, seed=3)
    re = knn.rescore_pools(X, full_pool, 8)
    exact = knn.topk_pearson(X, 8)
    np.testing.assert_array_equal(np.asarray(re.indices),
                                  np.asarray(exact.indices))
    np.testing.assert_allclose(np.asarray(re.values),
                               np.asarray(exact.values), atol=2e-6)


# ---------------------------------------------------------------------------
# quality: the ARI floor and the §13.4 harness
# ---------------------------------------------------------------------------

def test_ari_floor_at_sim_k_32():
    """ISSUE 5 satellite: on the synthetic regime data, the sim_k=32
    approx path keeps ≥ 0.9 of the dense path's ARI (averaged over
    seeds — single-seed ARI is noisy in both directions)."""
    dense_ari, approx_ari = [], []
    for seed in range(3):
        X, labels = make_dataset(96, 64, 4, noise=0.5, seed=seed)
        d = cluster(X, k=4, config=PipelineConfig.opt())
        a = cluster(X, k=4, config=PipelineConfig.approx(sim_k=32))
        dense_ari.append(ari(labels, d.labels))
        approx_ari.append(ari(labels, a.labels))
    assert np.mean(approx_ari) >= 0.9 * np.mean(dense_ari), \
        (dense_ari, approx_ari)


def test_quality_harness_full_k_is_perfect():
    _, X, _ = clustered_similarity(40, k=3, seed=4)
    rep = quality.compare_to_dense(X, sim_k=39, k=3)
    assert rep["ari"] == 1.0
    assert rep["edge_recall"] == 1.0
    assert rep["edge_sum_ratio"] == pytest.approx(1.0)


def test_counters_surface_in_timings():
    """§13.3 fallback/recall counters: zero pair misses at full K (all
    values come from the table; the ≤4 fallbacks are the end-of-build
    lookups where no uninserted vertex remains), nonzero fallbacks at
    small K, surfaced through cluster(collect_timings=True)."""
    n = 40
    _, X, _ = clustered_similarity(n, k=3, seed=6)
    full = cluster(X, k=3, config=PipelineConfig.approx(sim_k=n - 1),
                   collect_timings=True)
    assert full.timings["sim_pair_misses"] == 0
    assert full.timings["sim_fallbacks"] <= 4
    small = cluster(X, k=3, config=PipelineConfig.approx(sim_k=6),
                    collect_timings=True)
    assert small.timings["sim_fallbacks"] > 0
    assert 0.0 < small.timings["sim_fallback_rate"] <= 1.0
    # batch surface: summed counters
    bs = cluster_batch(np.stack([X, X]), k=3,
                       config=PipelineConfig.approx(sim_k=6),
                       collect_timings=True)
    assert bs.timings["sim_fallbacks"] >= 2 * small.timings["sim_fallbacks"]


def test_sparse_builder_matches_dense_builder_directly():
    """Unit pin under the pipeline: build_tmfg_sparse at full K equals
    build_tmfg(method='lazy') field for field, and its edge weights are
    the dense matrix's gathers."""
    from repro.core.tmfg import build_tmfg
    n = 36
    _, X, _ = clustered_similarity(n, k=3, seed=8)
    S = pearson_ref(jnp.asarray(X, jnp.float32))
    dense = build_tmfg(S, method="lazy", topk=0)
    table = knn.topk_pearson(X, n - 1)
    sp, w, counters = build_tmfg_sparse(
        table, Xn=standardize_rows(jnp.asarray(X, jnp.float32)))
    for f in ("clique", "edges", "faces", "insert_order", "bubble_verts",
              "bubble_parent", "bubble_tri", "home_bubble"):
        np.testing.assert_array_equal(np.asarray(getattr(dense, f)),
                                      np.asarray(getattr(sp, f)), err_msg=f)
    e = np.asarray(sp.edges)
    np.testing.assert_array_equal(np.asarray(S)[e[:, 0], e[:, 1]],
                                  np.asarray(w))
    assert int(counters.pair_misses) == 0


# ---------------------------------------------------------------------------
# wiring: config, keys, fused rejection, stream
# ---------------------------------------------------------------------------

class TestApproxWiring:
    def test_approx_constructor_and_validation(self):
        cfg = PipelineConfig.approx(sim_k=64)
        assert (cfg.similarity, cfg.sim_k) == ("topk", 64)
        assert cfg.method == "lazy"              # OPT base
        assert PipelineConfig.approx(sim_k=8, backend="jnp").backend == "jnp"
        with pytest.raises(ValueError, match="sim_k"):
            PipelineConfig(similarity="topk")    # needs sim_k >= 1
        with pytest.raises(ValueError, match="sim_k"):
            PipelineConfig(sim_k=8)              # dense ignores it: reject
        with pytest.raises(ValueError, match="similarity"):
            PipelineConfig(similarity="sparse")
        with pytest.raises(ValueError, match="approx"):
            PipelineConfig.approx(similarity="dense")

    def test_content_key_includes_similarity_fields(self):
        """A topk result is a different answer than a dense one: the
        content-cache key must split on similarity AND sim_k."""
        dense = PipelineConfig.opt()
        a64 = PipelineConfig.approx(sim_k=64)
        a32 = PipelineConfig.approx(sim_k=32)
        assert dense.content_key() != a64.content_key()
        assert a64.content_key() != a32.content_key()
        # dbht_impl stays excluded on the approx configs too
        assert a64.content_key() == \
            a64.replace(dbht_impl="host").content_key()

    def test_scheduler_keys_split_dense_from_topk(self):
        from repro.stream.scheduler import MicroBatcher
        mb = MicroBatcher(max_batch=4)
        S, _, _ = clustered_similarity(24, k=2, seed=3)
        r_dense = mb.submit(S, k=2, config=PipelineConfig.opt())
        r_topk = mb.submit(S, k=2, config=PipelineConfig.approx(sim_k=8))
        assert r_dense.key != r_topk.key          # different batches
        assert r_dense.config != r_topk.config    # different cache keys
        done = mb.flush()
        assert all(r.done for r in done)
        assert mb.batches_run == 2

    def test_fused_path_accepts_topk_end_to_end(self):
        """ISSUE 9 acceptance: the §13.5 staged-only boundary is
        retired — run_pipeline_device takes PipelineConfig.approx()
        and the fused default equals the staged path bitwise."""
        _, X, _ = clustered_similarity(24, k=2, seed=1)
        cfg = PipelineConfig.approx(sim_k=8)
        out = run_pipeline_device(np.asarray(X, np.float32), cfg,
                                  is_similarity=False)
        assert out.linkage.shape == (23, 4)
        fz = cluster(X, k=2, config=cfg, fused=True)
        st = cluster(X, k=2, config=cfg, fused=False)
        np.testing.assert_array_equal(fz.labels, st.labels)
        np.testing.assert_array_equal(fz.linkage, st.linkage)
        bf = cluster_batch(X[None], k=2, config=cfg, fused=True)
        np.testing.assert_array_equal(bf.labels[0], st.labels)

    def test_reuse_tmfg_needs_materialized_similarity(self):
        S, X, _ = clustered_similarity(24, k=2, seed=2)
        cfg = PipelineConfig.approx(sim_k=23)
        full = cluster(S=S, k=2, config=cfg)
        with pytest.raises(ValueError, match="reuse_tmfg"):
            cluster(X, k=2, config=cfg, reuse_tmfg=full.tmfg)
        warm = cluster(S=S, k=2, config=cfg, reuse_tmfg=full.tmfg)
        np.testing.assert_array_equal(warm.labels, full.labels)
        assert warm.reused_tmfg

    def test_stream_service_runs_approx_config(self):
        """The streaming façade with an approx config: exact at full K
        (scheduler + content cache key on the new fields throughout)."""
        from repro.stream import ClusterService
        n, w = 24, 16
        rng = np.random.default_rng(0)
        svc = ClusterService(n, w, k=2,
                             config=PipelineConfig.approx(sim_k=n - 1))
        for _ in range(w):
            svc.tick(rng.normal(size=n).astype(np.float32))
        res = svc.recluster()
        want = cluster(S=svc.similarity(), k=2,
                       config=PipelineConfig.approx(sim_k=n - 1))
        np.testing.assert_array_equal(res.labels, want.labels)
