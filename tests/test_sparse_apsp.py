"""Sparse APSP + DBHT tail (DESIGN.md §14): the parity/property layer.

ISSUE 6's acceptance pins:
  * kernel parity — ``sparse_apsp_sources`` equals a numpy f32
    Bellman-Ford oracle bitwise, and the kernel backends agree bitwise;
  * hub parity — ``apsp_sparse(n_hubs=h)`` is BITWISE ``apsp_hub``
    at the same hub count (both left-fold one edge extension per round
    with exact-min combining), and stays within the hub approximation's
    tolerance of ``apsp_exact``;
  * DBHT parity — ``dbht(apsp_method="sparse", impl="device")`` equals
    the densified host oracle (§14.5) on every field, across variants,
    batches, and the degenerate n=4/5 graphs;
  * the tree fallback (§14.4) — structural properties when clusters
    exceed ``hac_max``;
  * the §14.2 hub-threshold regression — ``apsp(method="hub")`` runs
    exact below ``HUB_MIN_N`` (the BENCH_5 small-n fix).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import clustered_similarity, random_symmetric, regime_batch, \
    tmfg_f32
import repro.core.apsp as A
import repro.core.dbht as D
from repro.core import sparse_dbht
from repro.core.ari import ari
from repro.core.config import PipelineConfig
from repro.core.pipeline import VARIANTS, cluster, cluster_batch, \
    resolve_variant
from repro.kernels.sparse_apsp import csr_from_edges, sparse_apsp_sources


def _tmfg_lengths(n, seed=0, k=3, variant="opt"):
    """A TMFG and its dense length matrix W (the sparse tail's input)."""
    S, _, _ = clustered_similarity(n, k=k, seed=seed)
    method, prefix, topk, _ = resolve_variant(variant)
    tm = tmfg_f32(S, method=method, prefix=prefix, topk=topk)
    W = A.edge_lengths(n, jnp.asarray(tm.edges),
                       jnp.asarray(S, jnp.float32))
    return tm, S, np.asarray(W)


def _np_bellman_ford(W, sources, rounds):
    """f32 numpy mirror of ``sparse_apsp_sources``: per round, one edge
    extension D[s,r] <- min(D[s,r], min_e D[s,col[e]] + w[e]) with
    order-independent (min) combining."""
    n = W.shape[0]
    iu, ju = np.nonzero(np.isfinite(W) & ~np.eye(n, dtype=bool))
    rows = np.concatenate([iu])
    cols = np.concatenate([ju])
    vals = W[rows, cols].astype(np.float32)
    Dm = np.full((len(sources), n), np.inf, np.float32)
    Dm[np.arange(len(sources)), sources] = 0.0
    for _ in range(rounds):
        cand = Dm[:, cols] + vals[None, :]
        new = Dm.copy()
        np.minimum.at(new.T, rows, cand.T)
        if np.array_equal(new, Dm):
            break
        Dm = new
    return Dm


# ---------------------------------------------------------------------------
# kernel parity: the relaxation itself
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [16, 48, 96])
def test_sparse_sources_match_numpy_bellman_ford(n):
    _, _, W = _tmfg_lengths(n, seed=n)
    graph = A.csr_from_dense(W)
    src = np.arange(0, n, 3, dtype=np.int32)
    got = np.asarray(sparse_apsp_sources(graph, jnp.asarray(src), rounds=32))
    want = _np_bellman_ford(W, src, 32)
    np.testing.assert_array_equal(got, want)


def test_sparse_backends_agree_bitwise():
    """jnp / interpret / auto produce identical bits: the round has one
    XLA form and takes ``backend`` only for the ops calling convention
    (§14.1)."""
    _, _, W = _tmfg_lengths(40, seed=7)
    graph = A.csr_from_dense(W)
    src = jnp.arange(8, dtype=jnp.int32)
    ref = np.asarray(sparse_apsp_sources(graph, src, backend="jnp"))
    for backend in ("interpret", "auto"):
        got = np.asarray(sparse_apsp_sources(graph, src, backend=backend))
        np.testing.assert_array_equal(got, ref, err_msg=backend)


def test_sparse_sources_converge_early():
    """The while_loop early-exit: extra rounds are no-ops once every
    shortest path is found (TMFG diameters are tiny)."""
    _, _, W = _tmfg_lengths(32, seed=3)
    graph = A.csr_from_dense(W)
    src = jnp.arange(6, dtype=jnp.int32)
    d32 = np.asarray(sparse_apsp_sources(graph, src, rounds=32))
    d99 = np.asarray(sparse_apsp_sources(graph, src, rounds=99))
    np.testing.assert_array_equal(d32, d99)


# ---------------------------------------------------------------------------
# hub parity: sparse == dense hub program, tolerance vs exact
# ---------------------------------------------------------------------------

def test_apsp_sparse_bitwise_matches_apsp_hub():
    """Both programs left-fold one edge extension per round from the
    same D0 with exact-min combining and share the composition
    epilogue, so the densified sparse estimate is BITWISE the dense
    hub one.  (Per-variant TMFG topologies are exercised by the seeded
    sweep in tests/test_property.py, ISSUE 8; this keeps one fast
    in-file smoke.)"""
    n = 48
    _, _, W = _tmfg_lengths(n, seed=11, variant="opt")
    for h in (4, 8):
        got = np.asarray(A.apsp_sparse(W, n_hubs=h))
        want = np.asarray(A.apsp_hub(jnp.asarray(W), n_hubs=h))
        np.testing.assert_array_equal(got, want, err_msg=f"h={h}")


def test_apsp_sparse_default_hubs_matches_hub():
    _, _, W = _tmfg_lengths(64, seed=2)
    np.testing.assert_array_equal(np.asarray(A.apsp_sparse(W)),
                                  np.asarray(A.apsp_hub(jnp.asarray(W))))


@pytest.mark.parametrize("n", [4, 5, 48])
def test_apsp_sparse_vs_exact_tolerance(n):
    """The hub estimate is an upper bound; at full hub count it is
    exact, and at the default count it stays within the documented
    approximation band on TMFG graphs."""
    _, _, W = _tmfg_lengths(n, seed=n, k=2)
    exact = np.asarray(A.apsp_exact(jnp.asarray(W)))
    sp = np.asarray(A.apsp_sparse(W, n_hubs=n))    # every vertex a hub
    np.testing.assert_allclose(sp, exact, rtol=1e-6, atol=1e-6)
    sp_def = np.asarray(A.apsp_sparse(W))
    assert (sp_def >= exact - 1e-6).all()          # upper bound
    # and a tight one: the mean overshoot is a small fraction of the
    # mean distance (the hub-tolerance band; bitwise == apsp_hub above)
    assert np.mean(sp_def - exact) <= 0.2 * max(np.mean(exact), 1e-6)


def test_apsp_dispatcher_sparse_method():
    _, _, W = _tmfg_lengths(32, seed=5)
    np.testing.assert_array_equal(
        np.asarray(A.apsp(jnp.asarray(W), method="sparse", n_hubs=6)),
        np.asarray(A.apsp_sparse(W, n_hubs=6)))
    with pytest.raises(ValueError, match="APSP method"):
        A.apsp(jnp.asarray(W), method="bogus")


# ---------------------------------------------------------------------------
# the §14.2 hub-threshold regression (BENCH_5 small-n fix)
# ---------------------------------------------------------------------------

def test_hub_dispatch_falls_back_to_exact_below_threshold():
    """``apsp(method="hub")`` with n < HUB_MIN_N runs the exact program
    (bitwise): the hub program's compile+dispatch overhead dominated at
    small n (BENCH_5.json speedups 0.15-0.87).  Direct ``apsp_hub``
    calls still force the hub program shape."""
    n = 64
    assert n < A.HUB_MIN_N
    _, _, W = _tmfg_lengths(n, seed=13)
    Wj = jnp.asarray(W)
    np.testing.assert_array_equal(
        np.asarray(A.apsp(Wj, method="hub")),
        np.asarray(A.apsp_exact(Wj)))
    # the forced hub program differs from exact on this graph (the
    # approximation is real), so the dispatcher demonstrably switched
    assert not np.array_equal(np.asarray(A.apsp_hub(Wj, n_hubs=4)),
                              np.asarray(A.apsp_exact(Wj)))


def test_hub_dispatch_uses_hub_program_at_threshold():
    n = A.HUB_MIN_N
    rng = np.random.default_rng(0)
    # synthetic sparse lengths: ring + chords (no TMFG build at n=200)
    W = np.full((n, n), np.inf, np.float32)
    i = np.arange(n)
    ring = rng.uniform(0.1, 2.0, n).astype(np.float32)
    W[i, (i + 1) % n] = W[(i + 1) % n, i] = ring
    for _ in range(3 * n):
        a, b = rng.integers(0, n, 2)
        if a != b:
            W[a, b] = W[b, a] = np.float32(rng.uniform(0.1, 2.0))
    np.fill_diagonal(W, 0.0)
    Wj = jnp.asarray(W)
    np.testing.assert_array_equal(
        np.asarray(A.apsp(Wj, method="hub", n_hubs=8)),
        np.asarray(A.apsp_hub(Wj, n_hubs=8)))


def test_hub_count_shared_by_both_paths():
    assert A.hub_count(100) == 10
    assert A.hub_count(9) == 4            # floor at 4
    assert A.hub_count(3) == 3            # clamp to n
    assert A.hub_count(100, n_hubs=7) == 7


# ---------------------------------------------------------------------------
# DBHT parity: sparse tail vs densified host oracle (§14.5)
# ---------------------------------------------------------------------------

def _assert_dbht_equal_no_apsp(rh, rd, msg=""):
    """Field-for-field equality except ``apsp``: the sparse result holds
    the (h, n) hub factor where dense impls hold (n, n)."""
    np.testing.assert_array_equal(rh.direction, rd.direction, err_msg=msg)
    np.testing.assert_array_equal(rh.converging, rd.converging, err_msg=msg)
    np.testing.assert_array_equal(rh.cluster_of, rd.cluster_of, err_msg=msg)
    np.testing.assert_array_equal(rh.bubble_of, rd.bubble_of, err_msg=msg)
    np.testing.assert_array_equal(rh.linkage, rd.linkage, err_msg=msg)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_sparse_dbht_matches_host_oracle_all_variants(variant):
    """The tentpole pin: the host oracle fed the DENSIFIED factor
    (bitwise the blocked compositions, §14.3) must reproduce the sparse
    tail's every output on each variant's TMFG topology."""
    n = 48
    S, _, _ = clustered_similarity(n, k=4, seed=5)
    method, prefix, topk, _ = resolve_variant(variant)
    tm = tmfg_f32(S, method=method, prefix=prefix, topk=topk)
    rd = D.dbht(S, tm, apsp_method="sparse", impl="device")
    rh = D.dbht(S, tm, apsp_method="sparse", impl="host")
    _assert_dbht_equal_no_apsp(rh, rd, msg=variant)
    for kk in (2, 4, 7):
        np.testing.assert_array_equal(rh.labels(kk), rd.labels(kk),
                                      err_msg=f"{variant} k={kk}")
    # the sparse result carries the factor, not the matrix
    h = A.hub_count(n)
    assert rd.apsp.shape == (h, n)
    assert rd.hubs.shape == (h,)
    assert rh.apsp.shape == (n, n)        # the oracle densified


@pytest.mark.parametrize("n", [4, 5])
def test_sparse_dbht_degenerate_small_n(n):
    S, _, _ = clustered_similarity(n, k=2, L=24, seed=n)
    tm = tmfg_f32(S)
    rd = D.dbht(S, tm, apsp_method="sparse", impl="device")
    rh = D.dbht(S, tm, apsp_method="sparse", impl="host")
    _assert_dbht_equal_no_apsp(rh, rd, msg=f"n={n}")
    assert rd.linkage.shape == (n - 1, 4)


def test_sparse_dbht_random_symmetric_property():
    """Adversarial inputs (no regime structure): the sparse tail still
    matches its oracle.  Scaled inside (-1, 1): values clipped AT ±1
    manufacture exact zero-length ties across clusters, the one
    documented emission-order divergence (module docstring §14.5)."""
    for seed in range(4):
        n = 20 + 4 * seed
        S = random_symmetric(n, seed)
        S = S / (np.abs(S).max() + 1.0)
        tm = tmfg_f32(S)
        rd = D.dbht(S, tm, apsp_method="sparse", impl="device")
        rh = D.dbht(S, tm, apsp_method="sparse", impl="host")
        _assert_dbht_equal_no_apsp(rh, rd, msg=f"seed={seed}")


def test_sparse_dbht_edge_weights_equals_from_S():
    """The no-S entry (§14.3): passing the per-edge similarities
    instead of S reproduces the from-S result bitwise (same gathers)."""
    n = 40
    S, _, _ = clustered_similarity(n, k=3, seed=9)
    tm = tmfg_f32(S)
    e = np.asarray(tm.edges)
    w = np.asarray(S, np.float32)[e[:, 0], e[:, 1]]
    r1 = sparse_dbht.dbht_sparse(S, tm)
    r2 = sparse_dbht.dbht_sparse(None, tm, edge_weights=w)
    _assert_dbht_equal_no_apsp(r1, r2)
    np.testing.assert_array_equal(r1.apsp, r2.apsp)
    with pytest.raises(ValueError, match="edge_weights"):
        sparse_dbht.dbht_sparse(None, tm)
    with pytest.raises(ValueError, match="impl"):
        sparse_dbht.dbht_sparse(S, tm, impl="gpu")


# ---------------------------------------------------------------------------
# pipeline wiring: staged routing, fused rejection, batches
# ---------------------------------------------------------------------------

def test_cluster_sparse_config_staged_parity():
    cfg = PipelineConfig(apsp_method="sparse", topk=0)
    S, _, _ = clustered_similarity(64, k=4, seed=1)
    rd = cluster(S=S, config=cfg)
    rh = cluster(S=S, config=cfg.replace(dbht_impl="host"))
    np.testing.assert_array_equal(rd.labels, rh.labels)
    np.testing.assert_array_equal(rd.linkage, rh.linkage)


def test_cluster_approx_sparse_never_needs_S():
    """similarity='topk' + apsp='sparse': the end-to-end no-(n, n)
    configuration.  At full K it equals the from-S sparse run bitwise
    (same TMFG, same edge values through the w_edges path)."""
    n = 48
    _, X, _ = clustered_similarity(n, k=3, seed=4)
    cfg = PipelineConfig.approx(sim_k=n - 1, apsp_method="sparse")
    ax = cluster(X, config=cfg)
    # reference: dense device similarity from the same X, sparse tail
    ref = cluster(X, config=PipelineConfig(apsp_method="sparse", topk=0,
                                           method="lazy"), fused=False)
    np.testing.assert_array_equal(ax.labels, ref.labels)
    np.testing.assert_array_equal(ax.linkage, ref.linkage)
    assert ax.dbht.hubs is not None


def test_fused_accepts_sparse_apsp_end_to_end():
    """ISSUE 9 acceptance: the §14.6 boundary is retired — the sparse
    APSP+DBHT tail lowers into the fused program (DESIGN.md §17) and
    matches the staged host-orchestrated tail."""
    from repro.core.pipeline import run_pipeline_device
    cfg = PipelineConfig(apsp_method="sparse", topk=0)
    S, X, _ = clustered_similarity(24, k=2, seed=2)
    out = run_pipeline_device(np.asarray(S, np.float32), cfg,
                              is_similarity=True)
    assert out.hubs is not None and out.apsp.shape[0] < 24
    fz = cluster(X, k=2, config=cfg, fused=True)
    st = cluster(X, k=2, config=cfg, fused=False)
    np.testing.assert_array_equal(fz.labels, st.labels)
    np.testing.assert_array_equal(fz.linkage, st.linkage)
    bf = cluster_batch(X[None], k=2, config=cfg, fused=True)
    np.testing.assert_array_equal(bf.labels[0], st.labels)


@pytest.mark.parametrize("from_x", [False, True])
def test_cluster_batch_sparse_parity(from_x):
    """Batched sparse tail: each entry equals the single-matrix sparse
    run AND the host oracle, with and without a materialized S."""
    n, B = 40, 2
    Xs = regime_batch(B, n, L=32, stack=False)
    if from_x:
        cfg = PipelineConfig.approx(sim_k=n - 1, apsp_method="sparse")
        inp = dict(X=np.stack(Xs))
    else:
        cfg = PipelineConfig(apsp_method="sparse", topk=0)
        inp = dict(S=np.stack([np.corrcoef(x).astype(np.float32)
                               for x in Xs]))
    bres = cluster_batch(k=3, config=cfg, **inp)
    bhost = cluster_batch(k=3, config=cfg.replace(dbht_impl="host"), **inp)
    for b in range(B):
        single = cluster(Xs[b], k=3, config=cfg) if from_x else \
            cluster(S=inp["S"][b], k=3, config=cfg)
        np.testing.assert_array_equal(single.labels, bres.labels[b])
        np.testing.assert_array_equal(single.linkage, bres[b].linkage)
        np.testing.assert_array_equal(bres.labels[b], bhost.labels[b])
        np.testing.assert_array_equal(bres[b].linkage, bhost[b].linkage)


def test_content_key_splits_sparse():
    dense = PipelineConfig.opt()
    sp = PipelineConfig.opt().replace(apsp_method="sparse")
    assert dense.content_key() != sp.content_key()


# ---------------------------------------------------------------------------
# the §14.4 tree fallback for oversized clusters
# ---------------------------------------------------------------------------

def test_tree_mode_structural_properties():
    """Forcing ``hac_max=1`` sends every multi-member cluster through
    the bubble-tree approximation: the linkage must still be a valid
    full dendrogram with monotone per-cluster heights, and cutting at
    the converging-bubble count must reproduce the flat partition."""
    n = 64
    S, _, _ = clustered_similarity(n, k=4, seed=6)
    tm = tmfg_f32(S)
    rd = sparse_dbht.dbht_sparse(S, tm, hac_max=1)
    Z = rd.linkage
    assert Z.shape == (n - 1, 4)
    # every internal id referenced exactly once, all leaves present
    refs = np.concatenate([Z[:, 0], Z[:, 1]]).astype(np.int64)
    assert sorted(refs.tolist()) == list(range(2 * n - 2))
    assert Z[-1, 3] == n                    # root covers every vertex
    # flat cut at the cluster count == the flow partition
    C = len(rd.converging)
    if C > 1:
        labels = rd.labels(C)
        assert ari(labels, rd.cluster_of) == 1.0
    # exact mode on the same input agrees on the flat partition too
    re = sparse_dbht.dbht_sparse(S, tm)
    np.testing.assert_array_equal(rd.cluster_of, re.cluster_of)
    np.testing.assert_array_equal(rd.bubble_of, re.bubble_of)
    if C > 1:
        assert ari(rd.labels(C), re.labels(C)) == 1.0


def test_tree_mode_close_to_exact_dendrogram():
    """The approximation's quality floor: flat partitions from the tree
    fallback stay close to the exact nested HAC across cut levels."""
    n = 96
    S, _, labels_true = clustered_similarity(n, k=4, seed=8)
    tm = tmfg_f32(S)
    exact = sparse_dbht.dbht_sparse(S, tm)
    tree = sparse_dbht.dbht_sparse(S, tm, hac_max=1)
    for kk in (2, 4):
        a = ari(exact.labels(kk), tree.labels(kk))
        assert a >= 0.6, f"k={kk}: tree/exact ARI {a}"
    # and it still recovers the planted regimes about as well
    a_exact = ari(labels_true, exact.labels(4))
    a_tree = ari(labels_true, tree.labels(4))
    assert a_tree >= 0.8 * a_exact
