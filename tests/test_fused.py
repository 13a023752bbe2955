"""The fused one-jit pipeline and PipelineConfig (DESIGN.md §12).

Pins of ISSUE 4's acceptance criteria:
  * fused/staged parity — labels AND linkage identical, batched and
    unbatched, down to degenerate n=4/n=5 (the per-variant enumeration
    moved to the seeded sweep in tests/test_property.py, ISSUE 8);
  * the recompile guard — a sequence of ``cluster``/``cluster_batch``
    calls with one ``PipelineConfig`` and shape compiles each device
    program exactly once (JAX lowering counters);
  * the config object — hashability, variant constructors, the resolve
    precedence shared with the kwarg shim, and the content-key schema
    (``dbht_impl`` excluded);
  * the bounded executable cache — eviction at the bound, explicit
    ``clear()``.
"""

import numpy as np
import pytest

from conftest import clustered_similarity
from repro.core import jitcache
from repro.core.config import PipelineConfig
from repro.core.pipeline import (VARIANTS, cluster, cluster_batch,
                                 run_pipeline_device)
from repro.data.timeseries import make_dataset
from repro.obs import trace as obs_trace


def _assert_linkage_equal(a, b, msg=""):
    """Merge structure (ids, sizes) exact; heights to fp tolerance —
    the fused program's cross-stage XLA fusion may shift float values
    by ulps (DESIGN.md §12.2), which must never move a merge but may
    nudge a height."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_array_equal(a[:, [0, 1, 3]], b[:, [0, 1, 3]],
                                  err_msg=msg)
    np.testing.assert_allclose(a[:, 2], b[:, 2], rtol=1e-5, atol=1e-5,
                               err_msg=msg)


def _assert_result_equal(a, b, msg=""):
    np.testing.assert_array_equal(a.labels, b.labels, err_msg=msg)
    _assert_linkage_equal(a.linkage, b.linkage, msg=msg)
    assert a.edge_sum == pytest.approx(b.edge_sum, rel=1e-6), msg


# ---------------------------------------------------------------------------
# fused/staged parity (the §12.2 contract)
# ---------------------------------------------------------------------------

def test_fused_matches_staged_smoke():
    """One fast smoke of the §12.2 contract (from S and from X, plus a
    batch) on the default variant.  The per-variant coverage this file
    used to hand-enumerate lives in tests/test_property.py now: the
    seeded random-config sweep draws (n, B, k, variant) tuples and
    pins the same parity, one regression seed per variant."""
    S, X, _ = clustered_similarity(48, k=3, seed=5)
    cfg = PipelineConfig.opt()
    for kwargs in (dict(S=S), dict(X=X)):
        f = cluster(k=3, config=cfg, fused=True, **kwargs)
        s = cluster(k=3, config=cfg, fused=False, **kwargs)
        _assert_result_equal(f, s, msg=f"opt {sorted(kwargs)}")
    Xs = [make_dataset(48, 40, 3, noise=0.7, seed=s)[0] for s in range(2)]
    bf = cluster_batch(np.stack(Xs), k=3, config=cfg, fused=True)
    bs = cluster_batch(np.stack(Xs), k=3, config=cfg, fused=False)
    for b in range(2):
        _assert_result_equal(bf[b], bs[b], msg=f"opt entry {b}")
        single = cluster(Xs[b], k=3, config=cfg)
        np.testing.assert_array_equal(single.labels, bf.labels[b])
        _assert_linkage_equal(single.linkage, bf[b].linkage)


@pytest.mark.parametrize("n", [4, 5])
@pytest.mark.parametrize("variant", ["par-200", "opt"])
def test_fused_matches_staged_degenerate_small_n(n, variant):
    """The smallest legal graphs (n=4: the seed clique only; n=5: two
    bubbles, one tree edge) run fused and agree with staged exactly."""
    X, _ = make_dataset(n, 24, 2, noise=0.7, seed=n)
    f = cluster(X, variant=variant, fused=True)
    s = cluster(X, variant=variant, fused=False)
    _assert_result_equal(f, s, msg=f"n={n} {variant}")
    bf = cluster_batch(np.stack([X, X]), variant=variant, fused=True)
    np.testing.assert_array_equal(bf.labels[0], f.labels)


def test_fused_limit_drops_pad_entries():
    """The scheduler's bucket-pad contract on the fused path: limit
    slices the transfer and the materialized prefix matches singles."""
    Xs = [make_dataset(32, 24, 2, noise=0.7, seed=s)[0] for s in range(4)]
    bres = cluster_batch(np.stack(Xs), k=2, variant="opt", limit=3)
    assert len(bres) == 3
    for b in range(3):
        single = cluster(Xs[b], k=2, variant="opt")
        np.testing.assert_array_equal(single.labels, bres[b].labels)


def test_fused_timings_total_only_staged_per_stage():
    """§12.4: the fused path reports total only; the staged path keeps
    the per-stage keys (it is the timing/debug mode)."""
    X, _ = make_dataset(32, 24, 2, noise=0.7, seed=0)
    f = cluster(X, k=2, variant="opt", collect_timings=True)
    assert set(f.timings) == {"total"} and f.timings["total"] >= 0
    s = cluster(X, k=2, variant="opt", fused=False, collect_timings=True)
    assert set(s.timings) == {"similarity", "tmfg", "dbht+apsp", "total"}
    bf = cluster_batch(np.stack([X, X]), k=2, variant="opt",
                       collect_timings=True)
    assert set(bf.timings) == {"total"}
    assert all(set(r.timings) == {"total"} for r in bf)


def test_fused_rejected_for_host_impl_and_reuse_tmfg():
    """fused=True requires the device impl and no warm-start splice;
    the defaults silently fall back to staged for both."""
    S, _, _ = clustered_similarity(32, k=2, seed=1)
    with pytest.raises(ValueError, match="fused"):
        cluster(S=S, dbht_impl="host", fused=True)
    full = cluster(S=S, k=2, variant="opt")
    with pytest.raises(ValueError, match="fused"):
        cluster(S=S, k=2, variant="opt", reuse_tmfg=full.tmfg, fused=True)
    # default fused=None falls back to the staged path for both
    warm = cluster(S=S, k=2, variant="opt", reuse_tmfg=full.tmfg)
    assert warm.tmfg is full.tmfg
    host = cluster(S=S, k=2, variant="opt", dbht_impl="host")
    np.testing.assert_array_equal(host.labels, full.labels)


# ---------------------------------------------------------------------------
# the recompile guard (§12.3)
# ---------------------------------------------------------------------------

def test_identical_config_and_shape_compiles_once():
    """ISSUE 4 satellite: replaying one (PipelineConfig, shape) through
    cluster() and cluster_batch() lowers each device program exactly
    once — later calls hit the cached executables, producing ZERO new
    XLA compilations (counted by the repo's recompile watchdog)."""
    cfg = PipelineConfig.opt()
    X, _ = make_dataset(32, 24, 2, noise=0.7, seed=3)
    Xb = np.stack([make_dataset(32, 24, 2, noise=0.7, seed=s)[0]
                   for s in range(2)])

    jitcache.clear()                            # force a cold start
    cluster(X, k=2, config=cfg)                 # warm: compiles the programs
    cluster_batch(Xb, k=2, config=cfg)
    grew = jitcache.size()
    assert grew >= 2                            # single + batched executables

    with obs_trace.watch_recompiles() as w:
        for _ in range(3):
            r1 = cluster(X, k=2, config=cfg)
            rb = cluster_batch(Xb, k=2, config=cfg)
    assert w.count == 0, f"recompiled {w.count} programs on replay"
    assert jitcache.size() == grew              # no new executables either
    np.testing.assert_array_equal(rb.labels[0], cluster(Xb[0], config=cfg,
                                                        k=2).labels)
    assert r1.labels.shape == (32,)


def test_jitcache_bounded_and_clearable():
    """The executable cache evicts at the bound (LRU-first) and clear()
    empties it; stats track hits/misses/evictions."""
    prev = jitcache.set_maxsize(2)
    try:
        jitcache.clear()
        builds = []
        for key in ("a", "b", "c"):
            jitcache.cached(("test", key), lambda key=key: builds.append(key))
        assert jitcache.size() == 2
        assert ("test", "a") not in jitcache.keys()      # LRU evicted
        jitcache.cached(("test", "b"), lambda: builds.append("b2"))
        assert builds == ["a", "b", "c"]                 # "b" was a hit
        jitcache.clear()
        assert jitcache.size() == 0
        st = jitcache.stats()
        assert st["evictions"] >= 1 and st["misses"] >= 3
    finally:
        jitcache.set_maxsize(prev)
        jitcache.clear()


# ---------------------------------------------------------------------------
# PipelineConfig (§12.1)
# ---------------------------------------------------------------------------

class TestPipelineConfig:
    def test_hashable_frozen_and_variant_constructors(self):
        cfg = PipelineConfig.opt()
        assert cfg == PipelineConfig.variant("opt")
        assert hash(cfg) == hash(PipelineConfig.variant("opt"))
        assert {cfg: 1}[PipelineConfig.opt()] == 1       # usable as a key
        with pytest.raises(Exception):                   # frozen
            cfg.method = "corr"
        for name, fields in VARIANTS.items():
            c = PipelineConfig.variant(name)
            for f, v in fields.items():
                assert getattr(c, f) == v, (name, f)
        assert PipelineConfig.par(200) == PipelineConfig.variant("par-200")
        assert PipelineConfig.heap().apsp_method == "exact"
        assert PipelineConfig.corr().method == "corr"

    def test_resolve_matches_kwarg_shim_precedence(self):
        """The named variant overrides the fields it defines; caller
        kwargs fill the rest — byte-identical to the old
        resolve_variant behavior (pinned against it)."""
        from repro.core.pipeline import resolve_variant

        cfg = PipelineConfig.resolve("opt", apsp_method="exact",
                                     backend="jnp")
        assert cfg.apsp_method == "hub"          # variant wins
        assert cfg.backend == "jnp"              # kwarg fills the rest
        for v in VARIANTS:
            m, p, t, a = resolve_variant(v)
            c = PipelineConfig.resolve(v)
            assert (c.method, c.prefix, c.topk, c.apsp_method) == (m, p, t, a)

    def test_resolve_config_wins_and_conflicts_rejected(self):
        cfg = PipelineConfig.heap()
        assert PipelineConfig.resolve(None, cfg) is cfg
        with pytest.raises(ValueError, match="conflicts"):
            PipelineConfig.resolve("opt", cfg)
        with pytest.raises(ValueError, match="defines"):
            PipelineConfig.variant("opt", apsp_method="exact")

    def test_config_plus_loose_kwarg_rejected_not_dropped(self):
        """Regression (review): cluster(config=cfg, dbht_impl="host")
        must raise, not silently run the fused device path the user
        explicitly asked to avoid."""
        S, _, _ = clustered_similarity(24, k=2, seed=4)
        cfg = PipelineConfig.opt()
        with pytest.raises(ValueError, match="conflicts"):
            cluster(S=S, config=cfg, dbht_impl="host")
        with pytest.raises(ValueError, match="conflicts"):
            cluster_batch(S=S[None], config=cfg, backend="jnp")
        # the escape hatch the error message points at
        host = cluster(S=S, k=2, config=cfg.replace(dbht_impl="host"))
        np.testing.assert_array_equal(
            host.labels, cluster(S=S, k=2, config=cfg).labels)
        # the lower layers enforce the same contract (impl is dbht()'s
        # one documented override; the APSP knobs are not)
        import repro.core.dbht as dbht_mod
        from repro.core import build_tmfg
        tm = build_tmfg(np.asarray(S, np.float32))
        with pytest.raises(ValueError, match="conflicts"):
            dbht_mod.dbht(S, tm, apsp_method="exact", config=cfg)
        res = dbht_mod.dbht(S, tm, config=cfg, impl="host")  # allowed
        assert res.linkage.shape == (S.shape[0] - 1, 4)

    def test_validation(self):
        with pytest.raises(ValueError, match="method"):
            PipelineConfig(method="quantum")
        with pytest.raises(ValueError, match="APSP"):
            PipelineConfig(apsp_method="dijkstra")
        with pytest.raises(ValueError, match="impl"):
            PipelineConfig(dbht_impl="gpu")
        with pytest.raises(ValueError, match="backend"):
            PipelineConfig(backend="palas")       # the classic typo

    def test_content_key_excludes_dbht_impl(self):
        """dbht_impl selects an execution strategy, not semantics
        (DESIGN.md §11.4): the content-cache key must be shared across
        impls while every semantic field splits it."""
        a = PipelineConfig.opt()
        assert a.content_key() == a.replace(dbht_impl="host").content_key()
        assert a.content_key() != a.replace(backend="jnp").content_key()
        assert a.content_key() != a.replace(apsp_rounds=8).content_key()
        assert a.content_key() != PipelineConfig.heap().content_key()

    def test_apsp_hubs_rounds_flow_through(self):
        """The config's APSP knobs reach the hub-APSP stage: fewer
        rounds/hubs change the (approximate) distances but fused and
        staged still agree with each other."""
        S, _, _ = clustered_similarity(48, k=3, seed=7)
        cfg = PipelineConfig(apsp_method="hub", apsp_hubs=3, apsp_rounds=2)
        f = cluster(S=S, k=3, config=cfg, fused=True)
        s = cluster(S=S, k=3, config=cfg, fused=False)
        _assert_result_equal(f, s)


# ---------------------------------------------------------------------------
# run_pipeline_device (§12.2)
# ---------------------------------------------------------------------------

def test_run_pipeline_device_outputs_stay_on_device():
    """The program returns device arrays (no implicit transfer) and the
    square-input heuristic routes S vs X correctly."""
    import jax

    S, X, _ = clustered_similarity(40, k=3, seed=2)
    cfg = PipelineConfig.opt()
    out = run_pipeline_device(np.asarray(S, np.float32), cfg)
    assert isinstance(out.linkage, jax.Array)
    # a host-impl config has no fused form: rejected, not coerced
    with pytest.raises(ValueError, match="fused=False"):
        run_pipeline_device(np.asarray(S, np.float32),
                            cfg.replace(dbht_impl="host"))
    assert out.linkage.shape == (39, 4)
    assert out.tmfg.edges.shape == (3 * 40 - 6, 2)
    # explicit is_similarity overrides the heuristic; X path agrees
    # with the S path computed from the same pearson similarity
    out_x = run_pipeline_device(X, cfg, is_similarity=False)
    ref = cluster(X, k=3, config=cfg)
    _assert_linkage_equal(np.asarray(out_x.linkage), ref.linkage)
    # the inference guard: a square NON-symmetric input is ambiguous
    with pytest.raises(ValueError, match="is_similarity"):
        run_pipeline_device(np.random.default_rng(0)
                            .normal(size=(24, 24)).astype(np.float32), cfg)
