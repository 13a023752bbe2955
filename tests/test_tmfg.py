"""TMFG construction: JAX vs numpy oracles + structural invariants."""

import numpy as np
import pytest

import jax

from conftest import clustered_similarity, random_symmetric
from repro.core import tmfg_ref as R
from repro.core.tmfg import build_tmfg

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False


def _np(res):
    return jax.tree.map(np.asarray, res)


def check_invariants(res, n, S=None):
    """The paper's structural invariants (DESIGN.md §1)."""
    assert res.edges.shape == (3 * n - 6, 2)
    assert res.faces.shape == (2 * n - 4, 3)
    assert res.bubble_verts.shape == (n - 3, 4)
    # no duplicate / self edges
    e = np.sort(np.asarray(res.edges), axis=1)
    assert (e[:, 0] != e[:, 1]).all()
    assert len(set(map(tuple, e))) == 3 * n - 6
    # every vertex inserted exactly once
    assert sorted(np.asarray(res.insert_order).tolist()) == list(range(n))
    # bubble tree: parents precede children, root is bubble 0
    bp = np.asarray(res.bubble_parent)
    assert bp[0] == -1
    if n > 4:
        assert (bp[1:] >= 0).all() and (bp[1:] < np.arange(1, n - 3)).all()
    # every non-root bubble's separating triangle is a subset of its parent
    bv = np.asarray(res.bubble_verts)
    bt = np.asarray(res.bubble_tri)
    for b in range(1, n - 3):
        assert set(bt[b]) <= set(bv[bp[b]]), f"bubble {b} triangle not in parent"
        assert set(bt[b]) <= set(bv[b])
    # edge sum consistent
    if S is not None:
        s = sum(S[a, b] for a, b in e)
        assert abs(s - float(res.edge_sum)) < 1e-3 * n


@pytest.mark.parametrize("n", [8, 40, 90])
@pytest.mark.parametrize("method,ref_fn", [
    ("corr", R.tmfg_corr),
    ("lazy", R.tmfg_lazy),
])
def test_jax_matches_oracle(n, method, ref_fn):
    S, _, _ = clustered_similarity(n, seed=n)
    ref = ref_fn(S)
    got = _np(build_tmfg(S, method=method))
    assert (ref.insert_order == got.insert_order).all()
    np.testing.assert_allclose(ref.edge_sum, got.edge_sum, rtol=1e-4)
    assert (np.sort(ref.edges, 1) == np.sort(got.edges, 1)).all()
    assert (ref.bubble_parent == got.bubble_parent).all()
    check_invariants(got, n, S)


@pytest.mark.parametrize("prefix", [1, 7, 25])
def test_orig_matches_oracle(prefix):
    n = 60
    S, _, _ = clustered_similarity(n, seed=17)
    ref = R.tmfg_orig(S, prefix=prefix)
    got = _np(build_tmfg(S, method="orig", prefix=prefix))
    assert (ref.insert_order == got.insert_order).all()
    np.testing.assert_allclose(ref.edge_sum, got.edge_sum, rtol=1e-4)
    check_invariants(got, n, S)


def test_orig_prefix1_equals_exact_serial():
    S, _, _ = clustered_similarity(50, seed=3)
    assert (R.tmfg_orig(S, 1).insert_order == R.tmfg_exact(S).insert_order).all()


def test_topk_lookup_equivalent():
    """The top-K candidate table must not change the construction."""
    n = 80
    S, _, _ = clustered_similarity(n, seed=9)
    base = _np(build_tmfg(S, method="lazy", topk=0))
    for K in (4, 16, 128):
        tk = _np(build_tmfg(S, method="lazy", topk=K))
        assert (base.insert_order == tk.insert_order).all(), f"topk={K}"


def test_edge_sum_quality_ordering():
    """Paper §5.2: corr/lazy edge sums within ~1% of exact; large prefixes
    are strictly worse."""
    n = 150
    S, _, _ = clustered_similarity(n, k=5, seed=21)
    exact = R.tmfg_exact(S).edge_sum
    corr = float(build_tmfg(S, method="corr").edge_sum)
    lazy = float(build_tmfg(S, method="lazy").edge_sum)
    p200 = float(build_tmfg(S, method="orig", prefix=200).edge_sum)
    assert corr >= 0.97 * exact
    assert lazy >= 0.97 * exact
    assert abs(corr - lazy) <= 0.01 * abs(exact)
    assert p200 < lazy  # large prefix degrades quality (paper fig. 7)


@pytest.mark.parametrize("method,kw", [
    ("lazy", dict(topk=0)), ("lazy", dict(topk=64)), ("corr", {}),
    ("orig", dict(prefix=7)),
])
def test_vmap_matches_single_builds(method, kw):
    """``vmap(build_tmfg)`` equals the per-matrix builds field by field,
    ``pops`` included.  The lazy builds pop stale faces many times and
    finish after different numbers of pops, so the batched loop keeps
    popping finished builds, which must write nothing to their results."""
    n = 60
    Ss = [clustered_similarity(n, k=k, seed=s)[0]
          for k, s in ((3, 1), (4, 2), (5, 3), (2, 4), (6, 5))]
    Ss += [random_symmetric(n, 7), random_symmetric(n, 8)]
    build = lambda s: build_tmfg(s, method=method, **kw)
    batched = _np(jax.jit(jax.vmap(build))(np.stack(Ss)))
    singles = [_np(build(s)) for s in Ss]
    for field in batched._fields:
        want = np.stack([getattr(r, field) for r in singles])
        got = getattr(batched, field)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), \
            field
    if method == "lazy":
        pops = [int(r.pops) for r in singles]
        assert min(pops) >= 2 * (n - 4), pops     # many stale pops
        assert len(set(pops)) > 1, pops           # builds end apart


def test_lazy_pops_bounded():
    """Lazy revalidation overhead: pops = n-4 inserts + few stale refreshes."""
    n = 120
    S, _, _ = clustered_similarity(n, seed=5)
    res = _np(build_tmfg(S, method="lazy"))
    inserts = n - 4
    assert res.pops >= inserts
    assert res.pops <= 12 * inserts, f"too many stale pops: {res.pops}"


if HAVE_HYP:

    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=5, max_value=40), st.integers(0, 10_000))
    def test_property_invariants_random(n, seed):
        """Hypothesis: invariants hold for arbitrary symmetric inputs."""
        S = random_symmetric(n, seed)
        res = _np(build_tmfg(S, method="lazy"))
        check_invariants(res, n, S)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=6, max_value=30), st.integers(0, 10_000))
    def test_property_lazy_matches_ref(n, seed):
        S = random_symmetric(n, seed)
        ref = R.tmfg_lazy(S)
        got = _np(build_tmfg(S, method="lazy"))
        # ties are possible with arbitrary data; compare edge sums not order
        assert float(got.edge_sum) >= float(ref.edge_sum) - 1e-3
