"""Complete-linkage HAC: JAX implementation vs numpy oracle + offset trick."""

import numpy as np
import pytest

import jax.numpy as jnp

import repro.core.hac as hac
from repro.core import tmfg_ref as R

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False


def _rand_dist(n, seed):
    r = np.random.default_rng(seed)
    P = r.normal(size=(n, 3))
    D = np.linalg.norm(P[:, None] - P[None, :], axis=-1)
    return D


@pytest.mark.parametrize("n", [5, 20, 64])
def test_linkage_matches_oracle(n):
    D = _rand_dist(n, n)
    Z_ref = R.complete_linkage(D.copy())
    Z = np.asarray(hac.complete_linkage(jnp.asarray(D)))
    np.testing.assert_allclose(Z[:, 2], Z_ref[:, 2], rtol=1e-5)
    assert (Z[:, :2].astype(int) == Z_ref[:, :2].astype(int)).all()
    assert (Z[:, 3] == Z_ref[:, 3]).all()


@pytest.mark.parametrize("backend", ["auto", "interpret"])
def test_linkage_backend_parity(backend):
    """DESIGN.md §11.3: the masked_argmax-based min-merge scan (the
    gain-scan kernel reuse) is bitwise identical to the flat-argmin
    reference formulation on every backend."""
    D = _rand_dist(32, 5)
    Z_ref = np.asarray(hac.complete_linkage(jnp.asarray(D)))
    Z = np.asarray(hac.complete_linkage(jnp.asarray(D), backend=backend))
    assert (Z == Z_ref).all()


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_linkage_ties_match_oracle(backend):
    """Integer distances tie everywhere: the cached nearest neighbours
    must keep the oracle's lowest-flat-index merge order."""
    A = np.random.default_rng(3).integers(0, 4, (40, 40)).astype(float)
    D = np.minimum(A, A.T)
    Z_ref = R.complete_linkage(D.copy())
    Z = np.asarray(hac.complete_linkage(jnp.asarray(D), backend=backend))
    np.testing.assert_array_equal(Z, Z_ref)


def test_linkage_heights_monotone():
    D = _rand_dist(50, 7)
    Z = np.asarray(hac.complete_linkage(jnp.asarray(D)))
    assert (np.diff(Z[:, 2]) >= -1e-5).all(), "complete linkage is monotone"


def test_cut_linkage_counts():
    D = _rand_dist(30, 9)
    Z = np.asarray(hac.complete_linkage(jnp.asarray(D)))
    for k in (1, 2, 5, 30):
        labels = hac.cut_linkage(Z, 30, k)
        assert len(np.unique(labels)) == k


def test_hierarchical_offsets_respect_nesting():
    """Cutting the offset-adjusted dendrogram at the #clusters level must
    reproduce the coarse clusters exactly."""
    n = 48
    r = np.random.default_rng(3)
    D = _rand_dist(n, 11)
    cluster_of = r.integers(0, 3, n)
    bubble_of = cluster_of * 4 + r.integers(0, 4, n)
    adj = hac.hierarchical_offsets(jnp.asarray(D), jnp.asarray(bubble_of),
                                   jnp.asarray(cluster_of))
    Z = np.asarray(hac.complete_linkage(adj))
    labels = hac.cut_linkage(Z, n, 3)
    # same partition as cluster_of (up to relabelling)
    from repro.core.ari import ari
    assert ari(cluster_of, labels) == pytest.approx(1.0)


if HAVE_HYP:

    @settings(max_examples=15, deadline=None)
    @given(st.integers(3, 24), st.integers(0, 9999))
    def test_property_linkage_valid(n, seed):
        D = _rand_dist(n, seed)
        Z = np.asarray(hac.complete_linkage(jnp.asarray(D)))
        assert Z.shape == (n - 1, 4)
        assert Z[-1, 3] == n                       # final cluster has all
        ids = set(range(n))
        for k, (a, b, h, s) in enumerate(Z):
            assert int(a) in ids and int(b) in ids  # each id merged once
            ids.discard(int(a)); ids.discard(int(b))
            ids.add(n + k)
