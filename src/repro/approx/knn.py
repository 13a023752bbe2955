"""Exact-rescoring blocked top-K Pearson (DESIGN.md §13.2).

The candidate tables the sparse TMFG consumes, three ways:

  * :func:`topk_pearson` — exact blocked top-K straight from the time
    series, via the streaming ``kernels/topk.py`` kernel (dispatched
    through ``ops.topk``).  O(n·K) peak similarity memory; at
    ``k = n-1`` the table holds bit-identical values to the dense
    matrix's rows (the exactness contract).
  * :func:`topk_from_similarity` — the same table cut from an already
    materialized (n, n) matrix (the streaming window path, where the
    co-moment state is O(n²) anyway): one batched ``lax.top_k``.
  * :func:`rescore_pools` — exact Pearson restricted to precomputed
    candidate pools (``project.candidate_pools``), then per-row top-K.
    This is the a-TMFG recipe: sketches propose, exact dots dispose —
    O(n·P·L) rescoring FLOPs instead of O(n²·L).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.ref import SIM_PRECISION, standardize_rows

NEG = -jnp.inf


class TopKTable(NamedTuple):
    """Per-row candidate table: the sparse similarity representation.

    ``values[i, j]`` is the Pearson correlation of rows ``i`` and
    ``indices[i, j]``, sorted per row by (value desc, index asc) —
    ``lax.top_k`` order.  The diagonal never appears.
    """

    values: jax.Array   # (n, K) f32
    indices: jax.Array  # (n, K) i32


def topk_pearson(X, k: int, *, backend: str = "auto",
                 bm: int = 128, bn: int = 128) -> TopKTable:
    """Exact top-K Pearson candidates of each row of ``X (n, L)``.

    Walks (bm, n) row-panels of the (never-materialized) correlation
    matrix keeping a running (bm, K) top-K (DESIGN.md §13.2); ``k`` is
    clamped to ``n - 1`` (every off-diagonal partner)."""
    X = jnp.asarray(X, jnp.float32)
    k = min(int(k), X.shape[0] - 1)
    v, i = ops.topk(X, k, backend=backend, bm=bm, bn=bn)
    return TopKTable(values=v, indices=i)


@functools.partial(jax.jit, static_argnames=("k", "backend", "bm", "bn"))
def _topk_and_z(X, k: int, backend: str, bm: int, bn: int):
    v, i = ops.topk(X, k, backend=backend, bm=bm, bn=bn)
    return v, i, standardize_rows(X)


def topk_pearson_and_z(X, k: int, *, backend: str = "auto",
                       bm: int = 128, bn: int = 128):
    """``(TopKTable, standardized Z)`` in ONE jitted program — the
    staged from-X similarity stage needs both (Z is the sparse build's
    exact-value fallback source), and a separate eager standardize
    would redo the O(n·L) pass in a second dispatch."""
    X = jnp.asarray(X, jnp.float32)
    k = min(int(k), X.shape[0] - 1)
    v, i, z = _topk_and_z(X, k, backend, bm, bn)
    return TopKTable(values=v, indices=i), z


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_from_similarity(S, k: int):
    n = S.shape[0]
    Sd = jnp.where(jnp.eye(n, dtype=bool), NEG, S.astype(jnp.float32))
    v, i = lax.top_k(Sd, k)
    return v, i.astype(jnp.int32)


def topk_from_similarity(S, k: int) -> TopKTable:
    """Candidate table cut from a dense (n, n) similarity matrix.

    For callers that already hold S — the streaming window, or a
    precomputed-similarity ``cluster(S=...)`` call — there is no memory
    to save, but the candidate-restricted TMFG semantics (and the cache
    keys) stay identical to the from-X path."""
    S = jnp.asarray(S, jnp.float32)
    k = min(int(k), S.shape[0] - 1)
    v, i = _topk_from_similarity(S, k)
    return TopKTable(values=v, indices=i)


FLOOR = -2.0  # finite fill below the Pearson range [-1, 1]


@functools.partial(jax.jit, static_argnames=("n",))
def _densify(values, indices, n: int):
    return jnp.full((n, n), FLOOR, jnp.float32).at[
        jnp.arange(n)[:, None], indices].set(values)


def densify(table: TopKTable, *, n: int) -> jax.Array:
    """The table as a dense (n, n) sparsified-similarity matrix.

    Missing entries (pairs outside the table, plus the diagonal) are
    floored at ``FLOOR = -2.0`` — finite, below any Pearson value, so
    the whole-row scans of the non-lazy TMFG methods stay well-defined
    (an -inf fill could starve ``method="orig"``'s finite-gain guard).
    At ``k = n-1`` every off-diagonal entry is present and the result
    matches the dense matrix bit for bit where it is ever read
    (``build_tmfg`` masks the diagonal itself).  This is the compat
    path for ``similarity="topk"`` with non-lazy methods — it is O(n²)
    again; the lazy method is the memory-saving path (DESIGN.md §13.3).
    """
    return _densify(table.values, table.indices, n)


@functools.partial(jax.jit, static_argnames=("k",))
def _rescore(X, pools, k: int):
    Z = standardize_rows(X)                                  # (n, L)
    cand = Z[pools]                                          # (n, P, L)
    s = jnp.clip(jnp.einsum("nl,npl->np", Z, cand,
                            precision=SIM_PRECISION), -1.0, 1.0)
    n, P = s.shape
    s = jnp.where(pools == jnp.arange(n, dtype=pools.dtype)[:, None],
                  NEG, s)                                    # drop self
    # TopKTable's contract is (value desc, index asc) — a plain top_k
    # over pool POSITIONS would break ties by pool order instead, so
    # sort lexicographically on (-value, candidate index)
    neg_v, idx = lax.sort((-s, pools.astype(jnp.int32)),
                          dimension=1, num_keys=2)
    return -neg_v[:, :k], idx[:, :k]


def rescore_pools(X, pools, k: int) -> TopKTable:
    """Exact Pearson rescoring of sketch-proposed candidate pools.

    ``pools (n, P)`` holds per-row candidate indices (P ≥ k, e.g. from
    ``project.candidate_pools``); each pool is rescored with true
    Pearson dots and reduced to its top-K, tie order per the TopKTable
    contract (the batched-gather dots can differ from the streaming
    kernel's by ~1 ulp, so tables agree with ``topk_pearson`` up to
    value rounding, exactly on well-separated values).  Rows whose true
    top-K escapes the pool lose those entries — quantified by
    ``quality.edge_recall`` and repaired at TMFG time by the dense-row
    fallback (DESIGN.md §13.3)."""
    X = jnp.asarray(X, jnp.float32)
    pools = jnp.asarray(pools)
    k = min(int(k), pools.shape[1], X.shape[0] - 1)
    v, i = _rescore(X, pools, k)
    return TopKTable(values=v, indices=i)
