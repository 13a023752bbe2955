"""Pallas TPU kernel: streaming blocked top-K Pearson (DESIGN.md §13.2).

The similarity stage of every pipeline path used to materialize the
full ``(n, n)`` Pearson matrix even though TMFG construction only ever
consumes a per-row candidate list.  This kernel computes, for each row
of ``X (n, L)``, the K highest-correlation partner rows — values and
indices — WITHOUT ever holding an ``(n, n)`` buffer: it walks
``(bm, n)`` row-panels of the correlation matrix one ``(bm, bn)``
column tile at a time, keeping a running ``(bm, K)`` top-K in VMEM, so
peak memory is ``O(n·K + n·L)`` instead of ``O(n²)``.

Tie semantics match ``jax.lax.top_k`` on the dense matrix exactly:
values descending, equal values ordered by ascending column index.
The diagonal (self-correlation) is excluded.  The jnp fallback
computes each ``(bm, n)`` row-panel with ``ref.pearson_rows``, the
arithmetic of ``ref.pearson_ref``, so
at ``K = n-1`` the candidate table holds bit-identical values to the
dense similarity matrix's rows (the exactness contract
tests/test_approx.py pins end to end).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .ref import SIM_PRECISION, pearson_rows, standardize_rows

NEG = -3.4e38  # finite -inf stand-in (kernel-internal; Pearson ∈ [-1, 1])


def _merge_topk(run_v, run_i, cand_v, cand_i, k: int):
    """Merge candidate (value, index) pairs into a running top-K.

    ``run_v/run_i`` are (bm, K); ``cand_v/cand_i`` are (bm, C).  Selects
    the K best of the K+C pairs per row by (value desc, index asc) with
    K iterative max-extractions over the two arrays side by side — no
    sort, no lane-axis concatenate and no dynamic slice write (slot s is
    written through a lane-iota mask), so the same body runs under
    Mosaic, interpret mode, and plain XLA.
    """
    big_i = jnp.int32(2 ** 30)
    bm = run_v.shape[0]
    slot = lax.broadcasted_iota(jnp.int32, (bm, k), 1)

    def step(s, carry):
        rv, ri, cv, ci, out_v, out_i = carry
        best_v = jnp.maximum(jnp.max(rv, axis=1, keepdims=True),
                             jnp.max(cv, axis=1, keepdims=True))  # (bm, 1)
        r_at, c_at = rv == best_v, cv == best_v
        best_i = jnp.minimum(
            jnp.min(jnp.where(r_at, ri, big_i), axis=1, keepdims=True),
            jnp.min(jnp.where(c_at, ci, big_i), axis=1, keepdims=True))
        out_v = jnp.where(slot == s, best_v, out_v)
        out_i = jnp.where(slot == s, best_i, out_i)
        r_take, c_take = r_at & (ri == best_i), c_at & (ci == best_i)
        rv, ri = jnp.where(r_take, NEG, rv), jnp.where(r_take, big_i, ri)
        cv, ci = jnp.where(c_take, NEG, cv), jnp.where(c_take, big_i, ci)
        return rv, ri, cv, ci, out_v, out_i

    out_v = jnp.full((bm, k), NEG, run_v.dtype)
    out_i = jnp.full((bm, k), big_i, jnp.int32)
    carry = lax.fori_loop(0, k, step,
                          (run_v, run_i, cand_v, cand_i, out_v, out_i))
    return carry[4], carry[5]


def _topk_kernel(rid_ref, zrow_ref, zcol_ref, val_ref, idx_ref, *, bn: int,
                 k: int, n: int):
    """Grid (i, j): stream column tiles j through row panel i's top-K.

    ``rid_ref`` holds the panel rows' global ids (the diagonal to skip).
    The output blocks (bm, K) are revisited for every j — they ARE the
    running top-K state (the gainscan kernel's running-argmax idiom,
    widened from 1 to K slots)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG)
        idx_ref[...] = jnp.full_like(idx_ref, jnp.int32(2 ** 30))

    z = zrow_ref[...]                                        # (bm, L)
    w = zcol_ref[...]                                        # (bn, L)
    s = jnp.dot(z, w.T, preferred_element_type=jnp.float32,
                precision=SIM_PRECISION)                     # (bm, bn)
    s = jnp.clip(s, -1.0, 1.0)
    bm = s.shape[0]
    cols = j * bn + lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    s = jnp.where((rid_ref[...] == cols) | (cols >= n), NEG, s)  # no self/pad
    v, ix = _merge_topk(val_ref[...], idx_ref[...], s, cols, k)
    val_ref[...] = v
    idx_ref[...] = ix


def _check_k(k: int, n: int) -> None:
    if not 1 <= k <= n - 1:
        raise ValueError(f"need 1 <= k <= n-1, got k={k} for n={n}")


@functools.partial(jax.jit,
                   static_argnames=("k", "bm", "bn", "interpret"))
def topk_rows_pallas(Zr: jax.Array, Z: jax.Array, rid: jax.Array, k: int, *,
                     bm: int = 128, bn: int = 128, interpret: bool = False):
    """Top-K partners among the n standardized rows ``Z (n, L)`` for the
    standardized rows ``Zr (m, L)``, whose global ids ``rid (m,)`` mark
    the self-pair to skip.  The streaming kernel behind both the
    single-device scan (``Zr = Z``) and a device's row panel of the
    sharded scan (``dist.sharding.topk_pearson_sharded``): each row's
    result depends on that row alone, so a panel's rows come out
    bitwise as in the whole scan.

    Returns ``(values (m, k) f32, indices (m, k) i32)``, sorted by
    (value desc, index asc) per row — ``lax.top_k`` order.
    """
    m, L = Zr.shape
    n = Z.shape[0]
    _check_k(k, n)
    bm_, bn_ = min(bm, m), min(bn, n)
    pm, pn = (-m) % bm_, (-n) % bn_
    Zrp = jnp.pad(Zr, ((0, pm), (0, 0)))
    ridp = jnp.pad(rid.astype(jnp.int32), (0, pm), constant_values=-1)
    Zp = jnp.pad(Z, ((0, pn), (0, 0)))                      # zero rows: s=0,
    M, N = m + pm, n + pn                                   # masked by col>=n

    val, idx = pl.pallas_call(
        functools.partial(_topk_kernel, bn=bn_, k=k, n=n),
        grid=(M // bm_, N // bn_),
        in_specs=[
            pl.BlockSpec((bm_, 1), lambda i, j: (i, 0)),     # row ids
            pl.BlockSpec((bm_, L), lambda i, j: (i, 0)),     # row panel
            pl.BlockSpec((bn_, L), lambda i, j: (j, 0)),     # column tile
        ],
        out_specs=[
            pl.BlockSpec((bm_, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm_, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, k), jnp.float32),
            jax.ShapeDtypeStruct((M, k), jnp.int32),
        ],
        interpret=interpret,
    )(ridp[:, None], Zrp, Zp)
    return val[:m], idx[:m]


@functools.partial(jax.jit,
                   static_argnames=("k", "bm", "bn", "interpret"))
def topk_pearson_pallas(X: jax.Array, k: int, *, bm: int = 128,
                        bn: int = 128, interpret: bool = False):
    """Top-K Pearson candidates of each row of X via the streaming kernel.

    Unlike the dense pearson kernel the standardized ``Z (n, L)`` IS
    materialized (it is only O(n·L)); what is never materialized is the
    (n, n) similarity matrix.
    """
    n = X.shape[0]
    _check_k(k, n)
    Z = standardize_rows(X)
    return topk_rows_pallas(Z, Z, jnp.arange(n, dtype=jnp.int32), k, bm=bm,
                            bn=bn, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "bm"))
def topk_rows_jnp(Zr: jax.Array, Z: jax.Array, rid: jax.Array, k: int, *,
                  bm: int = 128):
    """``topk_rows_pallas`` in pure XLA (the CPU production path).

    Scans ``(bm, n)`` row-panels — ``ref.pearson_rows``, the arithmetic
    of ``ref.pearson_ref``, whose rows XLA computes bit-identically to
    the corresponding rows of the full matmul — and reduces each to
    its per-row ``lax.top_k``.  Peak live memory is the panel plus the
    (m, k) outputs; the (n, n) matrix never exists (the jaxpr shape
    check in tests/test_approx.py pins this).
    """
    m, L = Zr.shape
    n = Z.shape[0]
    _check_k(k, n)
    bm_ = min(bm, m)
    pad = (-m) % bm_
    Zrp = jnp.pad(Zr, ((0, pad), (0, 0)))
    ridp = jnp.pad(rid.astype(jnp.int32), (0, pad), constant_values=-1)

    def panel(_, i0):
        rows = lax.dynamic_slice(Zrp, (i0, 0), (bm_, L))
        s = pearson_rows(rows, Z)                            # (bm, n)
        r = lax.dynamic_slice(ridp, (i0,), (bm_,))
        s = jnp.where(r[:, None] == jnp.arange(n)[None, :], -jnp.inf, s)
        v, ix = lax.top_k(s, k)
        return None, (v, ix.astype(jnp.int32))

    starts = jnp.arange(0, m + pad, bm_, dtype=jnp.int32)
    _, (v, ix) = lax.scan(panel, None, starts)
    return v.reshape(-1, k)[:m], ix.reshape(-1, k)[:m]


@functools.partial(jax.jit, static_argnames=("k", "bm"))
def topk_pearson_jnp(X: jax.Array, k: int, *, bm: int = 128):
    """Blocked top-K Pearson of the rows of X, pure XLA."""
    n = X.shape[0]
    _check_k(k, n)
    Z = standardize_rows(X)
    return topk_rows_jnp(Z, Z, jnp.arange(n, dtype=jnp.int32), k, bm=bm)
