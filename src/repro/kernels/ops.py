"""Jit'd public wrappers for the Pallas kernels with backend dispatch.

Backends:
  * ``"pallas"``    — compiled pallas_call (the TPU production path).
  * ``"interpret"`` — pallas_call in interpret mode (kernel body executed in
    Python on CPU; used by the correctness tests in this container).
  * ``"jnp"``       — pure-jnp oracle/fallback (fast on CPU via XLA).
  * ``"auto"``      — pallas on TPU, jnp elsewhere.

The default is "auto" so the same library code runs on a CPU (the tests,
with jnp kernels or interpret mode) and on the TPU (compiled kernels).
``sparse_relax`` has one XLA form on every platform (no Mosaic form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from . import sparse_apsp as _sparse
from .gainscan import masked_argmax_pallas
from .minplus import minplus_jnp, minplus_pallas
from .pearson import pearson_pallas
from .topk import (topk_pearson_jnp, topk_pearson_pallas, topk_rows_jnp,
                   topk_rows_pallas)


def _resolve(backend: str) -> str:
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def minplus(A: jax.Array, B: jax.Array, *, backend: str = "auto",
            bm: int = 128, bk: int = 128, bn: int = 128) -> jax.Array:
    """Tropical matmul: out[i,j] = min_k A[i,k] + B[k,j]."""
    b = _resolve(backend)
    if b == "pallas":
        return minplus_pallas(A, B, bm=bm, bk=bk, bn=bn)
    if b == "interpret":
        return minplus_pallas(A, B, bm=bm, bk=bk, bn=bn, interpret=True)
    return minplus_jnp(A, B)


def pearson(X: jax.Array, *, backend: str = "auto", bm: int = 128,
            bn: int = 128, bl: int = 128) -> jax.Array:
    """Pearson correlation matrix of the rows of X."""
    b = _resolve(backend)
    if b == "pallas":
        return pearson_pallas(X, bm=bm, bn=bn, bl=bl)
    if b == "interpret":
        return pearson_pallas(X, bm=bm, bn=bn, bl=bl, interpret=True)
    return ref.pearson_ref(X)


def masked_argmax(S: jax.Array, mask: jax.Array, *, backend: str = "auto",
                  bm: int = 8, bn: int = 512):
    """Per-row (max, argmax) of S with True-masked columns excluded."""
    b = _resolve(backend)
    if b == "pallas":
        return masked_argmax_pallas(S, mask, bm=bm, bn=bn)
    if b == "interpret":
        return masked_argmax_pallas(S, mask, bm=bm, bn=bn, interpret=True)
    return ref.masked_argmax_ref(S, mask)


def sparse_relax(D: jax.Array, graph, *,
                 backend: str = "auto") -> jax.Array:
    """One multi-source tropical SpMM round against a CSR adjacency.

    out[s, v] = min(D[s, v], min over CSR entries (u, v) of D[s, u] + w)
    (DESIGN.md §14.1).  ``graph`` is a ``kernels.sparse_apsp.CSRGraph``.
    One XLA form on every platform: ``backend`` selects nothing here."""
    return _sparse.sparse_relax(D, graph, backend=backend)


def topk(X: jax.Array, k: int, *, backend: str = "auto", bm: int = 128,
         bn: int = 128):
    """Top-k Pearson candidates per row of X (n, L), diagonal excluded.

    Returns (values (n, k) f32, indices (n, k) i32) in ``lax.top_k``
    order (value desc, index asc) — computed BLOCKED, so the (n, n)
    similarity matrix is never materialized (DESIGN.md §13.2)."""
    b = _resolve(backend)
    if b == "pallas":
        return topk_pearson_pallas(X, k, bm=bm, bn=bn)
    if b == "interpret":
        return topk_pearson_pallas(X, k, bm=bm, bn=bn, interpret=True)
    return topk_pearson_jnp(X, k, bm=bm)


def topk_rows(Zr: jax.Array, Z: jax.Array, rid: jax.Array, k: int, *,
              backend: str = "auto", bm: int = 128, bn: int = 128):
    """Top-k partners among the standardized rows Z (n, L) for the
    standardized rows Zr (m, L) with global ids rid (m,) — one row panel
    of ``topk``; the sharded scan's per-device step."""
    b = _resolve(backend)
    if b == "pallas":
        return topk_rows_pallas(Zr, Z, rid, k, bm=bm, bn=bn)
    if b == "interpret":
        return topk_rows_pallas(Zr, Z, rid, k, bm=bm, bn=bn, interpret=True)
    return topk_rows_jnp(Zr, Z, rid, k, bm=bm)
