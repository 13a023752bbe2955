"""Sparse-similarity TMFG: the lazy gain scan on a candidate table
(DESIGN.md §13.3).

This is ``core/tmfg.py``'s LAZY (HEAP-TMFG) construction re-pointed at
an ``(n, K)`` top-K candidate table (``knn.TopKTable``) instead of the
dense ``(n, n)`` similarity matrix.  Three operations touched S; each
gets a table-first equivalent:

  * per-row best-uninserted lookup (``maxcorr``) — first uninserted
    entry of the row's sorted candidate list; when the list is
    exhausted, the EXISTING masked-argmax dense-row fallback runs on a
    row recomputed on the fly (one ``clip(Z @ Z[v])`` matvec from the
    standardized series, or a gather when a dense S is the source) —
    counted in ``SparseCounters.fallbacks``.
  * pair values S[u, w] (gains, edge weights) — a K-wide search of row
    u's candidate list; a miss (pair outside the table) is rescored
    exactly from the source and counted in ``pair_misses``.
  * the batched init reductions (clique row-sums, maxcorr init) — the
    table is scattered back to dense ``(bm, n)`` ROW PANELS, never the
    full matrix, and reduced panel-wise.

At ``K = n-1`` every value comes from the table, whose entries are
bit-identical to the dense rows (kernels/topk.py), and every reduction
sees exactly the dense operands — so the construction (edges, bubbles,
edge weights, edge_sum) is bitwise-identical to
``build_tmfg(S, method="lazy")``; tests/test_approx.py pins the full
pipeline on top of this.  At K < n-1 the construction is the a-TMFG
approximation: candidates come from the table, values stay exact.

The result carries per-edge weights (``edge_weights``) so downstream
stages — edge lengths, DBHT edge directions — never need S at all:
:func:`repro.core.tmfg.adjacency_from_weights` scatters them into the
weighted adjacency the DBHT stage consumes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tmfg import (NEG, TMFGResult, _State, _clique_edges,
                             _clique_faces, _face, _insert_one, _result,
                             _root_state, _split_face)
from repro.kernels.ref import SIM_PRECISION

from .knn import TopKTable


class SparseCounters(NamedTuple):
    """Fallback/recall diagnostics of one sparse construction
    (DESIGN.md §13.3); surfaced in ``cluster(...).timings``."""

    lookups: jax.Array      # () i32 — maxcorr lookups served
    fallbacks: jax.Array    # () i32 — lookups that needed a dense row
    pair_lookups: jax.Array  # () i32 — pair-value probes
    pair_misses: jax.Array   # () i32 — probes rescored outside the table


class _SparseState(NamedTuple):
    st: _State              # the dense construction's bookkeeping state
    w_edges: jax.Array      # (E,) f32 — S value of each inserted edge
    lookups: jax.Array
    fallbacks: jax.Array
    pair_lookups: jax.Array
    pair_misses: jax.Array


# ---------------------------------------------------------------------------
# table-first primitives (each mirrors one dense-S access pattern)
# ---------------------------------------------------------------------------

def _true_row(src, from_x: bool, v):
    """Row v of the similarity matrix, recomputed on the fly: the
    dense-row fallback's operand.  O(n·L) from the standardized series
    (never an (n, n) buffer), or a gather when S is the source."""
    if from_x:
        row = jnp.clip(jnp.dot(src, src[v], precision=SIM_PRECISION),
                       -1.0, 1.0)
        return row.at[v].set(NEG)
    return src[v]                       # from-S source has NEG diagonal


def _pair_value(src, from_x: bool, topv, topi, u, w):
    """(S[u, w], hit?) — table search of row u, exact rescore on miss."""
    tk = topi[u]                                             # (K,)
    pos = jnp.argmax(tk == w)
    hit = tk[pos] == w
    if from_x:
        fb = jnp.clip(jnp.dot(src[u], src[w], precision=SIM_PRECISION),
                      -1.0, 1.0)
    else:
        fb = src[u, w]
    return jnp.where(hit, topv[u, pos], fb), hit


def _face_gains(src, from_x, topv, topi, faces, cands):
    """Per-face candidate gains with dense-identical reduction shape.

    ``faces (..., 3)``, ``cands (..., 3)`` → gains ``(..., 3)`` as
    ``vals.sum(axis=-2)`` over the corner axis — the same jnp reduction
    the dense ``_all_face_pairs`` runs on its gathered (..., 3, 3)
    values, so full-K gains are bitwise-identical.  Also returns the
    (lookups, misses) counts."""
    pv = functools.partial(_pair_value, src, from_x, topv, topi)
    pair = jax.vmap(jax.vmap(pv, in_axes=(None, 0)),        # over cands
                    in_axes=(0, None))                      # over corners
    if faces.ndim == 1:
        vals, hits = pair(faces, cands)                     # (3, 3)
    else:
        vals, hits = jax.vmap(pair)(faces, cands)           # (F, 3, 3)
    g = vals.sum(axis=-2)                                   # corner axis
    return g, hits


def _lookup_sparse(src, from_x, topv, topi, inserted, v):
    """Best uninserted vertex for row v: first uninserted candidate in
    the sorted list (== the dense masked argmax whenever the list still
    holds one — lax.top_k order is value desc, index asc), else the
    dense-row fallback.  Returns (vertex, fell_back?)."""
    tk = topi[v]
    ok = ~inserted[tk]
    j = jnp.argmax(ok)
    found = ok[j]

    def fallback():
        row = jnp.where(inserted, NEG, _true_row(src, from_x, v))
        return jnp.argmax(row).astype(jnp.int32)

    return lax.cond(found, lambda: tk[j].astype(jnp.int32), fallback), ~found


# ---------------------------------------------------------------------------
# blocked init: the (n,)-wide reductions without an (n, n) buffer
# ---------------------------------------------------------------------------

def _panels(topv, topi, n: int, bm: int):
    """Scan helper: yields dense (bm, n) row panels scattered from the
    table (missing entries NEG) — the ONLY dense form the sparse path
    ever builds, one panel at a time."""
    K = topv.shape[1]
    bm = min(bm, n)
    pad = (-n) % bm
    tv = jnp.pad(topv, ((0, pad), (0, 0)), constant_values=NEG)
    # padded rows need distinct in-range indices for a deterministic
    # scatter; their values are NEG and the rows are sliced off anyway
    ti = jnp.concatenate(
        [topi, jnp.broadcast_to(jnp.arange(K, dtype=topi.dtype) % n,
                                (pad, K))]) if pad else topi
    starts = jnp.arange(0, n + pad, bm, dtype=jnp.int32)

    def scatter(i0):
        v = lax.dynamic_slice(tv, (i0, 0), (bm, K))
        ix = lax.dynamic_slice(ti, (i0, 0), (bm, K))
        return jnp.full((bm, n), NEG, jnp.float32).at[
            jnp.arange(bm)[:, None], ix].set(v)

    return starts, scatter


def _row_sums_blocked(topv, topi, n: int, bm: int):
    """Weighted-degree row sums for clique seeding: per panel, the same
    ``where(isfinite, ·, 0).sum(axis=1)`` the dense init runs."""
    starts, scatter = _panels(topv, topi, n, bm)

    def body(_, i0):
        d = scatter(i0)
        return None, jnp.where(jnp.isfinite(d), d, 0.0).sum(axis=1)

    _, rs = lax.scan(body, None, starts)
    return rs.reshape(-1)[:n]


def _maxcorr_blocked(topv, topi, inserted, n: int, bm: int):
    """Fresh maxcorr for every row: per panel, the dense init's masked
    argmax (missing entries NEG, so only candidates compete)."""
    starts, scatter = _panels(topv, topi, n, bm)

    def body(_, i0):
        d = scatter(i0)
        return None, jnp.argmax(jnp.where(inserted[None, :], NEG, d),
                                axis=1).astype(jnp.int32)

    _, mc = lax.scan(body, None, starts)
    return mc.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def _init_sparse(topv, topi, src, from_x: bool, n: int, bm: int
                 ) -> _SparseState:
    """Mirror of ``tmfg._init_state`` driven by the table: identical
    clique choice, edge bookkeeping and face gains at full K."""
    F, E = 2 * n - 4, 3 * n - 6
    row_sums = _row_sums_blocked(topv, topi, n, bm)
    _, idx = lax.top_k(row_sums, 4)
    clique = jnp.sort(idx).astype(jnp.int32)
    inserted = jnp.zeros((n,), bool).at[clique].set(True)

    init_edges = _clique_edges(clique)
    pv = functools.partial(_pair_value, src, from_x, topv, topi)
    w6, hits6 = jax.vmap(pv)(init_edges[:, 0], init_edges[:, 1])
    w_edges = jnp.zeros((E,), jnp.float32).at[:6].set(w6)
    faces = _clique_faces(clique, F)

    maxcorr = _maxcorr_blocked(topv, topi, inserted, n, bm)

    valid = jnp.arange(F) < 4
    cands = maxcorr[faces]                                   # (F, 3)
    g, hits = _face_gains(src, from_x, topv, topi, faces, cands)
    j = jnp.argmax(g, axis=1)
    best_v = jnp.take_along_axis(cands, j[:, None], axis=1)[:, 0] \
        .astype(jnp.int32)
    gains = jnp.take_along_axis(g, j[:, None], axis=1)[:, 0]
    gains = jnp.where(valid, gains, NEG)

    st = _root_state(clique, n, edge_sum=w6.sum(), maxcorr=maxcorr,
                     best_v=best_v, gains=gains)
    init_pairs = 6 + 9 * 4                                  # clique + faces
    init_miss = (6 - hits6.sum()) + jnp.sum(
        jnp.where(valid[:, None, None], ~hits, False))
    return _SparseState(
        st=st, w_edges=w_edges,
        lookups=jnp.int32(0), fallbacks=jnp.int32(0),
        pair_lookups=jnp.int32(init_pairs),
        pair_misses=init_miss.astype(jnp.int32))


def sparse_lazy_tmfg(topv: jax.Array, topi: jax.Array, src: jax.Array,
                     *, from_x: bool, bm: int = 64
                     ) -> Tuple[TMFGResult, jax.Array, SparseCounters]:
    """Traceable sparse LAZY construction (jit/vmap it like the dense
    builder).  ``src`` is the exact-value source: the standardized
    series ``Z (n, L)`` when ``from_x`` (fallback rows are matvecs), or
    the dense ``S (n, n)`` when not (the streaming-window path).

    Returns ``(TMFGResult, edge_weights (3n-6,), SparseCounters)``.
    """
    n = topi.shape[0]
    if from_x:
        src = src.astype(jnp.float32)
    else:
        src = jnp.where(jnp.eye(n, dtype=bool), NEG,
                        src.astype(jnp.float32))
    topv = topv.astype(jnp.float32)
    lookup = functools.partial(_lookup_sparse, src, from_x, topv, topi)
    pairval = functools.partial(_pair_value, src, from_x, topv, topi)

    def face_pair(mc, face):
        """(best vertex, gain, pair-miss count) for one face — the
        dense ``_face_pair`` with table-first values."""
        cands = mc[face]                                     # (3,)
        g, hits = _face_gains(src, from_x, topv, topi, face, cands)
        j = jnp.argmax(g)
        return cands[j].astype(jnp.int32), g[j], jnp.sum(~hits)

    def refresh(s: _SparseState, f):
        st = s.st
        face = _face(st, f)
        mc, fb = st.maxcorr, jnp.int32(0)
        for i in range(3):
            v, fell = lookup(st.inserted, face[i])
            mc = mc.at[face[i]].set(v)
            fb = fb + fell
        bv, g, miss = face_pair(mc, face)
        st = st._replace(maxcorr=mc, best_v=st.best_v.at[f].set(bv),
                         gains=st.gains.at[f].set(g))
        return s._replace(st=st, lookups=s.lookups + 3,
                          fallbacks=s.fallbacks + fb,
                          pair_lookups=s.pair_lookups + 9,
                          pair_misses=s.pair_misses + miss)

    def do_insert(s: _SparseState, f, v):
        st = s.st
        face = _face(st, f)
        a, b, c = face[0], face[1], face[2]
        slots = jnp.stack([f, st.n_faces, st.n_faces + 1])
        # the three new edge weights, dense orientation S[v, ·]
        wv, hv = jax.vmap(pairval, in_axes=(None, 0))(
            v, jnp.stack([a, b, c]))
        # insertion i (from 0) adds edge rows 6 + 3i .. 8 + 3i
        w_edges = lax.dynamic_update_slice(
            s.w_edges, wv, (3 * st.n_inserted - 6,))
        st = _insert_one(st, f, face, v, wv)
        # refresh maxcorr for the 4 clique vertices (Alg. 2 lines 21-22)
        mc, fb = st.maxcorr, jnp.int32(0)
        for w in (v, a, b, c):
            u, fell = lookup(st.inserted, w)
            mc = mc.at[w].set(u)
            fb = fb + fell
        # pairs for the 3 new face slots (Alg. 2 lines 23-25)
        best_v, gains, miss = st.best_v, st.gains, jnp.int32(0)
        new_faces = _split_face(face, v)
        for i in range(3):
            bv, g, m = face_pair(mc, new_faces[3 * i:3 * i + 3])
            best_v = best_v.at[slots[i]].set(bv)
            gains = gains.at[slots[i]].set(g)
            miss = miss + m
        st = st._replace(maxcorr=mc, best_v=best_v, gains=gains)
        return s._replace(
            st=st, w_edges=w_edges, lookups=s.lookups + 4,
            fallbacks=s.fallbacks + fb,
            pair_lookups=s.pair_lookups + 3 + 27,
            pair_misses=s.pair_misses + miss
            + jnp.sum(~hv).astype(jnp.int32))

    def body(s: _SparseState) -> _SparseState:
        st = s.st
        f = jnp.argmax(st.gains).astype(jnp.int32)   # vectorized heap-pop
        v = st.best_v[f]
        stale = st.inserted[v]
        s = lax.cond(stale, lambda q: refresh(q, f),
                     lambda q: do_insert(q, f, v), s)
        return s._replace(st=s.st._replace(pops=s.st.pops + 1))

    s0 = _init_sparse(topv, topi, src, from_x, n, bm)
    s = lax.while_loop(lambda q: q.st.n_inserted < n, body, s0)

    result = _result(s.st)
    counters = SparseCounters(
        lookups=s.lookups, fallbacks=s.fallbacks,
        pair_lookups=s.pair_lookups, pair_misses=s.pair_misses)
    return result, s.w_edges, counters


@functools.partial(jax.jit, static_argnames=("from_x", "bm"))
def _build_jit(topv, topi, src, from_x: bool, bm: int):
    return sparse_lazy_tmfg(topv, topi, src, from_x=from_x, bm=bm)


def build_tmfg_sparse(table: TopKTable, *, Xn=None, S=None, bm: int = 64):
    """Jitted convenience wrapper: sparse lazy TMFG from a candidate
    table plus exactly one value source (standardized series ``Xn`` or
    dense ``S``).  Returns ``(TMFGResult, edge_weights, SparseCounters)``.
    """
    if (Xn is None) == (S is None):
        raise ValueError("pass exactly one of Xn= (standardized series) "
                         "or S= (dense similarity)")
    src = Xn if S is None else S
    return _build_jit(jnp.asarray(table.values), jnp.asarray(table.indices),
                      jnp.asarray(src, jnp.float32), S is None, bm)
