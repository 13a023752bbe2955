"""Multi-device TMFG-DBHT: shard_map formulations of every heavy stage.

Sharding plan (DESIGN.md §4.4) over a 1-D slice of the production mesh
(the flattened (pod, data) axes; `model` is unused by the clustering
pipeline and free for the LM workloads sharing the mesh):

  * X (n, L) time series      — row-sharded        P('data', None)
  * S (n, n) similarity       — column-sharded     P(None, 'data')
  * TMFG state                — replicated (O(n) integers)
  * top-K candidate table     — replicated (n×K)
  * hub distance rows (h, n)  — replicated; W row-sharded

Column-sharding S makes every row scan (the masked-argmax MaxCorrs lookup,
the ORIG (F, n) gain reduction, the up-front top-k) a local scan over n/d
columns followed by one tiny all-gather of per-device (value, index)
candidates — the same "aggregate, then reduce" shape as the paper's
multicore reduction, with the ICI all-gather playing the role of the
shared-memory join.  O(1) element gathers (face gains) use an
owner-computes + psum pattern.

At 1M+ vertices the per-step latency of the lazy loop's small collectives
dominates; the batched ORIG-P construction (one (F, n) scan per round,
P inserts) amortizes them — measured in benchmarks/bench_speedup.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as dist_sh
from . import config as config_mod
from .config import PipelineConfig
from .tmfg import TMFGResult, _State, _face_pair, _init_state, _insert_one

NEG = -jnp.inf


# ---------------------------------------------------------------------------
# sharded similarity
# ---------------------------------------------------------------------------

def _axis_total(mesh: Mesh, axis) -> int:
    return dist_sh.axis_size(mesh, axis)


def pearson_sharded(X: jax.Array, mesh: Mesh, axis="data") -> jax.Array:
    """Pearson correlation with X row-sharded; S returned column-sharded.

    Local compute: standardize local rows, all-gather standardized rows
    (the only collective), then S[:, local] = Z_full @ Z_local^T —
    implemented once in dist/sharding.py (pearson_shardmap).
    """
    return dist_sh.pearson_shardmap(X, mesh, axis)


# ---------------------------------------------------------------------------
# sharded TMFG construction
# ---------------------------------------------------------------------------

def _sharded_lookup_factory(S_local, n_local, axis):
    """Masked-argmax lookup over column-sharded S: local scan + tiny combine."""
    idx = lax.axis_index(axis)
    col0 = idx * n_local

    def lookup(inserted, v):
        local_mask = lax.dynamic_slice(inserted, (col0,), (n_local,))
        row = jnp.where(local_mask, NEG, S_local[v])
        j = jnp.argmax(row)
        cand_val = row[j]
        cand_idx = (col0 + j).astype(jnp.int32)
        vals = lax.all_gather(cand_val, axis)             # (d,)
        idxs = lax.all_gather(cand_idx, axis)             # (d,)
        b = jnp.argmax(vals)
        return idxs[b]

    return lookup


def _sharded_gather_factory(S_local, n_local, axis):
    """S[r, c] for scalar (r, c): owner computes, psum broadcasts."""
    idx = lax.axis_index(axis)
    col0 = idx * n_local

    def gather(r, c):
        local = (c >= col0) & (c < col0 + n_local)
        val = jnp.where(local, S_local[r, jnp.clip(c - col0, 0, n_local - 1)],
                        0.0)
        return lax.psum(val, axis)

    return gather


def _sharded_lookup_many_factory(S_local, n_local, axis):
    """Masked argmax for a BATCH of rows with ONE all_gather.

    The paper's core insight — aggregate the per-step work into one
    parallel step — applied to the collective layer: the lazy loop's 3–4
    per-step MaxCorrs refreshes become a single (k, n/d) scan + a single
    (d, k) all-gather instead of k sequential scalar combines
    (§Perf: ~10x fewer collectives per insertion)."""
    idx = lax.axis_index(axis)
    col0 = idx * n_local

    def lookup_many(inserted, vs):
        k = vs.shape[0]
        local_mask = lax.dynamic_slice(inserted, (col0,), (n_local,))
        rows = jnp.where(local_mask[None, :], NEG, S_local[vs])  # (k, nl)
        j = jnp.argmax(rows, axis=1)
        vals = rows[jnp.arange(k), j]
        idxs = (col0 + j).astype(jnp.int32)
        g_vals = lax.all_gather(vals, axis)               # (d, k)
        g_idxs = lax.all_gather(idxs, axis)
        b = jnp.argmax(g_vals, axis=0)                    # (k,)
        return g_idxs[b, jnp.arange(k)]

    return lookup_many


def _sharded_gather_many_factory(S_local, n_local, axis):
    """S[rs, cs] for index vectors: owner-computes + ONE psum."""
    idx = lax.axis_index(axis)
    col0 = idx * n_local

    def gather_many(rs, cs):
        local = (cs >= col0) & (cs < col0 + n_local)
        vals = jnp.where(
            local, S_local[rs, jnp.clip(cs - col0, 0, n_local - 1)], 0.0)
        return lax.psum(vals, axis)

    return gather_many


def build_tmfg_sharded(S: jax.Array, mesh: Mesh, *, axis="data",
                       method: Optional[str] = None,
                       collectives: str = "batched",
                       config: Optional[PipelineConfig] = None) -> TMFGResult:
    """TMFG construction with S column-sharded over ``axis``.

    State is replicated; every row scan is distributed.  Produces bitwise
    the same result as the single-device ``build_tmfg`` (verified in
    tests/test_distributed.py).  ``collectives="batched"`` (default) fuses
    each step's lookups into one all-gather + one psum; "per-element" is
    the naive baseline kept for the §Perf A/B.  ``config`` supplies the
    construction method from one :class:`PipelineConfig` (DESIGN.md
    §12.1) instead of the loose kwarg; combining the two surfaces is
    rejected, as in ``PipelineConfig.resolve``.
    """
    config_mod.check_no_conflict(config, method=method)
    if config is not None:
        method = config.method
    elif method is None:
        method = "lazy"
    n = S.shape[0]
    d = _axis_total(mesh, axis)
    assert n % d == 0, f"n={n} must divide the '{axis}' axes ({d})"
    n_local = n // d

    S = S.astype(jnp.float32)
    S = jnp.where(jnp.eye(n, dtype=bool), NEG, S)

    def fn(S_local_T):
        # arrives as the (n/d, n) row block of S^T == a column block of S;
        # transpose so Sl[v] gives the local columns of row v.
        Sl = S_local_T.T  # (n, n_local)
        lookup = _sharded_lookup_factory(Sl, n_local, axis)
        gather = _sharded_gather_factory(Sl, n_local, axis)

        # --- replicated init (row sums via local partial + psum) ----------
        part = jnp.where(jnp.isfinite(Sl), Sl, 0.0).sum(axis=1)
        row_sums = lax.psum(part, axis)
        st = _init_sharded(
            row_sums, lookup, gather, n,
            maxcorr_all=lambda ins: _init_maxcorr_all(Sl, n_local, axis,
                                                      ins, n))

        if method != "lazy":
            raise NotImplementedError("sharded construction: lazy only")
        if collectives == "batched":
            lookup_many = _sharded_lookup_many_factory(Sl, n_local, axis)
            gather_many = _sharded_gather_many_factory(Sl, n_local, axis)
            st = _lazy_loop_sharded_batched(st, lookup_many, gather_many, n)
        else:
            st = _lazy_loop_sharded(st, lookup, gather, n)
        return _result_of(st)

    out = jax.shard_map(
        fn, mesh=mesh, in_specs=dist_sh.timeseries_spec(axis),
        out_specs=jax.tree.map(lambda _: P(), _result_spec(n)),
        check_vma=False,
    )(S.T)
    return out


def _result_spec(n):
    F, E, B = 2 * n - 4, 3 * n - 6, n - 3
    f = jax.ShapeDtypeStruct
    return TMFGResult(
        clique=f((4,), jnp.int32), edges=f((E, 2), jnp.int32),
        faces=f((F, 3), jnp.int32), insert_order=f((n,), jnp.int32),
        bubble_verts=f((B, 4), jnp.int32), bubble_parent=f((B,), jnp.int32),
        bubble_tri=f((B, 3), jnp.int32), home_bubble=f((n,), jnp.int32),
        edge_sum=f((), jnp.float32), pops=f((), jnp.int32),
    )


def _gain_of(gather, face, v):
    return gather(face[0], v) + gather(face[1], v) + gather(face[2], v)


def _face_pair_sharded(gather, maxcorr, face):
    cands = maxcorr[face]
    g = jnp.stack([_gain_of(gather, face, cands[i]) for i in range(3)])
    j = jnp.argmax(g)
    return cands[j].astype(jnp.int32), g[j]


def _init_maxcorr_all(Sl, n_local, axis, inserted, n):
    """The paper's single aggregated up-front step, sharded: ONE local
    masked-argmax scan over all n rows + ONE (d, n) all-gather — replacing
    a per-row lookup loop that cost 2n sequential collectives (found by the
    §Perf analyzer: 38 913 all-gathers in the init alone)."""
    idx = lax.axis_index(axis)
    col0 = idx * n_local
    local_mask = lax.dynamic_slice(inserted, (col0,), (n_local,))
    masked = jnp.where(local_mask[None, :], NEG, Sl)       # (n, n_local)
    j = jnp.argmax(masked, axis=1)
    vals = masked[jnp.arange(n), j]
    idxs = (col0 + j).astype(jnp.int32)
    g_vals = lax.all_gather(vals, axis)                    # (d, n)
    g_idxs = lax.all_gather(idxs, axis)
    b = jnp.argmax(g_vals, axis=0)
    return g_idxs[b, jnp.arange(n)]


def _init_sharded(row_sums, lookup, gather, n, maxcorr_all=None):
    """Replicated-state init mirroring tmfg._init_state but with sharded S."""
    F, E, B = 2 * n - 4, 3 * n - 6, n - 3
    _, idx = lax.top_k(row_sums, 4)
    clique = jnp.sort(idx).astype(jnp.int32)
    v1, v2, v3, v4 = clique[0], clique[1], clique[2], clique[3]

    inserted = jnp.zeros((n,), bool).at[clique].set(True)
    insert_order = jnp.zeros((n,), jnp.int32).at[:4].set(clique)

    pair = lambda x, y: jnp.stack([x, y])
    init_edges = jnp.stack([pair(v1, v2), pair(v1, v3), pair(v1, v4),
                            pair(v2, v3), pair(v2, v4), pair(v3, v4)])
    edges = jnp.zeros((E, 2), jnp.int32).at[:6].set(init_edges.astype(jnp.int32))
    edge_sum = sum(gather(init_edges[i, 0], init_edges[i, 1])
                   for i in range(6))

    tri = lambda x, y, z: jnp.stack([x, y, z])
    init_faces = jnp.stack([tri(v1, v2, v3), tri(v1, v2, v4),
                            tri(v1, v3, v4), tri(v2, v3, v4)])
    faces = jnp.zeros((F, 3), jnp.int32).at[:4].set(init_faces.astype(jnp.int32))

    if maxcorr_all is not None:
        maxcorr = maxcorr_all(inserted)
    else:
        maxcorr = jnp.zeros((n,), jnp.int32)
        body = lambda v, mc: mc.at[v].set(lookup(inserted, v))
        maxcorr = lax.fori_loop(0, n, body, maxcorr)

    gains = jnp.full((F,), NEG)
    best_v = jnp.zeros((F,), jnp.int32)
    for i in range(4):
        bv, g = _face_pair_sharded(gather, maxcorr, faces[i])
        best_v = best_v.at[i].set(bv)
        gains = gains.at[i].set(g)

    return _State(
        inserted=inserted, n_inserted=jnp.int32(4), maxcorr=maxcorr,
        gains=gains, best_v=best_v, faces=faces,
        face_bubble=jnp.zeros((F,), jnp.int32), n_faces=jnp.int32(4),
        edges=edges, n_edges=jnp.int32(6),
        edge_sum=edge_sum.astype(jnp.float32), insert_order=insert_order,
        bubble_verts=jnp.zeros((B, 4), jnp.int32).at[0].set(clique),
        bubble_parent=jnp.full((B,), -1, jnp.int32),
        bubble_tri=jnp.full((B, 3), -1, jnp.int32),
        home_bubble=jnp.zeros((n,), jnp.int32), pops=jnp.int32(0),
    )


def _lazy_loop_sharded(st, lookup, gather, n):
    """The LAZY pop loop with sharded lookups (state replicated)."""

    def insert_bookkeeping(st, f, v):
        # _insert_one needs S only for the edge-sum update; recompute that
        # term with the sharded gather and patch it.
        face = st.faces[f]
        es_inc = _gain_of(gather, face, v)
        fake_S = jnp.zeros((1, 1), jnp.float32)  # placeholder, not indexed

        # replicate _insert_one's bookkeeping inline (S-free):
        a, b, c = face[0], face[1], face[2]
        inserted = st.inserted.at[v].set(True)
        n_before = st.n_inserted
        insert_order = st.insert_order.at[n_before].set(v)
        n_inserted = n_before + 1
        new_edges = jnp.stack([jnp.stack([v, a]), jnp.stack([v, b]),
                               jnp.stack([v, c])]).astype(jnp.int32)
        edges = lax.dynamic_update_slice(st.edges, new_edges, (st.n_edges, 0))
        bub = n_inserted - 4
        bubble_verts = st.bubble_verts.at[bub].set(
            jnp.stack([v, a, b, c]).astype(jnp.int32))
        bubble_parent = st.bubble_parent.at[bub].set(st.face_bubble[f])
        bubble_tri = st.bubble_tri.at[bub].set(face)
        home_bubble = st.home_bubble.at[v].set(bub)
        faces = st.faces.at[f].set(jnp.stack([v, a, b]).astype(jnp.int32))
        faces = faces.at[st.n_faces].set(jnp.stack([v, b, c]).astype(jnp.int32))
        faces = faces.at[st.n_faces + 1].set(
            jnp.stack([v, a, c]).astype(jnp.int32))
        face_bubble = st.face_bubble.at[f].set(bub)
        face_bubble = face_bubble.at[st.n_faces].set(bub)
        face_bubble = face_bubble.at[st.n_faces + 1].set(bub)
        return st._replace(
            inserted=inserted, n_inserted=n_inserted, faces=faces,
            face_bubble=face_bubble, n_faces=st.n_faces + 2, edges=edges,
            n_edges=st.n_edges + 3, edge_sum=st.edge_sum + es_inc,
            insert_order=insert_order, bubble_verts=bubble_verts,
            bubble_parent=bubble_parent, bubble_tri=bubble_tri,
            home_bubble=home_bubble,
        ), face

    def refresh(st, f):
        face = st.faces[f]
        mc = st.maxcorr
        for i in range(3):
            mc = mc.at[face[i]].set(lookup(st.inserted, face[i]))
        v, g = _face_pair_sharded(gather, mc, face)
        return st._replace(maxcorr=mc, best_v=st.best_v.at[f].set(v),
                           gains=st.gains.at[f].set(g))

    def do_insert(st, f, v):
        slots = jnp.stack([f, st.n_faces, st.n_faces + 1])
        st, face = insert_bookkeeping(st, f, v)
        mc = st.maxcorr
        for w in (v, face[0], face[1], face[2]):
            mc = mc.at[w].set(lookup(st.inserted, w))
        best_v, gains = st.best_v, st.gains
        for i in range(3):
            bv, g = _face_pair_sharded(gather, mc, st.faces[slots[i]])
            best_v = best_v.at[slots[i]].set(bv)
            gains = gains.at[slots[i]].set(g)
        return st._replace(maxcorr=mc, best_v=best_v, gains=gains)

    def body(st):
        f = jnp.argmax(st.gains).astype(jnp.int32)
        v = st.best_v[f]
        stale = st.inserted[v]
        st = lax.cond(stale, lambda s: refresh(s, f),
                      lambda s: do_insert(s, f, v), st)
        return st._replace(pops=st.pops + 1)

    return lax.while_loop(lambda s: s.n_inserted < n, body, st)


def _lazy_loop_sharded_batched(st, lookup_many, gather_many, n):
    """LAZY pop loop with per-step collectives fused (DESIGN.md §4.4).

    Per insertion: ONE (d,4) all-gather (MaxCorrs refresh for the new
    4-clique), ONE 27-element psum (the 3 new faces' candidate gains) and
    ONE 3-element psum (edge-sum increment) — versus ~17 scalar collectives
    in the per-element baseline.  Latency-bound loops live and die by
    collective count; this is the paper's aggregation insight at the ICI
    layer."""

    def face_gains(mc, faces3):
        """(3 faces x 3 candidates) gains with one psum."""
        cands = mc[faces3]                                  # (3, 3)
        rs = jnp.broadcast_to(faces3[:, None, :], (3, 3, 3)).reshape(-1)
        cs = jnp.broadcast_to(cands[:, :, None], (3, 3, 3)).reshape(-1)
        vals = gather_many(rs, cs).reshape(3, 3, 3).sum(axis=2)  # (3, 3)
        return cands, vals

    def refresh(st, f):
        face = st.faces[f]
        mc = st.maxcorr.at[face].set(lookup_many(st.inserted, face))
        cands = mc[face]                                    # (3,)
        rs = jnp.broadcast_to(face[None, :], (3, 3)).reshape(-1)
        cs = jnp.repeat(cands, 3)
        g = gather_many(rs, cs).reshape(3, 3).sum(axis=1)   # (3,)
        j = jnp.argmax(g)
        return st._replace(
            maxcorr=mc,
            best_v=st.best_v.at[f].set(cands[j].astype(jnp.int32)),
            gains=st.gains.at[f].set(g[j]))

    def do_insert(st, f, v):
        face = st.faces[f]
        a, b, c = face[0], face[1], face[2]
        es_inc = gather_many(face, jnp.stack([v, v, v])).sum()
        slots = jnp.stack([f, st.n_faces, st.n_faces + 1])

        inserted = st.inserted.at[v].set(True)
        n_before = st.n_inserted
        insert_order = st.insert_order.at[n_before].set(v)
        n_inserted = n_before + 1
        new_edges = jnp.stack([jnp.stack([v, a]), jnp.stack([v, b]),
                               jnp.stack([v, c])]).astype(jnp.int32)
        edges = lax.dynamic_update_slice(st.edges, new_edges,
                                         (st.n_edges, 0))
        bub = n_inserted - 4
        bubble_verts = st.bubble_verts.at[bub].set(
            jnp.stack([v, a, b, c]).astype(jnp.int32))
        bubble_parent = st.bubble_parent.at[bub].set(st.face_bubble[f])
        bubble_tri = st.bubble_tri.at[bub].set(face)
        home_bubble = st.home_bubble.at[v].set(bub)
        faces = st.faces.at[f].set(jnp.stack([v, a, b]).astype(jnp.int32))
        faces = faces.at[st.n_faces].set(
            jnp.stack([v, b, c]).astype(jnp.int32))
        faces = faces.at[st.n_faces + 1].set(
            jnp.stack([v, a, c]).astype(jnp.int32))
        face_bubble = st.face_bubble.at[f].set(bub)
        face_bubble = face_bubble.at[st.n_faces].set(bub)
        face_bubble = face_bubble.at[st.n_faces + 1].set(bub)
        st = st._replace(
            inserted=inserted, n_inserted=n_inserted, faces=faces,
            face_bubble=face_bubble, n_faces=st.n_faces + 2, edges=edges,
            n_edges=st.n_edges + 3, edge_sum=st.edge_sum + es_inc,
            insert_order=insert_order, bubble_verts=bubble_verts,
            bubble_parent=bubble_parent, bubble_tri=bubble_tri,
            home_bubble=home_bubble)

        # ONE all-gather: MaxCorrs for the new 4-clique
        four = jnp.stack([v, a, b, c])
        mc = st.maxcorr.at[four].set(lookup_many(st.inserted, four))
        # ONE psum: gains of the 3 new faces' candidates
        faces3 = st.faces[slots]                            # (3, 3)
        cands, g = face_gains(mc, faces3)
        j = jnp.argmax(g, axis=1)
        best3 = cands[jnp.arange(3), j].astype(jnp.int32)
        g3 = g[jnp.arange(3), j]
        best_v = st.best_v.at[slots].set(best3)
        gains = st.gains.at[slots].set(g3)
        return st._replace(maxcorr=mc, best_v=best_v, gains=gains)

    def body(st):
        f = jnp.argmax(st.gains).astype(jnp.int32)
        v = st.best_v[f]
        stale = st.inserted[v]
        st = lax.cond(stale, lambda s: refresh(s, f),
                      lambda s: do_insert(s, f, v), st)
        return st._replace(pops=st.pops + 1)

    return lax.while_loop(lambda s: s.n_inserted < n, body, st)


def _result_of(st) -> TMFGResult:
    return TMFGResult(
        clique=st.insert_order[:4], edges=st.edges, faces=st.faces,
        insert_order=st.insert_order, bubble_verts=st.bubble_verts,
        bubble_parent=st.bubble_parent, bubble_tri=st.bubble_tri,
        home_bubble=st.home_bubble, edge_sum=st.edge_sum, pops=st.pops,
    )


# ---------------------------------------------------------------------------
# sharded hub APSP
# ---------------------------------------------------------------------------

def apsp_hub_sharded(W: jax.Array, mesh: Mesh, *, axis="data",
                     n_hubs: Optional[int] = None,
                     rounds: Optional[int] = None,
                     config: Optional[PipelineConfig] = None) -> jax.Array:
    """Hub APSP with W row-sharded; returns row-sharded distance estimate.

    Per Bellman-Ford round each device contributes the min-plus partial for
    its row block of W; one (h, n) min-all-reduce combines (implemented as
    -psum of negated… no — lax.pmin exists via psum? use all_gather+min).
    ``config`` supplies ``apsp_hubs``/``apsp_rounds`` from one
    :class:`PipelineConfig` instead of the loose kwargs; combining the
    two surfaces is rejected, as in ``PipelineConfig.resolve``.
    """
    import math

    config_mod.check_no_conflict(config, n_hubs=n_hubs, rounds=rounds)
    if config is not None:
        n_hubs, rounds = config.apsp_hubs, config.apsp_rounds
    else:
        n_hubs = 0 if n_hubs is None else n_hubs
        rounds = 0 if rounds is None else rounds
    n = W.shape[0]
    d = _axis_total(mesh, axis)
    assert n % d == 0
    cap = rounds if rounds else n
    h = n_hubs if n_hubs > 0 else max(4, math.ceil(math.sqrt(n)))
    h = min(h, n)

    finite = jnp.isfinite(W) & (W > 0)
    strength = jnp.sum(jnp.where(finite, 1.0 / (W + 1e-6), 0.0), axis=1)
    hubs = lax.top_k(strength, h)[1]
    D_h0 = W[hubs]  # (h, n) replicated

    def fn(W_local, D_h):
        idx = lax.axis_index(axis)
        k0 = idx * (n // d)

        def cond(carry):
            i, _, changed = carry
            return (i < cap) & changed

        def round_body(carry):
            # local tropical product: D_h[:, local k] x W_local -> (h, n).
            # The pmin-combined update is replicated, so the fixed-point
            # predicate is identical on every device and the while_loop
            # stays in lockstep (rounds=0 = relax to convergence, the
            # same contract as the single-device apsp_hub).
            i, D_h, _ = carry
            A = lax.dynamic_slice(D_h, (0, k0), (h, n // d))
            part = jnp.min(A[:, :, None] + W_local[None, :, :], axis=1)
            combined = lax.pmin(part, axis)
            D2 = jnp.minimum(D_h, combined)
            return i + 1, D2, jnp.any(D2 < D_h)

        _, D_h, _ = lax.while_loop(cond, round_body,
                                   (0, D_h, jnp.bool_(True)))
        # composition for the local row block
        A = lax.dynamic_slice(D_h, (0, k0), (h, n // d))  # (h, n/d)
        est = jnp.min(A.T[:, :, None] + D_h[None, :, :], axis=1)  # (n/d, n)
        est = jnp.minimum(est, W_local)
        return est

    est = jax.shard_map(fn, mesh=mesh,
                        in_specs=(dist_sh.timeseries_spec(axis), P()),
                        out_specs=dist_sh.timeseries_spec(axis),
                        check_vma=False)(W, D_h0)
    return est


# ---------------------------------------------------------------------------
# the config-driven multi-device funnel (DESIGN.md §17.4)
# ---------------------------------------------------------------------------

def _replicated(fn, mesh: Mesh):
    """``fn`` run whole on every device of ``mesh``, inputs and outputs
    replicated.  XLA cannot partition a Pallas kernel, so the stages
    that run replicated go through ``shard_map`` like the sharded ones:
    each device runs its own copy of the kernels on local data."""
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def run_pipeline_sharded(X_or_S, config: PipelineConfig, mesh: Mesh, *,
                         axis="data", is_similarity: Optional[bool] = None,
                         caps=None):
    """The whole pipeline on ``mesh``, dispatched by ``config`` — the one
    sharded entry point (``run_pipeline_device(..., mesh=)`` lands here).

    The bespoke stage wrappers above (``pearson_sharded``,
    ``build_tmfg_sharded``, ``apsp_hub_sharded``) stay as the unit-tested
    building blocks; this funnel composes the ones the config selects:

      * ``similarity="topk"`` from a time series — the scaling path:
        ``dist.sharding.topk_pearson_sharded`` builds the (n, K) table
        with each device owning a row panel, and the fused §17 tail
        (core/fused_approx.fused_from_table) runs as one jitted program
        on its output.  Nothing (n, n) is ever materialized.
      * dense similarity — row-sharded Pearson, column-sharded TMFG
        construction, row-sharded hub APSP (or exact/replicated below
        ``HUB_MIN_N``, matching the single-device dispatcher), then the
        device DBHT core, all in one jitted program.
      * ``apsp_method="sparse"`` or topk-from-S — the fused single-jit
        program on the materialized input (GSPMD places it); there is
        no cross-device structure left to exploit by hand.

    Returns the same ``DeviceOutputs`` pytree as ``run_pipeline_device``
    (device arrays, no host transfer).
    """
    from repro.core import pipeline as pipe    # lazy: no import cycle
    import repro.core.apsp as apsp_mod
    import repro.core.dbht as dbht_mod
    import repro.core.jitcache as jitcache

    cfg = config
    if cfg.dbht_impl != "device":
        raise ValueError("run_pipeline_sharded IS the device program; "
                         "config.dbht_impl='host' has no fused form")
    arr = jnp.asarray(X_or_S, jnp.float32)
    assert arr.ndim == 2, f"sharded funnel takes one matrix, got {arr.shape}"
    if is_similarity is None:
        is_similarity = arr.shape[-1] == arr.shape[-2]
    n = arr.shape[0]

    if cfg.similarity == "topk" and not is_similarity:
        kk = min(cfg.sim_k, n - 1)
        v, i, z = dist_sh.topk_pearson_sharded(arr, kk, mesh, axis=axis,
                                               backend=cfg.backend)

        def build():
            from repro.core import fused_approx as fa
            tail = fa.fused_from_table(cfg, n, from_x=True, caps=caps)

            def whole(tv, ti, src):
                core = tail(tv, ti, src)
                return pipe.DeviceOutputs(
                    tmfg=core["tmfg"], direction=core["direction"],
                    conv_mask=core["conv_mask"],
                    cluster_of=core["cluster_of"],
                    bubble_of=core["bubble_of"], apsp=core["D"],
                    linkage=core["Z"], hubs=core["hubs"],
                    overflow=core["overflow"], counters=core["counters"])

            return jax.jit(_replicated(whole, mesh))

        fn = jitcache.cached(("sharded_tail", cfg, n, kk, caps, mesh), build)
        return fn(v, i, z)

    if cfg.similarity == "topk" or cfg.apsp_method == "sparse":
        # materialized-S topk, or the sparse tail: one fused program
        return pipe.run_pipeline_device(arr, cfg,
                                        is_similarity=is_similarity,
                                        caps=caps)

    def build_dense():
        def exact(w):
            # same small-n dispatch as apsp.apsp: exact squaring
            return apsp_mod.apsp(w, method="exact", backend=cfg.backend)

        def tail(S, tm, D):
            core = dbht_mod._dbht_device_core(
                S, tm.edges, tm.bubble_parent, tm.bubble_tri,
                tm.bubble_verts, tm.home_bubble, D, backend=cfg.backend)
            return pipe.DeviceOutputs(
                tmfg=tm, direction=core["direction"],
                conv_mask=core["conv_mask"], cluster_of=core["cluster_of"],
                bubble_of=core["bubble_of"], apsp=core["D"],
                linkage=core["Z"])

        def whole(arr):
            S = arr if is_similarity else pearson_sharded(arr, mesh,
                                                          axis=axis)
            tm = build_tmfg_sharded(S, mesh, axis=axis, config=cfg)
            W = apsp_mod.edge_lengths(n, tm.edges, S)
            if cfg.apsp_method == "hub" and n >= apsp_mod.HUB_MIN_N:
                D = apsp_hub_sharded(W, mesh, axis=axis, config=cfg)
            else:
                D = _replicated(exact, mesh)(W)
            return _replicated(tail, mesh)(S, tm, D)

        return jax.jit(whole)

    # one program for the whole dense funnel: a replay compiles nothing
    fn = jitcache.cached(("sharded_dense", cfg, n, is_similarity, axis, mesh),
                         build_dense)
    return fn(arr)
