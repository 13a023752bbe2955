"""Fused sparse-approx pipeline: one traceable body, no (n, n) buffer.

``core/pipeline.run_pipeline_device`` used to reject
``similarity="topk"`` (DESIGN.md §13.5) and ``apsp_method="sparse"``
(DESIGN.md §14.6): the
sparse tail ran as host-orchestrated staged programs because two of its
stages lived on the host — the Euler-tour direction sums and the
per-cluster HAC with data-dependent shapes.  This module retires that
boundary (DESIGN.md §17): every stage of the approx path — the blocked
top-K Pearson scan, the lazy sparse TMFG, the hub APSP factor, bubble
directions/flow, the blocked D~ panel sweep and the nested HAC — is
expressed with ``lax``-structured control flow over static
``(n, K, h)`` shapes, so the WHOLE pipeline is one jitted program with
a single device→host transfer, and the no-(n, n) guarantee now holds
over the fused jaxpr (pinned by tests/test_property.py).

The two formerly-host stages, made traceable:

  * directions (§17.2) — the host oracle walks the Euler tour and sums
    each triangle corner's adjacency into child/parent sides.  Here the
    tour itself is two O(B) ``fori_loop``s (subtree sizes bottom-up,
    preorder slots top-down; parents precede children by construction),
    and the side sums become prefix-sum range queries: the 2E directed
    CSR entries are sorted by ``src·n + tin[home(dst)]``, so "weight of
    v's neighbors inside subtree b" is two ``searchsorted``s and a
    cumsum difference.  f32 on device vs the oracle's f64 — same
    sign-parity caveat as the dense device directions (§11.4).
  * nested HAC (§17.3) — data-dependent cluster shapes become a static
    ``(c_cap, m_cap)`` slot grid: one ``lax.scan`` over cluster slots
    (ordered by minimum member, the oracle's order), a ``lax.switch``
    over power-of-two member tiers replicating the staged path's
    ``m_pad`` buckets bitwise, and a stable-argsort device assembly
    reproducing ``sparse_dbht._assemble_linkage``'s emission order.
    Clusters that overflow the caps raise the ``overflow`` flag in the
    outputs; ``cluster()`` falls back to the staged path (correct at
    any size) when it sees it.

Parity: at the property-test sizes the approx configs dispatch to the
DENSE formulation below (``apsp.apsp`` itself runs exact APSP under
``HUB_MIN_N``), which composes exactly the staged stages — fused ==
staged bitwise there.  The sparse tail equals the staged sparse tail
up to the direction-sum precision caveat above and exact cross-cluster
float height ties (the staged path's own §14.5 caveat).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import repro.core.apsp as apsp_mod
import repro.core.hac as hac_mod
from repro.approx.knn import _densify, _topk_and_z  # noqa: F401
from repro.approx.sparse_tmfg import SparseCounters, sparse_lazy_tmfg
from repro.kernels import ops
from repro.kernels.ref import standardize_rows
from repro.kernels.sparse_apsp import CSRGraph, csr_from_edges
from repro.obs import trace as obs_trace
from .tmfg import TMFGResult, adjacency_from_weights, build_tmfg

INF = jnp.inf

# Static capacity of the fused nested-HAC slot grid (DESIGN.md §17.3):
# at most c_cap coarse clusters of at most m_cap members each.  The
# converging-bubble count grows like ~2·√n on real clustered graphs
# (measured 41/51/92/129 at n = 500/1000/2000/4000 for BENCH_9), so the
# default slot cap scales as max(FUSED_C_CAP, 4·√n) — a flat 64 made
# every fused run from n ≈ 2000 overflow and silently pay fused PLUS
# the staged rerun.  A run that still exceeds either cap sets
# ``overflow`` and the caller reruns staged (correct at any partition).
# Both are clamped to the problem size at trace time (``fused_caps``).
FUSED_C_CAP = 64
FUSED_M_CAP = 2048

# int32 composite sort keys (src·n + preorder slot) bound the fused
# direction stage to n² < 2³¹.
FUSED_MAX_N = 46_340


def _next_pow2(x: int) -> int:
    return 1 << max(1, (x - 1).bit_length())


def fused_caps(n: int, caps: Optional[Tuple[int, int]] = None
               ) -> Tuple[int, int]:
    """(c_cap, m_cap) for problem size n: the configured caps — or the
    n-adaptive defaults, slot cap max(FUSED_C_CAP, 4·√n) for the ~2·√n
    converging-bubble growth — clamped to what n can even produce
    (≤ n-3 clusters; ≤ n members)."""
    if caps is not None:
        c_cap, m_cap = caps
    else:
        c_cap = max(FUSED_C_CAP, 4 * math.isqrt(n))
        m_cap = FUSED_M_CAP
    c_cap = max(2, min(c_cap, max(2, n - 3)))
    m_cap = max(2, min(m_cap, _next_pow2(n)))
    return c_cap, m_cap


# ---------------------------------------------------------------------------
# device Euler tour + direction sums (DESIGN.md §17.2)
# ---------------------------------------------------------------------------

def _device_euler_tour(parent: jax.Array):
    """Preorder (tin, tout) of the bubble tree, children ascending id —
    the same tour ``dbht._euler_tour`` walks recursively.

    Two O(B) sequential loops of scalar ops: parents have smaller ids
    than children (TMFG insertion order), so a reverse pass accumulates
    subtree sizes and a forward pass assigns preorder slots from a
    per-node next-free cursor.  ``tout = tin + size`` (half-open)."""
    B = parent.shape[0]
    parent = parent.astype(jnp.int32)
    size = jnp.ones((B,), jnp.int32)

    def back(i, sz):
        b = B - 1 - i                     # b = B-1 .. 1
        return sz.at[parent[b]].add(sz[b])

    size = lax.fori_loop(0, B - 1, back, size)

    tin = jnp.zeros((B,), jnp.int32)
    nxt = jnp.zeros((B,), jnp.int32).at[0].set(1)

    def fwd(b, carry):                    # b = 1 .. B-1 in id order =
        tin_, nxt_ = carry                # children ascending, like the DFS
        p = parent[b]
        t = nxt_[p]
        return (tin_.at[b].set(t),
                nxt_.at[p].set(t + size[b]).at[b].set(t + 1))

    tin, _ = lax.fori_loop(1, B, fwd, (tin, nxt))
    return tin, tin + size


def _device_directions_sparse(n: int, edges, w_sim, parent, tri,
                              home_bubble):
    """±1 bubble-tree edge directions from the edge list, O(E log E).

    Mirrors ``sparse_dbht._directions_sparse``: per tree edge b, per
    triangle corner v, sum v's adjacency into the child side when the
    neighbor's home bubble lies in b's subtree, else the parent side,
    excluding in-triangle neighbors from both.  The per-corner subtree
    sums are prefix-sum range queries over the directed entries sorted
    by (src, home-preorder); the six in-triangle ordered pairs are
    corrected by direct CSR key lookups.  f32 accumulation — sign
    parity with the f64 oracle except exact near-ties (§11.4)."""
    B = parent.shape[0]
    tin, tout = _device_euler_tour(parent)
    home_tin = tin[home_bubble.astype(jnp.int32)]            # (n,)

    src = jnp.concatenate([edges[:, 0], edges[:, 1]]).astype(jnp.int32)
    dst = jnp.concatenate([edges[:, 1], edges[:, 0]]).astype(jnp.int32)
    w2 = jnp.concatenate([w_sim, w_sim]).astype(jnp.float32)

    key = src * n + home_tin[dst]
    order = jnp.argsort(key)
    key_s, w_s = key[order], w2[order]
    cum = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                           jnp.cumsum(w_s)])
    total = jax.ops.segment_sum(w2, src, num_segments=n)     # (n,) row sums

    skey = src * n + dst                                     # sim-weight CSR
    so = jnp.argsort(skey)
    skey_s, sw_s = skey[so], w2[so]

    def pair_w(u, v):
        q = u * n + v
        pos = jnp.clip(jnp.searchsorted(skey_s, q), 0, skey_s.shape[0] - 1)
        return jnp.where(skey_s[pos] == q, sw_s[pos], jnp.float32(0.0))

    tri = tri.astype(jnp.int32)                              # (B, 3)
    q_lo = tri * n + tin[:, None]
    q_hi = tri * n + tout[:, None]
    p_lo = jnp.searchsorted(key_s, q_lo.reshape(-1)).reshape(B, 3)
    p_hi = jnp.searchsorted(key_s, q_hi.reshape(-1)).reshape(B, 3)
    in_range = cum[p_hi] - cum[p_lo]                         # (B, 3)
    s_child = in_range.sum(axis=1)
    s_total = total[tri].sum(axis=1)
    s_parent = s_total - s_child

    for i in range(3):                    # drop the 6 in-triangle pairs
        for j in range(3):
            if i == j:
                continue
            u, v = tri[:, i], tri[:, j]
            w_e = pair_w(u, v)
            ht = home_tin[v]
            inr = (ht >= tin) & (ht < tout)
            s_child = s_child - jnp.where(inr, w_e, 0.0)
            s_parent = s_parent - jnp.where(inr, 0.0, w_e)

    direction = jnp.where(s_child >= s_parent, 1, -1).astype(jnp.int32)
    return direction.at[0].set(0)


# ---------------------------------------------------------------------------
# blocked D~ panel sweep, in-program (DESIGN.md §17.1)
# ---------------------------------------------------------------------------

def _sweep_panels_device(D_h, graph: CSRGraph, bv, bubble_cluster,
                         cluster_of, c_cap: int, bm: int):
    """``sparse_dbht._panel_fn``'s per-panel ops under one lax.scan:
    returns (bubble_of (n,), dmax, ccm (c_cap, c_cap)).  Identical
    arithmetic per panel; the host loop's np.maximum accumulation
    becomes the scan carry (max is order-invariant)."""
    h, n = D_h.shape
    bm = min(bm, n)
    starts = jnp.arange(0, n + (-n) % bm, bm, dtype=jnp.int32)

    def panel(carry, r0):
        pmax, ccm = carry
        idx = jnp.clip(r0 + jnp.arange(bm), 0, n - 1)        # dup-pad last
        A = D_h[:, idx]                                      # (h, bm)

        def body(acc, ab):
            a, brow = ab
            return jnp.minimum(acc, a[:, None] + brow[None, :]), None

        P0 = jnp.full((bm, n), INF, jnp.float32)
        P, _ = lax.scan(body, P0, (A, D_h))                  # min over hubs
        pos = graph.rows - r0
        ok = (pos >= 0) & (pos < bm)
        P = P.at[jnp.where(ok, pos, 0), graph.cols].min(
            jnp.where(ok, graph.vals, INF))                  # edge floor
        P = jnp.where(jnp.arange(n)[None, :] == idx[:, None], 0.0, P)

        md = (((P[:, bv[:, 0]] + P[:, bv[:, 1]]) + P[:, bv[:, 2]])
              + P[:, bv[:, 3]]) / 4.0                        # (bm, B)
        cl = cluster_of[idx]
        same = bubble_cluster[None, :] == cl[:, None]
        bub = jnp.argmin(jnp.where(same, md, INF), axis=1)

        pmax = jnp.maximum(pmax, jnp.max(P))
        colmax = jax.ops.segment_max(P.T, cluster_of, num_segments=c_cap)
        ccm_p = jax.ops.segment_max(colmax.T, cl, num_segments=c_cap)
        return (pmax, jnp.maximum(ccm, ccm_p)), bub.astype(jnp.int32)

    carry0 = (jnp.float32(-jnp.inf),
              jnp.full((c_cap, c_cap), -jnp.inf, jnp.float32))
    (pmax, ccm), bub = lax.scan(panel, carry0, starts)
    bubble_of = bub.reshape(-1)[:n]
    dmax = pmax + jnp.float32(1.0)
    return bubble_of, dmax, ccm


# ---------------------------------------------------------------------------
# nested HAC on the static slot grid (DESIGN.md §17.3)
# ---------------------------------------------------------------------------

def _slot_hac(D_h, graph: CSRGraph, bubble_of, counts, bounds, perm,
              v_order, m1, c_cap: int, m_cap: int, backend: str):
    """Per-cluster complete linkage over ``c_cap`` static slots.

    One lax.scan over slots (perm order = ascending minimum member, the
    staged ``nonempty`` order); inside, a lax.switch over power-of-two
    member tiers runs exactly ``sparse_dbht._cluster_hac_fn``'s program
    at the tier the staged path would pick (``m_pad = next_pow2(m)``),
    so the local merge rows are bitwise staged.  Rows are normalized to
    slot-grid ids — leaf = member position (< m_cap), internal =
    m_cap + local row — and padded to (m_cap-1, 4) with +inf heights.
    Returns (rows (c_cap, m_cap-1, 4), members (c_cap, m_cap), the HAC
    rescans summed over the slots)."""
    h, n = D_h.shape
    tiers = []
    t = 2
    while t <= m_cap:
        tiers.append(t)
        t *= 2
    tarr = jnp.asarray(tiers, jnp.int32)
    rows_csr, cols_csr, vals_csr = graph.rows, graph.cols, graph.vals

    def make_branch(m_pad: int):
        def br(op):
            idx, valid, bloc, li, lj, e_ok, m_c = op
            idx_t = idx[:m_pad]
            A = jnp.where(jnp.arange(m_pad) < m_c, D_h[:, idx_t], INF)

            def body(acc, a):
                return jnp.minimum(acc, a[:, None] + a[None, :]), None

            D0 = jnp.full((m_pad, m_pad), INF, jnp.float32)
            Dc, _ = lax.scan(body, D0, A)
            ok_t = e_ok & (li < m_pad) & (lj < m_pad)
            Dc = Dc.at[jnp.where(ok_t, li, 0),
                       jnp.where(ok_t, lj, 0)].min(
                jnp.where(ok_t, vals_csr, INF))              # edge floor
            Dc = jnp.where(jnp.eye(m_pad, dtype=bool), 0.0, Dc)
            blt = bloc[:m_pad]
            cross = blt[:, None] != blt[None, :]
            adj = Dc + jnp.where(cross, m1, 0.0)
            vt = valid[:m_pad]
            adj = jnp.where(vt[:, None] & vt[None, :], adj, INF)
            Z, rescans = hac_mod.complete_linkage_rescans(adj,
                                                          backend=backend)
            l_, r_ = Z[:, 0], Z[:, 1]                        # tier-local ids
            l_ = jnp.where(l_ < m_pad, l_, l_ + (m_cap - m_pad))
            r_ = jnp.where(r_ < m_pad, r_, r_ + (m_cap - m_pad))
            Zn = jnp.stack([l_, r_, Z[:, 2], Z[:, 3]], axis=1)
            pad = (m_cap - 1) - (m_pad - 1)
            if pad:
                Zn = jnp.concatenate(
                    [Zn, jnp.full((pad, 4), INF, jnp.float32)], axis=0)
            return Zn, rescans

        return br

    branches = [make_branch(t) for t in tiers]

    def slot_body(_, s):
        c = perm[s]
        m_c = counts[c]
        start = bounds[c]
        ar = start + jnp.arange(m_cap)
        idx = v_order[jnp.clip(ar, 0, n - 1)]                # (m_cap,)
        valid = jnp.arange(m_cap) < m_c
        lpos = jnp.full((n,), -1, jnp.int32).at[
            jnp.where(valid, idx, n)].set(
            jnp.arange(m_cap, dtype=jnp.int32), mode="drop")
        li, lj = lpos[rows_csr], lpos[cols_csr]
        e_ok = (li >= 0) & (lj >= 0)
        bloc = jnp.where(valid, bubble_of[idx], -1)
        tier_ix = jnp.minimum(jnp.sum((tarr < m_c).astype(jnp.int32)),
                              len(tiers) - 1)                # next_pow2(m)
        Zs, rescans = lax.switch(tier_ix, branches,
                                 (idx, valid, bloc, li, lj, e_ok, m_c))
        return None, (Zs, idx, rescans)

    _, (all_rows, members, rescans) = lax.scan(
        slot_body, None, jnp.arange(c_cap, dtype=jnp.int32))
    return all_rows, members, jnp.sum(rescans)


def _assemble_device(n: int, all_rows, members, counts_perm, perm, Zt,
                     c_cap: int, m_cap: int):
    """(n-1, 4) linkage from slot rows + top rows, on device.

    Replicates ``sparse_dbht._assemble_linkage``: intra rows stably
    sorted by height (flat slot-major index = the staged concatenation
    order, so ties break identically), top rows appended after, refs
    resolved through the rank permutation, sizes recomputed bottom-up
    (children precede parents: heights are monotone per slot and the
    sort is stable)."""
    R = m_cap - 1
    m_perm = counts_perm                                     # (c_cap,)
    Cn = jnp.sum((m_perm > 0).astype(jnp.int32))
    n_intra = n - Cn
    DROP = jnp.int32(2 ** 30)

    heights = all_rows[:, :, 2]                              # (c_cap, R)
    row_real = jnp.arange(R)[None, :] < (m_perm[:, None] - 1)
    keys = jnp.where(row_real, heights, INF).reshape(-1)
    order = jnp.argsort(keys, stable=True)
    rank = jnp.zeros((c_cap * R,), jnp.int32).at[order].set(
        jnp.arange(c_cap * R, dtype=jnp.int32))
    rank2 = rank.reshape(c_cap, R)

    def resolve(ids_f):                                      # (c_cap, R)
        ids = jnp.clip(ids_f, 0.0, float(2 * m_cap)).astype(jnp.int32)
        leaf = ids < m_cap
        vert = jnp.take_along_axis(members,
                                   jnp.clip(ids, 0, m_cap - 1), axis=1)
        rr = jnp.clip(ids - m_cap, 0, R - 1)
        internal = n + jnp.take_along_axis(rank2, rr, axis=1)
        return jnp.where(leaf, vert, internal)

    l_res = resolve(all_rows[:, :, 0]).reshape(-1)
    r_res = resolve(all_rows[:, :, 1]).reshape(-1)
    tgt = jnp.where(row_real.reshape(-1), rank, DROP)

    Zl = jnp.zeros((n - 1,), jnp.float32).at[tgt].set(
        l_res.astype(jnp.float32), mode="drop")
    Zr = jnp.zeros((n - 1,), jnp.float32).at[tgt].set(
        r_res.astype(jnp.float32), mode="drop")
    Zh = jnp.zeros((n - 1,), jnp.float32).at[tgt].set(
        heights.reshape(-1), mode="drop")

    # top rows: slot-leaf refs resolve to the slot's root (its last
    # local row, or the lone member), internal refs to earlier top rows
    t_ar = jnp.arange(c_cap - 1, dtype=jnp.int32)
    top_real = t_ar < (Cn - 1)

    def resolve_top(ids_f):
        ids = jnp.clip(ids_f, 0.0, float(2 * c_cap)).astype(jnp.int32)
        is_slot = ids < c_cap
        s = jnp.clip(ids, 0, c_cap - 1)
        single = m_perm[s] <= 1
        vert = members[s, 0]
        last = jnp.clip(m_perm[s] - 2, 0, R - 1)
        root_row = n + rank2[s, last]
        slot_ref = jnp.where(single, vert, root_row)
        top_ref = n + n_intra + jnp.clip(ids - c_cap, 0, c_cap - 2)
        return jnp.where(is_slot, slot_ref, top_ref)

    tl = resolve_top(Zt[:, 0])
    tr = resolve_top(Zt[:, 1])
    tgt_top = jnp.where(top_real, n_intra + t_ar, DROP)
    Zl = Zl.at[tgt_top].set(tl.astype(jnp.float32), mode="drop")
    Zr = Zr.at[tgt_top].set(tr.astype(jnp.float32), mode="drop")
    Zh = Zh.at[tgt_top].set(Zt[:, 2], mode="drop")

    li = Zl.astype(jnp.int32)
    ri = Zr.astype(jnp.int32)
    sizes0 = jnp.ones((2 * n - 1,), jnp.int32)

    def sz(g, sizes):
        return sizes.at[n + g].set(sizes[li[g]] + sizes[ri[g]])

    sizes = lax.fori_loop(0, n - 1, sz, sizes0)
    return jnp.stack([Zl, Zr, Zh, sizes[n:].astype(jnp.float32)], axis=1)


def _sparse_tail(cfg, n: int, tm: TMFGResult, w_sim, c_cap: int,
                 m_cap: int, bm: int, mark):
    """TMFG edge list + per-edge similarities → sparse DBHT outputs.

    The traceable form of ``sparse_dbht.dbht_sparse``'s device stages;
    returns a dict matching ``dbht._dbht_device_core``'s plus
    (hubs, overflow, apsp_rounds).  ``mark``, an ``obs.trace.StageMarks``,
    marks the APSP/DBHT and DBHT/HAC boundaries; the panel sweep is the
    HAC's."""
    from repro.core import dbht as dbht_mod  # local: no import cycle
    from repro.core.sparse_dbht import PANEL_ROWS  # noqa: F401

    edges = tm.edges
    # metric transform, the same f32 ops as apsp.edge_lengths
    rho = jnp.clip(w_sim.astype(jnp.float32), -1.0, 1.0)
    w_len = jnp.sqrt(jnp.maximum(2.0 * (1.0 - rho), 0.0))
    graph = csr_from_edges(n, edges, w_len)
    with jax.named_scope("apsp"):
        hubs, D_h, rounds = apsp_mod.hub_factor_sparse_rounds(
            graph, cfg.apsp_hubs, cfg.apsp_rounds)
    D_h, tm, w_sim = mark("apsp", (D_h, tm, w_sim))

    with jax.named_scope("dbht"):
        direction = _device_directions_sparse(
            n, edges, w_sim, tm.bubble_parent, tm.bubble_tri,
            tm.home_bubble)
        _, dest, conv_mask = dbht_mod._device_flow(tm.bubble_parent,
                                                   direction)
        conv_id = jnp.cumsum(conv_mask.astype(jnp.int32)) - 1
        bubble_cluster = conv_id[dest]
        cluster_of = bubble_cluster[tm.home_bubble.astype(jnp.int32)]
    bubble_cluster, cluster_of = mark("dbht", (bubble_cluster, cluster_of))

    with jax.named_scope("hac"):
        # the composed-distance sweep gives the bubbles (DBHT's last
        # output) and the HAC's scale and cross-cluster maxima at once
        bubble_of, dmax, ccm = _sweep_panels_device(
            D_h, graph, tm.bubble_verts, bubble_cluster, cluster_of, c_cap,
            bm)

        m1 = jnp.float32(2.0) * dmax                         # oracle's f32
        m2 = jnp.float32(8.0) * dmax
        off2 = m2 - m1

        # member grouping: stable sort by cluster keeps members ascending
        # within a cluster; slots ordered by minimum member (staged order)
        v_order = jnp.argsort(cluster_of, stable=True).astype(jnp.int32)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), cluster_of,
                                     num_segments=c_cap)
        bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)])
        first = v_order[jnp.clip(bounds[:c_cap], 0, n - 1)]
        min_member = jnp.where(counts > 0, first, n)         # empties last
        perm = jnp.argsort(min_member).astype(jnp.int32)

        C_total = jnp.sum(conv_mask.astype(jnp.int32))
        overflow = (C_total > c_cap) | (jnp.max(counts) > m_cap)

        all_rows, members, rescans = _slot_hac(
            D_h, graph, bubble_of, counts, bounds, perm, v_order, m1,
            c_cap, m_cap, cfg.backend)

        # top level over slots: cross-cluster maxima in perm order, the
        # staged two-add offset, empty-slot pairs masked to +inf (their
        # merges land after every real one — §14.5 pad invariance)
        ccm_p = ccm[perm][:, perm]
        sym = jnp.maximum(ccm_p, ccm_p.T)
        top_adj = (sym + m1) + off2
        sv = counts[perm] > 0
        top_adj = jnp.where(sv[:, None] & sv[None, :], top_adj, INF)
        Zt, top_rescans = hac_mod.complete_linkage_rescans(
            top_adj, backend="jnp")                          # staged's jnp
        Z = _assemble_device(n, all_rows, members, counts[perm], perm,
                             Zt, c_cap, m_cap)
    return dict(direction=direction, conv_mask=conv_mask,
                cluster_of=cluster_of, bubble_of=bubble_of, D=D_h, Z=Z,
                hubs=hubs, overflow=overflow, apsp_rounds=rounds,
                hac_rescans=rescans + top_rescans)


# ---------------------------------------------------------------------------
# the fused one-matrix body (dense/sparse dispatch is trace-time)
# ---------------------------------------------------------------------------

def _dense_tail(cfg, S, tm: TMFGResult, mark):
    """The dense formulation — exactly ``pipeline._fused_one``'s tail,
    shared by the approx configs whose staged path is dense (exact APSP
    below HUB_MIN_N, or non-hub methods), with its stage marks."""
    from repro.core import dbht as dbht_mod

    with jax.named_scope("apsp"):
        W = apsp_mod.edge_lengths(S.shape[0], tm.edges, S)
        D, rounds = apsp_mod.apsp_rounds(
            W, method=cfg.apsp_method, n_hubs=cfg.apsp_hubs,
            rounds=cfg.apsp_rounds, backend=cfg.backend)
    S, tm, D = mark("apsp", (S, tm, D))
    core = dbht_mod._dbht_device_core(
        S, tm.edges, tm.bubble_parent, tm.bubble_tri, tm.bubble_verts,
        tm.home_bubble, D, backend=cfg.backend, mark=mark)
    core["hubs"] = None
    core["overflow"] = None
    core["apsp_rounds"] = rounds
    return core


def use_sparse_tail(cfg, n: int) -> bool:
    """Trace-time dispatch: the sparse tail runs when the config asks
    for it (apsp_method="sparse") or when the approx default (lazy +
    hub) is at a size where the staged path would run hub APSP — below
    ``HUB_MIN_N`` the staged dispatcher runs exact dense APSP, and the
    fused program matches it bitwise with the dense formulation."""
    if cfg.apsp_method == "sparse":
        return True
    return (cfg.similarity == "topk" and cfg.method == "lazy"
            and cfg.apsp_method == "hub" and n >= apsp_mod.HUB_MIN_N)


def fused_from_table(cfg, n: int, *, from_x: bool = True,
                     caps: Optional[Tuple[int, int]] = None, bm: int = 512):
    """The fused approx body starting AFTER the candidate table.

    For callers that produce the (n, K) table themselves — the sharded
    funnel (core/distributed.py, DESIGN.md §17.4) builds it with
    ``dist.sharding.topk_pearson_sharded`` and hands the rest of the
    pipeline to this one jitted tail.  Returns ``tail(tv, ti, src)``
    where ``src`` is the standardized series (``from_x=True``) or the
    materialized similarity, exactly as ``sparse_lazy_tmfg`` expects;
    output dict matches :func:`fused_one`'s."""
    if cfg.similarity != "topk" or cfg.method != "lazy":
        raise ValueError(
            "fused_from_table is the lazy topk tail; got "
            f"similarity={cfg.similarity!r} method={cfg.method!r}")
    if n > FUSED_MAX_N:
        raise ValueError(
            f"fused approx path supports n <= {FUSED_MAX_N} (int32 "
            f"composite sort keys); got n={n}")
    c_cap, m_cap = fused_caps(n, caps)
    sparse = use_sparse_tail(cfg, n)
    mark = obs_trace.StageMarks(False)

    def tail(tv, ti, src):
        with jax.named_scope("tmfg"):
            tm, w_edges, counters = sparse_lazy_tmfg(tv, ti, src,
                                                     from_x=from_x)
        if sparse:
            core = _sparse_tail(cfg, n, tm, w_edges, c_cap, m_cap, bm,
                                mark)
        else:
            S_use = adjacency_from_weights(n, tm.edges, w_edges) \
                if from_x else src
            core = _dense_tail(cfg, S_use, tm, mark)
        return _with_counters(core, n, tm, counters)

    return tail


def fused_one(cfg, have_S: bool, n: int,
              caps: Optional[Tuple[int, int]] = None, bm: int = 512,
              marks: bool = False):
    """The traceable single-matrix approx/sparse pipeline body.

    The counterpart of ``pipeline._fused_one`` for the configs it used
    to reject: ``similarity="topk"`` (any APSP method) and dense
    similarity with ``apsp_method="sparse"``.  Returns a function
    ``one(arr) -> dict`` with the ``_dbht_device_core`` keys plus
    (tmfg, hubs, overflow, counters).  With ``marks`` each boundary of
    ``pipeline.STAGES`` is also a host-clock mark (DESIGN.md §15.5):
    the top-K table (or the similarity) ends ``similarity``, and the
    sweep, the slot grid, the top-level HAC and the assembly make up
    ``hac``."""
    if n > FUSED_MAX_N:
        raise ValueError(
            f"fused approx path supports n <= {FUSED_MAX_N} (int32 "
            f"composite sort keys); got n={n} — run staged "
            f"(fused=False)")
    c_cap, m_cap = fused_caps(n, caps)
    approx = cfg.similarity == "topk"
    sparse = use_sparse_tail(cfg, n)

    def one(arr):
        mark = obs_trace.StageMarks(marks)
        arr = mark("start", arr)
        counters = None
        if not approx:
            # dense similarity + sparse APSP tail (§14.6 retired)
            with jax.named_scope("similarity"):
                S = arr if have_S else ops.pearson(arr, backend=cfg.backend)
            S = mark("similarity", S)
            with jax.named_scope("tmfg"):
                tm = build_tmfg(S, method=cfg.method, prefix=cfg.prefix,
                                topk=cfg.topk)
            w_sim = S[tm.edges[:, 0], tm.edges[:, 1]]
            tm, w_sim = mark("tmfg", (tm, w_sim))
            core = _sparse_tail(cfg, n, tm, w_sim, c_cap, m_cap, bm, mark)
        else:
            kk = min(cfg.sim_k, n - 1)
            with jax.named_scope("topk"):
                if have_S:
                    # staged _topk_from_similarity's exact ops
                    S = arr.astype(jnp.float32)
                    Sd = jnp.where(jnp.eye(n, dtype=bool), -jnp.inf, S)
                    tv, ti = lax.top_k(Sd, kk)
                    ti = ti.astype(jnp.int32)
                    src, from_x = S, False
                else:
                    tv, ti = ops.topk(arr, kk, backend=cfg.backend,
                                      bm=128, bn=128)
                    src, from_x = standardize_rows(arr), True
                    S = None
            tv, ti, src = mark("similarity", (tv, ti, src))
            if cfg.method == "lazy":
                with jax.named_scope("tmfg"):
                    tm, w_edges, counters = sparse_lazy_tmfg(
                        tv, ti, src, from_x=from_x)
                tm, w_edges = mark("tmfg", (tm, w_edges))
                if sparse:
                    core = _sparse_tail(cfg, n, tm, w_edges, c_cap,
                                        m_cap, bm, mark)
                else:
                    # staged: real S from a window, else the weighted
                    # adjacency scattered from the recorded edges
                    S_use = S if S is not None else \
                        adjacency_from_weights(n, tm.edges, w_edges)
                    core = _dense_tail(cfg, S_use, tm, mark)
            else:
                # non-lazy methods run on the densified table (§13.3)
                with jax.named_scope("tmfg"):
                    Sd = _densify(tv, ti, n)
                    tm = build_tmfg(Sd, method=cfg.method,
                                    prefix=cfg.prefix, topk=cfg.topk)
                Sd, tm = mark("tmfg", (Sd, tm))
                if sparse:
                    w_sim = Sd[tm.edges[:, 0], tm.edges[:, 1]]
                    core = _sparse_tail(cfg, n, tm, w_sim, c_cap,
                                        m_cap, bm, mark)
                else:
                    core = _dense_tail(cfg, Sd, tm, mark)
        core["Z"] = mark("hac", core["Z"])
        return _with_counters(core, n, tm, counters)

    return one


def _with_counters(core: dict, n: int, tm: TMFGResult, sparse) -> dict:
    """``core`` with the TMFG and the program's loop counters
    (``pipeline.loop_counters``, DESIGN.md §15.5) under ``tmfg`` and
    ``counters``; the tails' raw counts leave the dict."""
    from repro.core.pipeline import loop_counters  # local: no import cycle

    core["tmfg"] = tm
    core["counters"] = loop_counters(
        n, tm=tm, apsp_rounds=core.pop("apsp_rounds"),
        hac_rescans=core.pop("hac_rescans"), sparse=sparse)
    return core
