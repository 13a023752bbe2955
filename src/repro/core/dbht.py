"""DBHT — Directed Bubble Hierarchy Tree clustering on a TMFG.

Implements the DBHT method (Song et al. 2012) as described by the paper's
§2, with BOTH halves of the stage expressible on device (DESIGN.md §11):

  * ``impl="device"`` (production default) — the whole stage (bubble-tree
    ancestry, edge directions, converging-bubble flow, fine assignment,
    APSP and the nested HAC) is one jitted, vmappable JAX program; a
    batch of matrices finishes DBHT under a single ``vmap`` with one
    device→host transfer (:func:`dbht_batch`).  The recursive host walks
    are replaced by fixed-point pointer jumping (DESIGN.md §11.2).
  * ``impl="host"`` — the original per-matrix numpy tree walk, kept as
    the reference oracle; device and host are label- and
    linkage-identical (the §11.4 parity contract, pinned by
    tests/test_dbht_device.py).

Pipeline (both impls compute exactly these steps):
  1. bubble tree: node per 4-clique (from the TMFG insertion log), edge per
     shared separating triangle — a tree with n-3 nodes.
  2. edge directions: the tree edge between bubbles (c, p) with separating
     triangle t points toward the side whose vertices are more strongly
     connected to t (aggregate TMFG similarity strength).  Clique-tree
     running intersection ⇒ the two sides partition V \\ t, and a vertex's
     side is its home bubble's side.
  3. converging bubbles: only incoming edges (local attractors).
  4. coarse clusters: every bubble flows along its strongest outgoing edge
     until it reaches a converging bubble; a vertex inherits its home
     bubble's destination.
  5. fine structure: each vertex is re-assigned to the bubble in its
     cluster's basin with minimal mean APSP distance.
  6. dendrogram: one complete-linkage run on the offset-adjusted APSP
     matrix (hac.hierarchical_offsets) = nested intra-bubble/intra-cluster/
     inter-cluster HAC.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

import repro.core.apsp as apsp_mod
import repro.core.config as config_mod
import repro.core.hac as hac_mod
import repro.core.jitcache as jitcache
import repro.core.tmfg as tmfg_mod
from repro.core.config import PipelineConfig


@dataclass
class DBHTResult:
    linkage: np.ndarray          # (n-1, 4) scipy-style dendrogram
    cluster_of: np.ndarray       # (n,) coarse cluster id per vertex
    bubble_of: np.ndarray        # (n,) fine bubble assignment per vertex
    converging: np.ndarray       # ids of converging bubbles
    direction: np.ndarray        # (n-4,) +1 edge points parent->child else -1
    apsp: np.ndarray             # (n, n) distances — or the hub factor
    #                              D_h (h, n) from the sparse tail (§14.3)
    hubs: Optional[np.ndarray] = None  # (h,) hub vertex ids (sparse tail)

    def labels(self, k: int) -> np.ndarray:
        n = self.cluster_of.shape[0]
        return hac_mod.cut_linkage(self.linkage, n, k)


# ---------------------------------------------------------------------------
# host-side tree logic (the reference oracle — DESIGN.md §11.4)
# ---------------------------------------------------------------------------

def _euler_tour(parent: np.ndarray):
    """Iterative DFS in/out times for the bubble tree (parents precede kids)."""
    B = parent.shape[0]
    children = [[] for _ in range(B)]
    for b in range(1, B):
        children[parent[b]].append(b)
    tin = np.zeros(B, np.int64)
    tout = np.zeros(B, np.int64)
    t = 0
    stack = [(0, False)]
    while stack:
        node, done = stack.pop()
        if done:
            tout[node] = t
            continue
        tin[node] = t
        t += 1
        stack.append((node, True))
        for ch in reversed(children[node]):
            stack.append((ch, False))
    return tin, tout


def _edge_directions(S: np.ndarray, edges: np.ndarray, bubble_parent: np.ndarray,
                     bubble_tri: np.ndarray, home_bubble: np.ndarray):
    """Direction of every bubble-tree edge by side connection strength.

    Edge b (b>=1) connects bubble b to parent p with separating triangle t.
    side(b) = vertices whose home bubble lies in subtree(b); strength of a
    side is the sum of TMFG edge weights from t's vertices into that side.
    Returns +1 if the edge points p->b (subtree side stronger) else -1.
    """
    n = S.shape[0]
    B = bubble_parent.shape[0]
    tin, tout = _euler_tour(bubble_parent)

    # CSR-ish adjacency of the TMFG
    adj = [[] for _ in range(n)]
    for a, b in edges:
        adj[int(a)].append(int(b))
        adj[int(b)].append(int(a))

    home_tin = tin[home_bubble]  # (n,)
    direction = np.zeros(B, np.int64)  # index by child bubble id; [0] unused
    for b in range(1, B):
        t = bubble_tri[b]
        tset = set(int(x) for x in t)
        lo, hi = tin[b], tout[b]
        s_child = 0.0
        s_parent = 0.0
        for v in t:
            for u in adj[int(v)]:
                if u in tset:
                    continue
                if lo <= home_tin[u] < hi:
                    s_child += S[int(v), u]
                else:
                    s_parent += S[int(v), u]
        direction[b] = 1 if s_child >= s_parent else -1
    return direction, tin, tout


def _flow_to_converging(bubble_parent, direction, strength=None):
    """Follow outgoing edges (ties: strongest) until a converging bubble.

    Edge between child b and parent p: direction[b]=+1 means p->b (outgoing
    for p, incoming for b); -1 means b->p.  Converging bubble: no outgoing.
    Returns (flow destination per bubble, converging bubble ids).
    """
    B = bubble_parent.shape[0]
    out_edges = [[] for _ in range(B)]  # (target bubble)
    for b in range(1, B):
        p = bubble_parent[b]
        if direction[b] == 1:
            out_edges[p].append(b)
        else:
            out_edges[b].append(p)
    converging = np.array([b for b in range(B) if not out_edges[b]],
                          dtype=np.int64)
    dest = np.full(B, -1, np.int64)

    def walk(b):
        path = []
        cur = b
        while dest[cur] == -1 and out_edges[cur]:
            path.append(cur)
            cur = out_edges[cur][0]  # tree ⇒ no cycles along out-edges
        d = dest[cur] if dest[cur] != -1 else cur
        dest[cur] = d
        for x in path:
            dest[x] = d
        return d

    for b in range(B):
        if dest[b] == -1:
            walk(b)
    return dest, converging


def _dbht_host(S, tmfg, *, apsp_method, apsp_backend, precomputed_apsp,
               apsp_hubs: int = 0, apsp_rounds: int = 0):
    """The original per-matrix numpy walk (reference oracle)."""
    S = np.asarray(S, dtype=np.float64)
    n = S.shape[0]
    edges = np.asarray(tmfg.edges)
    bubble_parent = np.asarray(tmfg.bubble_parent)
    bubble_tri = np.asarray(tmfg.bubble_tri)
    bubble_verts = np.asarray(tmfg.bubble_verts)
    home_bubble = np.asarray(tmfg.home_bubble)
    B = bubble_parent.shape[0]

    # 2-3. directions and converging bubbles (host, O(n))
    direction, tin, tout = _edge_directions(
        S, edges, bubble_parent, bubble_tri, home_bubble)
    dest, converging = _flow_to_converging(bubble_parent, direction)
    conv_index = {int(c): i for i, c in enumerate(converging)}
    cluster_of = np.array([conv_index[int(dest[home_bubble[v]])]
                           for v in range(n)], dtype=np.int64)

    # 7. APSP on device (the heavy stage; hub-approximate by default = C3)
    if precomputed_apsp is not None:
        D = np.asarray(precomputed_apsp)
    else:
        W = apsp_mod.edge_lengths(n, jnp.asarray(edges), jnp.asarray(S))
        D = np.asarray(apsp_mod.apsp(W, method=apsp_method,
                                     n_hubs=apsp_hubs, rounds=apsp_rounds,
                                     backend=apsp_backend))

    # 8. fine bubble assignment: nearest (mean APSP) bubble in the cluster
    # basin.  basin(c) = bubbles flowing to converging bubble c.
    bubble_cluster = np.array([conv_index[int(dest[b])] for b in range(B)],
                              dtype=np.int64)
    mean_dist = D[:, bubble_verts.reshape(-1)].reshape(n, B, 4).mean(axis=2)
    same = bubble_cluster[None, :] == cluster_of[:, None]          # (n, B)
    masked = np.where(same, mean_dist, np.inf)
    bubble_of = np.argmin(masked, axis=1)

    # 9. nested dendrogram via one offset-adjusted complete linkage (device)
    adj = hac_mod.hierarchical_offsets(
        jnp.asarray(D, dtype=jnp.float32),
        jnp.asarray(bubble_of), jnp.asarray(cluster_of))
    Z = np.asarray(hac_mod.complete_linkage(adj))

    return DBHTResult(linkage=Z, cluster_of=cluster_of, bubble_of=bubble_of,
                      converging=converging, direction=direction[1:],
                      apsp=D)


# ---------------------------------------------------------------------------
# device-side tree logic (DESIGN.md §11) — jit/vmap-traceable throughout
# ---------------------------------------------------------------------------

def _anc_matrix(bubble_parent: jax.Array) -> jax.Array:
    """Ancestor-or-self indicator of the bubble tree by pointer doubling.

    ``anc[b, a]`` is True iff a lies on the path b → root (including
    b itself).  The parent pointers are squared ⌈log2 B⌉+1 times; each
    step ORs in the ancestor set reachable through the current jump
    pointer, so subtree membership — the Euler-tour interval test of the
    host oracle — becomes one gathered row lookup (DESIGN.md §11.1).
    """
    B = bubble_parent.shape[0]
    ptr = jnp.where(bubble_parent < 0, jnp.arange(B, dtype=jnp.int32),
                    bubble_parent.astype(jnp.int32))
    anc = jnp.eye(B, dtype=bool)
    steps = int(math.ceil(math.log2(max(B, 2)))) + 1

    def body(_, carry):
        anc, ptr = carry
        return anc | anc[ptr], ptr[ptr]

    anc, _ = lax.fori_loop(0, steps, body, (anc, ptr))
    return anc


def _device_directions(S: jax.Array, edges: jax.Array, bubble_tri: jax.Array,
                       home_bubble: jax.Array, anc: jax.Array) -> jax.Array:
    """Edge directions for all B-1 tree edges in one (B, n) reduction.

    Side strength of edge b = sum of TMFG edge weights from the
    separating triangle's corners into each side; a vertex u is on the
    child side iff b is an ancestor-or-self of u's home bubble
    (DESIGN.md §11.1).  Returns (B,) int32 with [0] fixed to 0 (unused).
    """
    n = S.shape[0]
    A_w = tmfg_mod.tmfg_adjacency(n, edges, S)            # (n, n), 0 off-graph
    tri = bubble_tri                                       # (B, 3)
    rows = A_w[tri[:, 0]] + A_w[tri[:, 1]] + A_w[tri[:, 2]]   # (B, n)
    cols = jnp.arange(n)
    in_tri = ((cols[None, :] == tri[:, 0:1])
              | (cols[None, :] == tri[:, 1:2])
              | (cols[None, :] == tri[:, 2:3]))            # (B, n)
    member = anc[home_bubble].T                            # (B, n)
    w = jnp.where(in_tri, 0.0, rows)
    s_child = jnp.sum(jnp.where(member, w, 0.0), axis=1)
    s_parent = jnp.sum(jnp.where(member, 0.0, w), axis=1)
    direction = jnp.where(s_child >= s_parent, 1, -1).astype(jnp.int32)
    return direction.at[0].set(0)


def _device_flow(bubble_parent: jax.Array, direction: jax.Array):
    """Flow-to-converging by fixed-point pointer jumping (DESIGN.md §11.2).

    Each bubble's single outgoing successor mirrors the host walk's
    ``out_edges[cur][0]``: the parent when this bubble's own edge points
    up (its key — the edge id — is smaller than any child edge's), else
    the lowest-id child edge pointing down, else itself (converging).
    Squaring the successor map ⌈log2 B⌉+1 times reaches the converging
    fixed points without any recursion.  Returns (nxt, dest, conv_mask).
    """
    B = bubble_parent.shape[0]
    ar = jnp.arange(B, dtype=jnp.int32)
    parent = bubble_parent.astype(jnp.int32)
    safe_parent = jnp.where(ar >= 1, parent, 0)
    child_key = jnp.where((ar >= 1) & (direction == 1), ar, B)
    first_child = jnp.full((B,), B, jnp.int32).at[safe_parent].min(
        child_key.astype(jnp.int32))
    to_parent = (ar >= 1) & (direction == -1)
    nxt = jnp.where(to_parent, safe_parent,
                    jnp.where(first_child < B, first_child, ar))

    steps = int(math.ceil(math.log2(max(B, 2)))) + 1
    dest = lax.fori_loop(0, steps, lambda _, d: d[d], nxt)
    conv_mask = nxt == ar
    return nxt, dest, conv_mask


def _device_assign(D: jax.Array, bubble_verts: jax.Array,
                   home_bubble: jax.Array, dest: jax.Array,
                   conv_mask: jax.Array):
    """Coarse clusters + fine bubble re-assignment on device.

    Converging bubbles are numbered in ascending bubble id (matching the
    host oracle's enumeration); the fine stage picks, per vertex, the
    basin bubble with minimal mean APSP distance to its 4 defining
    vertices — one masked (n, B) argmin (DESIGN.md §11.1).
    """
    conv_id = jnp.cumsum(conv_mask.astype(jnp.int32)) - 1
    bubble_cluster = conv_id[dest]                         # (B,)
    cluster_of = bubble_cluster[home_bubble]               # (n,)

    bv = bubble_verts                                      # (B, 4)
    # mean over the 4 defining vertices, summed in the oracle's
    # (sequential) association so host and device round identically
    md = (((D[:, bv[:, 0]] + D[:, bv[:, 1]]) + D[:, bv[:, 2]])
          + D[:, bv[:, 3]]) / 4.0                          # (n, B)
    same = bubble_cluster[None, :] == cluster_of[:, None]
    bubble_of = jnp.argmin(jnp.where(same, md, jnp.inf), axis=1)
    return cluster_of, bubble_of.astype(jnp.int32), bubble_cluster


def _dbht_device_core(S, edges, bubble_parent, bubble_tri, bubble_verts,
                      home_bubble, D, *, backend: str = "auto", mark=None):
    """Traceable single-matrix device DBHT: TMFG arrays + APSP → outputs.

    Everything is fixed-shape, so the whole stage jit-compiles and vmaps
    over a batch axis (DESIGN.md §11).  ``conv_mask`` stands in for the
    variable-length converging-id list until the (single) host transfer.
    ``hac_rescans`` is the nested HAC's rescan count (DESIGN.md §15.5);
    ``mark``, an ``obs.trace.StageMarks``, marks the DBHT/HAC boundary.
    """
    with jax.named_scope("dbht"):
        anc = _anc_matrix(bubble_parent)
        direction = _device_directions(S, edges, bubble_tri, home_bubble,
                                       anc)
        _, dest, conv_mask = _device_flow(bubble_parent, direction)
        cluster_of, bubble_of, _ = _device_assign(
            D, bubble_verts, home_bubble, dest, conv_mask)
        adj = hac_mod.hierarchical_offsets(D, bubble_of, cluster_of)
    if mark is not None:
        adj = mark("dbht", adj)
    with jax.named_scope("hac"):
        Z, rescans = hac_mod.complete_linkage_rescans(adj, backend=backend)
    return dict(direction=direction, conv_mask=conv_mask,
                cluster_of=cluster_of, bubble_of=bubble_of, D=D, Z=Z,
                hac_rescans=rescans)


def _device_dbht_jit(apsp_method: str, apsp_hubs: int, apsp_rounds: int,
                     backend: str, precomputed: bool, batched: bool,
                     shape=None):
    """Jitted (optionally vmapped) device DBHT program per static config
    AND input shape, held in the shared bounded executable cache
    (DESIGN.md §12.3) so repeated calls reuse one compiled executable
    without the unbounded growth of the old per-module lru_cache —
    shape is part of the key so evicting an entry actually frees its
    compiled code (a shape-free key would keep one hot jit callable
    accumulating per-shape XLA executables forever)."""

    def build():
        def with_apsp(S, edges, bp, bt, bv, hb):
            W = apsp_mod.edge_lengths(S.shape[0], edges, S)
            D = apsp_mod.apsp(W, method=apsp_method, n_hubs=apsp_hubs,
                              rounds=apsp_rounds, backend=backend)
            return _dbht_device_core(S, edges, bp, bt, bv, hb, D,
                                     backend=backend)

        def with_D(S, edges, bp, bt, bv, hb, D):
            return _dbht_device_core(S, edges, bp, bt, bv, hb, D,
                                     backend=backend)

        f = with_D if precomputed else with_apsp
        return jax.jit(jax.vmap(f) if batched else f)

    return jitcache.cached(("dbht", apsp_method, apsp_hubs, apsp_rounds,
                            backend, precomputed, batched, shape), build)


def _result_from_device(out, b=None) -> DBHTResult:
    """DBHTResult from (host copies of) the device-core output dict."""
    pick = (lambda a: a) if b is None else (lambda a: a[b])
    conv = np.flatnonzero(pick(out["conv_mask"])).astype(np.int64)
    return DBHTResult(
        linkage=pick(out["Z"]), cluster_of=pick(out["cluster_of"]),
        bubble_of=pick(out["bubble_of"]), converging=conv,
        direction=pick(out["direction"])[1:], apsp=pick(out["D"]))


def _tmfg_args(tmfg):
    return (jnp.asarray(tmfg.edges), jnp.asarray(tmfg.bubble_parent),
            jnp.asarray(tmfg.bubble_tri), jnp.asarray(tmfg.bubble_verts),
            jnp.asarray(tmfg.home_bubble))


def _apsp_knobs(config, kwargs):
    """Resolve the APSP knobs from ``config`` XOR loose kwargs
    (config.check_no_conflict enforces the XOR); without a config, None
    kwargs take the dataclass defaults."""
    config_mod.check_no_conflict(config, **kwargs)
    if config is not None:
        return (config.apsp_method, config.apsp_hubs, config.apsp_rounds,
                config.backend)
    d = PipelineConfig()
    backend = kwargs.get("backend", kwargs.get("apsp_backend"))
    return (kwargs.get("apsp_method") or d.apsp_method,
            d.apsp_hubs if kwargs.get("apsp_hubs") is None
            else kwargs["apsp_hubs"],
            d.apsp_rounds if kwargs.get("apsp_rounds") is None
            else kwargs["apsp_rounds"],
            backend or d.backend)


def dbht_batch(S, tmfg, *, apsp_method: Optional[str] = None,
               backend: Optional[str] = None,
               apsp_hubs: Optional[int] = None,
               apsp_rounds: Optional[int] = None,
               config: Optional[PipelineConfig] = None,
               limit: Optional[int] = None,
               edge_weights=None) -> List[DBHTResult]:
    """Batched device DBHT: (B, n, n) similarities + batched TMFG arrays.

    The whole batch — APSP, tree directions, flow, fine assignment, HAC —
    runs as ONE vmapped jitted program followed by a single device→host
    transfer; no per-matrix host work happens until the final (cheap)
    result unpacking (DESIGN.md §11.4).  ``limit`` slices the transfer:
    pad entries of a bucketed micro-batch pay device FLOPs only.
    ``config`` supplies the APSP knobs + backend from one
    :class:`PipelineConfig` instead of the loose kwargs (combining the
    two surfaces is rejected, as in ``PipelineConfig.resolve``).
    """
    apsp_method, apsp_hubs, apsp_rounds, backend = _apsp_knobs(
        config, dict(apsp_method=apsp_method, apsp_hubs=apsp_hubs,
                     apsp_rounds=apsp_rounds, backend=backend))
    if apsp_method == "sparse":
        # the sparse tail is host-orchestrated per entry (DESIGN.md
        # §14.6) — no dense (B, n, n) program to vmap.  S entries (or
        # per-entry edge weights) are sliced on host.
        from repro.core import sparse_dbht
        B = (len(S) if S is not None else len(edge_weights))
        B_out = B if limit is None else min(limit, B)
        outs = []
        for b in range(B_out):
            tm_b = jax.tree.map(lambda a: np.asarray(a)[b], tmfg)
            outs.append(sparse_dbht.dbht_sparse(
                None if S is None else np.asarray(S[b]), tm_b,
                edge_weights=(None if edge_weights is None
                              else np.asarray(edge_weights[b])),
                n_hubs=apsp_hubs, rounds=apsp_rounds, backend=backend))
        return outs
    S_b = jnp.asarray(S, jnp.float32)
    B = S_b.shape[0]
    B_out = B if limit is None else min(limit, B)
    fn = _device_dbht_jit(apsp_method, apsp_hubs, apsp_rounds, backend,
                          False, True, S_b.shape)
    out = fn(S_b, *_tmfg_args(tmfg))
    out = jax.device_get({k: v[:B_out] for k, v in out.items()})
    return [_result_from_device(out, b) for b in range(B_out)]


# ---------------------------------------------------------------------------
# main entry
# ---------------------------------------------------------------------------

def dbht(S, tmfg, *, apsp_method: Optional[str] = None,
         apsp_backend: Optional[str] = None,
         apsp_hubs: Optional[int] = None, apsp_rounds: Optional[int] = None,
         precomputed_apsp: Optional[np.ndarray] = None,
         config: Optional[PipelineConfig] = None,
         impl: Optional[str] = None,
         edge_weights: Optional[np.ndarray] = None) -> DBHTResult:
    """Run DBHT on a TMFG (accepts JAX or numpy TMFGResult fields).

    ``apsp_method="sparse"`` routes to the edge-list tail
    (core/sparse_dbht.py); there ``S`` may be None when ``edge_weights``
    — the similarity per TMFG edge, data not config — carries the edge
    values instead, so no (n, n) array is ever formed (DESIGN.md §14.3).

    ``impl`` selects the execution strategy (DESIGN.md §11.4):
    ``"device"`` (default) runs the entire stage as one jitted JAX
    program with a single device→host transfer; ``"host"`` is the numpy
    reference walk.  Both return identical labels, linkage, converging
    set and assignments on the same inputs (the parity contract).
    ``config`` supplies apsp_method/hubs/rounds, backend and the impl
    from one :class:`PipelineConfig` instead of the loose kwargs;
    combining the two surfaces is rejected — except ``impl``, the one
    deliberate override, so the parity tests can pin both impls of one
    config.
    """
    apsp_method, apsp_hubs, apsp_rounds, apsp_backend = _apsp_knobs(
        config, dict(apsp_method=apsp_method, apsp_hubs=apsp_hubs,
                     apsp_rounds=apsp_rounds, apsp_backend=apsp_backend))
    if impl is None:
        impl = config.dbht_impl if config is not None else "device"
    if apsp_method == "sparse" and precomputed_apsp is None:
        # the edge-list tail (DESIGN.md §14): host-orchestrated staged
        # device programs, never an (n, n) buffer; impl="host" is its
        # densified oracle (validated there)
        from repro.core import sparse_dbht
        return sparse_dbht.dbht_sparse(
            S, tmfg, edge_weights=edge_weights, n_hubs=apsp_hubs,
            rounds=apsp_rounds, backend=apsp_backend, impl=impl)
    if impl == "host":
        return _dbht_host(S, tmfg, apsp_method=apsp_method,
                          apsp_backend=apsp_backend,
                          apsp_hubs=apsp_hubs, apsp_rounds=apsp_rounds,
                          precomputed_apsp=precomputed_apsp)
    if impl != "device":
        raise ValueError(f"unknown DBHT impl {impl!r}")

    S_j = jnp.asarray(S, jnp.float32)
    if precomputed_apsp is not None:
        fn = _device_dbht_jit(apsp_method, apsp_hubs, apsp_rounds,
                              apsp_backend, True, False, S_j.shape)
        out = fn(S_j, *_tmfg_args(tmfg),
                 jnp.asarray(precomputed_apsp, jnp.float32))
    else:
        fn = _device_dbht_jit(apsp_method, apsp_hubs, apsp_rounds,
                              apsp_backend, False, False, S_j.shape)
        out = fn(S_j, *_tmfg_args(tmfg))
    return _result_from_device(jax.device_get(out))
