"""The plain reference: Pearson similarity, lazy TMFG, DBHT and the cut.

Written for the benchmark and independent of the program under test: it
imports nothing from ``repro`` and takes nothing the program made.  It
follows the same published algorithms the program implements, so on the
same input the two must agree up to rounding:

* similarity: Pearson correlation of the rows, in float64;
* TMFG: HEAP-TMFG (Raphael & Shun 2024, Algorithm 2) with the lazy
  re-validation of popped (face, vertex) pairs, ties to the lowest index;
* DBHT (Song, Di Matteo & Aste 2012): bubble tree, edge directions by
  side strength, converging bubbles, coarse clusters by flow;
* APSP: the hub approximation of the paper's optimisation C3 (shortest
  paths from the ceil(sqrt(n)) strongest vertices, composed through them,
  floored by the direct edge), exact APSP below 200 vertices;
* the nested complete-linkage dendrogram (bubble, then cluster, then
  global), cut into k flat clusters.

Every step runs on the host in numpy and scipy except the all-pairs hub
composition, which is plain ``jax.numpy`` in row blocks so that it runs on
whatever device is present (n^2 * h min-plus steps: about 5e10 at
n = 19,412).
"""

from __future__ import annotations

import heapq
import math
from dataclasses import dataclass

import numpy as np

NEG = -np.inf
HUB_MIN_N = 200          # below this the program's "hub" APSP is exact


# ---------------------------------------------------------------------------
# similarity
# ---------------------------------------------------------------------------

def standardize(X: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Rows centred and scaled to unit norm, so Z @ Z.T is Pearson."""
    Z = np.asarray(X, dtype)
    Z = Z - Z.mean(axis=1, keepdims=True)
    return Z / np.linalg.norm(Z, axis=1, keepdims=True)


def pearson(X: np.ndarray) -> np.ndarray:
    """Pearson correlation of the rows of X, in float64."""
    Z = standardize(X)
    S = Z @ Z.T
    np.clip(S, -1.0, 1.0, out=S)
    return S


def pearson_f32(X: np.ndarray) -> np.ndarray:
    """Pearson in float32 throughout."""
    Z = standardize(X, np.float32)
    return np.clip(Z @ Z.T, -1.0, 1.0).astype(np.float64)


def pearson_bf16(X: np.ndarray) -> np.ndarray:
    """Pearson as one bfloat16 pass computes it (a TPU matmul's default
    precision): both operands rounded to bfloat16, products summed in
    float32.  The control's similarity, one precision below float32."""
    import ml_dtypes

    Z = standardize(X, np.float32).astype(ml_dtypes.bfloat16)
    Z = Z.astype(np.float32)
    return np.clip(Z @ Z.T, -1.0, 1.0).astype(np.float64)


def pearson_3pass(X: np.ndarray) -> np.ndarray:
    """Pearson as a three-pass bfloat16 product computes it: each float32
    operand split into a bfloat16 high part and a bfloat16 remainder, the
    remainder-times-remainder term dropped, the rest summed in float32
    (what ``precision=HIGH`` does on a TPU)."""
    import ml_dtypes

    Z = standardize(X, np.float32)
    hi = Z.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (Z - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    S = hi @ hi.T + (hi @ lo.T + lo @ hi.T)
    np.clip(S, -1.0, 1.0, out=S)
    return S.astype(np.float64)


# ---------------------------------------------------------------------------
# TMFG (HEAP-TMFG, lazy)
# ---------------------------------------------------------------------------

@dataclass
class TMFG:
    edges: np.ndarray          # (3n-6, 2), each row sorted
    bubble_verts: np.ndarray   # (n-3, 4)
    bubble_parent: np.ndarray  # (n-3,), -1 for the root clique
    bubble_tri: np.ndarray     # (n-3, 3) separating triangle
    home_bubble: np.ndarray    # (n,) bubble created by each vertex


def tmfg_lazy(S: np.ndarray) -> TMFG:
    """HEAP-TMFG: the four vertices of largest row sum form the first
    clique; each face caches its best uninserted vertex among the three
    corners' most-similar uninserted vertices; a max-heap of cached gains
    is popped, and a pair whose vertex is already in is re-validated."""
    n = S.shape[0]
    if S.shape != (n, n) or n < 4:
        raise ValueError(f"need a square similarity with n >= 4, got {S.shape}")
    row_sums = S.sum(axis=1) - np.diag(S)
    clique = np.sort(np.argsort(-row_sums, kind="stable")[:4])
    # M is S with the diagonal and every inserted column at -inf, so a
    # corner's best uninserted vertex is one argmax of its row
    M = np.array(S, np.float64)
    np.fill_diagonal(M, NEG)
    inserted = np.zeros(n, bool)

    def put(v):
        inserted[v] = True
        M[:, v] = NEG

    edges, faces, face_bubble = [], [], []
    bubble_verts = [tuple(int(x) for x in clique)]
    bubble_parent, bubble_tri = [-1], [(-1, -1, -1)]
    home = np.zeros(n, np.int64)
    v1, v2, v3, v4 = (int(x) for x in clique)
    edges += [(v1, v2), (v1, v3), (v1, v4), (v2, v3), (v2, v4), (v3, v4)]
    for tri in ((v1, v2, v3), (v1, v2, v4), (v1, v3, v4), (v2, v3, v4)):
        faces.append(tri)
        face_bubble.append(0)
    for v in clique:
        put(int(v))
    count = 4

    def best(face):
        a, b, c = face
        cands = [int(np.argmax(M[w])) for w in face]
        gains = [S[a, u] + S[b, u] + S[c, u] for u in cands]
        j = int(np.argmax(gains))
        return cands[j], float(gains[j])

    heap, version = [], [0, 0, 0, 0]

    def push(fi):
        v, g = best(faces[fi])
        heapq.heappush(heap, (-g, fi, version[fi], v))

    for fi in range(4):
        push(fi)
    while count < n:
        _, fi, ver, v = heapq.heappop(heap)
        if version[fi] != ver:
            continue
        if inserted[v]:
            push(fi)
            continue
        a, b, c = faces[fi]
        put(v)
        count += 1
        edges += [(min(w, v), max(w, v)) for w in (a, b, c)]
        bub = len(bubble_verts)
        bubble_verts.append((v, a, b, c))
        bubble_parent.append(face_bubble[fi])
        bubble_tri.append((a, b, c))
        home[v] = bub
        n_before = len(faces)
        faces[fi] = (v, a, b)
        face_bubble[fi] = bub
        faces += [(v, b, c), (v, a, c)]
        face_bubble += [bub, bub]
        version[fi] += 1
        version += [0, 0]
        if count < n:
            for i in (fi, n_before, n_before + 1):
                push(i)
    return TMFG(edges=np.asarray(edges, np.int64),
                bubble_verts=np.asarray(bubble_verts, np.int64),
                bubble_parent=np.asarray(bubble_parent, np.int64),
                bubble_tri=np.asarray(bubble_tri, np.int64),
                home_bubble=home)


# ---------------------------------------------------------------------------
# DBHT: bubble tree, directions, converging bubbles, coarse clusters
# ---------------------------------------------------------------------------

def _euler_tour(parent: np.ndarray):
    B = parent.shape[0]
    children = [[] for _ in range(B)]
    for b in range(1, B):
        children[parent[b]].append(b)
    tin = np.zeros(B, np.int64)
    tout = np.zeros(B, np.int64)
    t, stack = 0, [(0, False)]
    while stack:
        node, done = stack.pop()
        if done:
            tout[node] = t
            continue
        tin[node] = t
        t += 1
        stack.append((node, True))
        stack.extend((ch, False) for ch in reversed(children[node]))
    return tin, tout


def coarse_clusters(S: np.ndarray, tm: TMFG):
    """(cluster_of (n,), bubble_cluster (B,)): each bubble-tree edge points
    to the side more strongly tied (sum of TMFG similarities) to its
    separating triangle; converging bubbles have no outgoing edge; every
    bubble flows along its first outgoing edge to a converging one."""
    n = S.shape[0]
    parent = tm.bubble_parent
    B = parent.shape[0]
    tin, tout = _euler_tour(parent)
    adj = [[] for _ in range(n)]
    for a, b in tm.edges:
        adj[a].append(b)
        adj[b].append(a)
    home_tin = tin[tm.home_bubble]
    out_edges = [[] for _ in range(B)]
    for b in range(1, B):
        tri = [int(x) for x in tm.bubble_tri[b]]
        lo, hi = tin[b], tout[b]
        s_child = s_parent = 0.0
        for v in tri:
            for u in adj[v]:
                if u in tri:
                    continue
                if lo <= home_tin[u] < hi:
                    s_child += S[v, u]
                else:
                    s_parent += S[v, u]
        if s_child >= s_parent:
            out_edges[parent[b]].append(b)
        else:
            out_edges[b].append(parent[b])
    converging = [b for b in range(B) if not out_edges[b]]
    index = {b: i for i, b in enumerate(converging)}
    dest = np.full(B, -1, np.int64)
    for b in range(B):
        path, cur = [], b
        while dest[cur] == -1 and out_edges[cur]:
            path.append(cur)
            cur = out_edges[cur][0]
        d = dest[cur] if dest[cur] != -1 else cur
        dest[cur] = d
        dest[path] = d
    bubble_cluster = np.array([index[int(d)] for d in dest], np.int64)
    return bubble_cluster[tm.home_bubble], bubble_cluster


# ---------------------------------------------------------------------------
# shortest paths
# ---------------------------------------------------------------------------

def edge_lengths(S: np.ndarray, edges: np.ndarray) -> np.ndarray:
    rho = np.clip(S[edges[:, 0], edges[:, 1]], -1.0, 1.0)
    return np.sqrt(np.maximum(2.0 * (1.0 - rho), 0.0))


def hub_distances(n: int, edges: np.ndarray, w: np.ndarray):
    """(hubs, D_h (h, n)): exact shortest paths from the ceil(sqrt(n))
    vertices of largest sum of 1/length, or from every vertex below
    ``HUB_MIN_N`` (where the composition below is then exact APSP)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import dijkstra

    if n < HUB_MIN_N:
        hubs = np.arange(n)
    else:
        h = min(n, max(4, math.ceil(math.sqrt(n))))
        inv = np.where(w > 0, 1.0 / (w + 1e-6), 0.0)
        strength = (np.bincount(edges[:, 0], inv, n)
                    + np.bincount(edges[:, 1], inv, n))
        hubs = np.argsort(-strength, kind="stable")[:h]
    G = coo_matrix((np.r_[w, w], (np.r_[edges[:, 0], edges[:, 1]],
                                  np.r_[edges[:, 1], edges[:, 0]])),
                   shape=(n, n)).tocsr()
    # a zero length (rho = 1) is an edge, not a missing entry
    G.data = np.maximum(G.data, 1e-300)
    return hubs, dijkstra(G, directed=False, indices=hubs)


def _hub_blocks(D_h: np.ndarray, edges, w, rows: int):
    """Yield (r0, block) of the composed distance matrix, ``rows`` rows at
    a time: min over hubs of D_h[h, u] + D_h[h, v], floored by the direct
    edge, zero on the diagonal.  Plain jax.numpy in float32."""
    import jax
    import jax.numpy as jnp

    n = D_h.shape[1]
    Dh = jnp.asarray(D_h, jnp.float32)
    e = np.concatenate([edges, edges[:, ::-1]])
    ec = jnp.asarray(e[:, 1], jnp.int32)
    ew = jnp.asarray(np.r_[w, w], jnp.float32)

    @jax.jit
    def block(Dh, r0, er, ec, ew):
        cols = jax.lax.dynamic_slice_in_dim(Dh, r0, rows, axis=1)   # (h, R)

        def body(i, acc):
            return jnp.minimum(acc, cols[i][:, None] + Dh[i][None, :])

        acc = jax.lax.fori_loop(0, Dh.shape[0], body,
                                jnp.full((rows, n), jnp.inf, jnp.float32))
        acc = acc.at[er, ec].min(ew, mode="drop")
        diag = jnp.arange(rows)
        return acc.at[diag, r0 + diag].set(0.0, mode="drop")

    for r0 in range(0, n, rows):
        r0 = min(r0, n - rows)
        sel = (e[:, 0] >= r0) & (e[:, 0] < r0 + rows)
        # fixed-size edge arrays: out-of-range rows are dropped
        er = np.full(len(e), rows, np.int32)
        er[sel] = e[sel, 0] - r0
        yield r0, block(Dh, r0, jnp.asarray(er), ec, ew)


def composed_distances(D_h, edges, w, rows: int = 512) -> np.ndarray:
    """The whole (n, n) hub-composed distance matrix (small n only)."""
    n = D_h.shape[1]
    rows = min(rows, n)
    D = np.empty((n, n), np.float32)
    for r0, blk in _hub_blocks(D_h, edges, w, rows):
        D[r0:r0 + rows] = np.asarray(blk)
    return np.minimum(D, D.T)


def cluster_max_distances(D_h, edges, w, cluster_of, c: int,
                          rows: int = 512) -> np.ndarray:
    """(c, c): the largest composed distance between any member of one
    coarse cluster and any of another, without holding (n, n)."""
    import jax
    import jax.numpy as jnp

    n = D_h.shape[1]
    rows = min(rows, n)
    lab = jnp.asarray(cluster_of, jnp.int32)
    seg = jax.jit(lambda blk, r: jax.ops.segment_max(
        jax.ops.segment_max(blk.T, lab, c).T, r, c))
    M = np.full((c, c), -np.inf, np.float32)
    done = np.zeros(n, bool)
    for r0, blk in _hub_blocks(D_h, edges, w, rows):
        # overlapping last block: rows already counted are re-maxed, which
        # leaves a maximum unchanged
        M = np.maximum(M, np.asarray(seg(blk, lab[r0:r0 + rows])))
        done[r0:r0 + rows] = True
    assert done.all()
    return np.maximum(M, M.T)


# ---------------------------------------------------------------------------
# complete linkage and the cut
# ---------------------------------------------------------------------------

def complete_linkage(D: np.ndarray) -> np.ndarray:
    """scipy's complete linkage of a dense symmetric distance matrix."""
    from scipy.cluster.hierarchy import linkage
    from scipy.spatial.distance import squareform

    D = np.array(D, np.float64)
    np.fill_diagonal(D, 0.0)
    return linkage(squareform(D, checks=False), method="complete")


def cut(Z: np.ndarray, n: int, k: int) -> np.ndarray:
    """k flat clusters: merges applied in height order until k remain."""
    k = max(1, min(k, n))
    parent = np.arange(n + len(Z))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    clusters = n
    for idx in np.argsort(Z[:, 2], kind="stable"):
        if clusters <= k:
            break
        new = n + int(idx)
        parent[find(int(Z[idx, 0]))] = new
        parent[find(int(Z[idx, 1]))] = new
        clusters -= 1
    roots, labels = {}, np.zeros(n, np.int64)
    for v in range(n):
        labels[v] = roots.setdefault(find(v), len(roots))
    return labels


FULL_MAX_N = 4096        # the nested cut below the coarse level holds (n, n)


def dbht_labels(S: np.ndarray, tm: TMFG, k: int) -> np.ndarray:
    """k flat clusters of the nested DBHT dendrogram.

    The dendrogram merges inside bubbles, then inside coarse clusters,
    then across them, each level by complete linkage of the composed
    distances.  When k is at most the number c of coarse clusters the cut
    only joins whole clusters, so it needs just the (c, c) largest
    cross-cluster distances.  Otherwise the bubble level matters: every
    vertex joins the bubble of its cluster with the least mean distance to
    the bubble's four vertices, and the whole nested linkage is built."""
    n = S.shape[0]
    cluster_of, bubble_cluster = coarse_clusters(S, tm)
    c = int(cluster_of.max()) + 1
    w = edge_lengths(S, tm.edges)
    _, D_h = hub_distances(n, tm.edges, w)
    if k <= c:
        M = cluster_max_distances(D_h, tm.edges, w, cluster_of, c)
        return cut(complete_linkage(M), c, k)[cluster_of]
    if n > FULL_MAX_N:
        raise ValueError(f"k={k} > {c} coarse clusters at n={n}: the "
                         "bubble-level cut needs the (n, n) distances")
    D = composed_distances(D_h, tm.edges, w).astype(np.float64)
    Bn = tm.bubble_verts.shape[0]
    mean = D[:, tm.bubble_verts.reshape(-1)].reshape(n, Bn, 4).mean(axis=2)
    mean[bubble_cluster[None, :] != cluster_of[:, None]] = np.inf
    bubble_of = np.argmin(mean, axis=1)
    dmax = D.max() + 1.0
    adj = (D + np.where(bubble_of[:, None] != bubble_of[None, :],
                        2.0 * dmax, 0.0)
           + np.where(cluster_of[:, None] != cluster_of[None, :],
                      6.0 * dmax, 0.0))
    return cut(complete_linkage(adj), n, k)


# ---------------------------------------------------------------------------
# agreement of two partitions
# ---------------------------------------------------------------------------

def ari(a, b) -> float:
    """Adjusted Rand index of two labelings."""
    a = np.unique(np.asarray(a), return_inverse=True)[1]
    b = np.unique(np.asarray(b), return_inverse=True)[1]
    table = np.zeros((a.max() + 1, b.max() + 1), np.int64)
    np.add.at(table, (a, b), 1)

    def comb2(x):
        x = np.asarray(x, np.float64)
        return (x * (x - 1) / 2).sum()

    index = comb2(table)
    ea, eb = comb2(table.sum(axis=1)), comb2(table.sum(axis=0))
    total = comb2([len(a)])
    expected = ea * eb / total if total else 0.0
    top = (ea + eb) / 2 - expected
    if top == 0:
        return 1.0
    return float((index - expected) / top)
