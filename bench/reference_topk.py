"""The plain reference of the a-TMFG: the TMFG built on a top-K candidate
table, then the same DBHT, APSP and cut as the dense reference.

A configuration selects it with ``"reference": "topk"``; it is the
reference of ``PipelineConfig.approx(sim_k=K)``, with K from the
configuration's ``pipeline.args.sim_k`` (clamped to n - 1, as the
program clamps it).  It imports nothing from ``repro`` and never holds an
(n, n) array: the similarity is read a block of rows at a time, or pair
by pair.

The semantics are those of ``approx/sparse_tmfg.py`` and the sparse tail
of ``core/fused_approx.py``:

* table: each row's K most similar other rows, by Pearson computed in
  blocks of ``ROWS`` rows, ordered by value with ties to the lower index
  (``lax.top_k``'s order);
* clique: the four largest sums of a row's K table values, ties to the
  lower index;
* build: HEAP-TMFG as ``reference.tmfg_lazy`` builds it, except that a
  corner's best uninserted vertex is the first uninserted entry of its
  table row, and, where every entry is inserted, the argmax of the
  exact row over the uninserted vertices (the program's fallback);
* DBHT, hub APSP and the cut: ``reference.py``'s own functions, handed
  the similarity of the TMFG's edges alone (they read nothing else).

Departures from the program, each for a reason:

* Values are float64 and every gain and edge value is a dot of two
  standardized rows.  The program reads a value from the table where the
  pair is in it and computes the dot where it is not; both are the same
  number up to float32 rounding, which is what the comparison measures.
* The clique's row sums are float64 sums of float64 values (the program
  sums float32 values in float32).
* Only a cut into k <= c flat clusters, c the number of coarse clusters,
  is computed: it joins whole clusters, so it needs only the (c, c)
  largest cross-cluster distances.  A cut below the coarse level needs
  the (n, n) distances, and ``answer`` raises there.
* The program scans a row's K candidates on every lookup, and computes
  the exact row on every lookup whose candidates are all inserted.  This
  keeps a position per row that only moves forward (an inserted vertex
  stays inserted, so the first uninserted entry never moves back), and
  where a row's candidates run out it computes the exact row once and
  takes its ``EXTRA`` best uninserted vertices, in the table's order, as
  the row's next candidates: the first uninserted of them is the exact
  row's argmax over the uninserted vertices for as long as one is left.
  Both find the same vertex.
"""

from __future__ import annotations

import copy
import heapq

import numpy as np

import reference as ref

ROWS = 512               # rows of the similarity held at a time
EXTRA = 256              # candidates taken from an exact row at a time


class Pearson:
    """The Pearson similarity of the rows of X, a block at a time.

    ``form`` is the precision it is computed in: ``f64`` (the reference),
    or a control's: ``f32``, ``bf16`` (both operands rounded to bfloat16,
    products summed in float32: one default-precision TPU pass), ``3pass``
    (bfloat16 high and low parts, the low-times-low term dropped: what
    ``precision=HIGH`` computes).  Every value is clipped to [-1, 1]."""

    def __init__(self, X: np.ndarray, form: str = "f64"):
        if form == "f64":
            terms = [(ref.standardize(X),) * 2]
        else:
            import ml_dtypes

            Z = ref.standardize(X, np.float32)
            hi = Z.astype(ml_dtypes.bfloat16).astype(np.float32)
            if form == "f32":
                terms = [(Z, Z)]
            elif form == "bf16":
                terms = [(hi, hi)]
            elif form == "3pass":
                lo = (Z - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
                terms = [(hi, hi), (hi, lo), (lo, hi)]
            else:
                raise ValueError(f"unknown similarity form {form!r}")
        self.terms = terms
        self.shape = (X.shape[0], X.shape[0])

    def _sum(self, parts):
        # hi.hi + (hi.lo + lo.hi), the order pearson_3pass sums in
        S = parts[0] if len(parts) == 1 else parts[0] + (parts[1] + parts[2])
        # np.clip, without its wrapper's cost on the build's 3 x 3 blocks
        S = np.minimum(np.maximum(S, -1.0, out=S), 1.0, out=S)
        return S.astype(np.float64, copy=False)

    def block(self, rows, cols=slice(None)) -> np.ndarray:
        """S[rows][:, cols]: ``rows`` and ``cols`` index rows of X."""
        return self._sum([P[rows] @ Q[cols].T for P, Q in self.terms])

    def columns(self, cols) -> "Pearson":
        """S[:, cols]: the same similarity over those columns alone."""
        sub = copy.copy(self)
        sub.terms = [(P, Q[cols]) for P, Q in self.terms]
        sub.shape = (self.shape[0], len(cols))
        return sub

    def pairs(self, u, v) -> np.ndarray:
        """S[u[i], v[i]] for index arrays u, v."""
        return self._sum([np.einsum("ij,ij->i", P[u], Q[v])
                          for P, Q in self.terms])


def pearson(X):
    return Pearson(X, "f64")


# the controls: the reference with its similarity one step below float32
# (bf16), and the two above it for comparison
CONTROLS = {form: (lambda X, form=form: Pearson(X, form))
            for form in ("bf16", "3pass", "f32")}


class EdgeValues:
    """The similarity on the TMFG's edges alone, in the form
    ``reference.py``'s DBHT and APSP read it: ``.shape`` and ``[u, v]``
    for scalars or index arrays.  A pair that is not an edge raises."""

    def __init__(self, sim: Pearson, edges: np.ndarray):
        n = sim.shape[0]
        u, v = edges[:, 0], edges[:, 1]
        vals = sim.pairs(u, v)
        keys = np.r_[u * n + v, v * n + u]
        order = np.argsort(keys)
        self.n, self.shape = n, sim.shape
        self.keys, self.vals = keys[order], np.r_[vals, vals][order]
        self.scalar = dict(zip(self.keys.tolist(), self.vals.tolist()))

    def __getitem__(self, uv):
        u, v = uv
        if np.ndim(u) == 0 and np.ndim(v) == 0:
            return self.scalar[int(u) * self.n + int(v)]
        q = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        pos = np.minimum(np.searchsorted(self.keys, q), len(self.keys) - 1)
        if not np.array_equal(self.keys[pos], q):
            raise KeyError("the similarity is read on TMFG edges only")
        return self.vals[pos]


def _largest(B: np.ndarray, K: int) -> np.ndarray:
    """Indices of each row's K largest entries of B, by value descending,
    ties to the lower index (``lax.top_k``'s order)."""
    part = np.argpartition(-B, K - 1, axis=1)[:, :K]
    pv = np.take_along_axis(B, part, axis=1)
    part = np.take_along_axis(part, np.lexsort((part, -pv), axis=1), axis=1)
    # a tie at the K-th value may have left out a lower index
    tied = np.flatnonzero((B >= pv.min(axis=1)[:, None]).sum(axis=1) > K)
    for r in tied:
        part[r] = np.argsort(-B[r], kind="stable")[:K]
    return part


def top_k(sim: Pearson, K: int):
    """(values (n, K), indices (n, K)): each row's K largest values over
    the other rows."""
    n = sim.shape[0]
    vals = np.empty((n, K), np.float64)
    idx = np.empty((n, K), np.int64)
    for r0 in range(0, n, ROWS):
        r1 = min(n, r0 + ROWS)
        B = sim.block(slice(r0, r1))
        B[np.arange(r1 - r0), np.arange(r0, r1)] = -np.inf
        idx[r0:r1] = _largest(B, K)
        vals[r0:r1] = np.take_along_axis(B, idx[r0:r1], axis=1)
    return vals, idx


def tmfg_topk(sim: Pearson, K: int) -> ref.TMFG:
    """HEAP-TMFG on the candidate table of ``sim``: ``reference.tmfg_lazy``
    with the table's candidates and exact values."""
    n = sim.shape[0]
    if n < 4:
        raise ValueError(f"need n >= 4, got {n}")
    # the table, and after it each row's next EXTRA vertices in the same
    # order: where a row's K candidates are all inserted, the first
    # uninserted of these is the exact row's argmax
    wide = min(K + EXTRA, n - 1)
    topv, topi = top_k(sim, wide)
    clique = np.sort(np.argsort(-topv[:, :K].sum(axis=1), kind="stable")[:4])
    inserted = np.zeros(n, bool)
    rows = list(topi[:, :K])         # each row's candidates, best first
    first = [0] * n                  # first position not yet seen inserted
    more = [wide > K] * n            # the row's next vertices are in topi
    # the exact rows' columns: every uninserted vertex, and some inserted
    # ones, dropped once they are a quarter of them
    pool, pool_sim = np.arange(n), sim

    def candidate(w):
        """w's best uninserted vertex: the first uninserted entry of its
        table row, else the argmax of its exact row."""
        nonlocal pool, pool_sim
        row, p = rows[w], first[w]
        while True:
            if p < len(row) and inserted[row[p]]:
                seen = inserted[row[p:]]
                j = int(seen.argmin())
                p = len(row) if seen[j] else p + j
            if p < len(row):
                break
            if more[w]:
                row, more[w] = topi[w, K:], False
            else:
                if 4 * (n - count) < 3 * len(pool):
                    pool = pool[~inserted[pool]]
                    pool_sim = sim.columns(pool)
                r = pool_sim.block([w])
                r[:, inserted[pool]] = -np.inf
                row = pool[_largest(r, min(EXTRA, n - count))[0]]
            rows[w], p = row, 0
        first[w] = p
        return int(row[p])

    def best(face):
        cands = [candidate(w) for w in face]
        G = sim.block(list(face), cands)                  # (corner, cand)
        gains = (G[0] + G[1]) + G[2]
        j = int(np.argmax(gains))
        return cands[j], float(gains[j])

    v1, v2, v3, v4 = (int(x) for x in clique)
    edges = [(v1, v2), (v1, v3), (v1, v4), (v2, v3), (v2, v4), (v3, v4)]
    faces = [(v1, v2, v3), (v1, v2, v4), (v1, v3, v4), (v2, v3, v4)]
    face_bubble = [0, 0, 0, 0]
    bubble_verts = [(v1, v2, v3, v4)]
    bubble_parent, bubble_tri = [-1], [(-1, -1, -1)]
    home = np.zeros(n, np.int64)
    inserted[clique] = True
    count = 4
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
        inserted[v] = True
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
    return ref.TMFG(edges=np.asarray(edges, np.int64),
                    bubble_verts=np.asarray(bubble_verts, np.int64),
                    bubble_parent=np.asarray(bubble_parent, np.int64),
                    bubble_tri=np.asarray(bubble_tri, np.int64),
                    home_bubble=home)


def labels(sim: Pearson, tm: ref.TMFG, k: int) -> np.ndarray:
    """k flat clusters of the nested DBHT dendrogram, for k at most the
    number of coarse clusters (``reference.dbht_labels``' first case)."""
    n = sim.shape[0]
    S = EdgeValues(sim, tm.edges)
    cluster_of, _ = ref.coarse_clusters(S, tm)
    c = int(cluster_of.max()) + 1
    if k > c:
        raise ValueError(f"k={k} > {c} coarse clusters: the bubble-level "
                         "cut needs the (n, n) distances")
    w = ref.edge_lengths(S, tm.edges)
    _, D_h = ref.hub_distances(n, tm.edges, w)
    M = ref.cluster_max_distances(D_h, tm.edges, w, cluster_of, c)
    return ref.cut(ref.complete_linkage(M), c, k)[cluster_of]


def answer(X: np.ndarray, k: int, similarity=pearson, *, config: dict):
    """(TMFG, labels) of the a-TMFG reference on series X (n, L), with
    ``similarity(X)`` (a ``Pearson``; float64 unless a control asks for a
    lower precision)."""
    sim = similarity(X)
    K = min(int(config["pipeline"]["args"]["sim_k"]), X.shape[0] - 1)
    tm = tmfg_topk(sim, K)
    return tm, labels(sim, tm, k)
