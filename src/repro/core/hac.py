"""Complete-linkage hierarchical agglomerative clustering, vectorized.

DBHT's final stage runs complete linkage at several levels of the bubble
hierarchy.  We use the single-matrix trick (DESIGN.md §4.2): membership
offsets are added to the pairwise distance matrix so that ONE complete-
linkage run produces the nested (bubble ⊂ cluster ⊂ global) dendrogram with
exactly the same merge order as three separate per-level runs.

The JAX implementation is a fixed-shape `fori_loop` over the n-1 merges
that keeps each row's nearest alive neighbour (value, column).  A merge
reads the global minimum off those n cached pairs, does the row/column
`max` update, and rescans only the rows whose cached neighbour was one of
the merged pair (plus the merged row): O(n) work per merge plus a few
row scans.  A masked argmin over all (n, n) pairs per merge would make it
O(n^3): at the paper's n=19,412, 19,411 passes over 1.5 GB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.gainscan import NEG

INF = jnp.inf


RESCAN_ROWS = 8      # rows per rescan step: one sublane tile of the kernel


def _nearest(D_rows: jax.Array, alive: jax.Array, backend: str):
    """Per row of ``D_rows`` (R, n): (-distance, column) of its nearest
    alive column, ties to the lowest column.  A row with no finite alive
    entry gets (-inf, 0), what a flat argmin over all-inf pairs picks."""
    if backend == "jnp":
        s = jnp.where(alive[None, :], -D_rows, -INF)
        return jnp.max(s, axis=1), jnp.argmax(s, axis=1).astype(jnp.int32)
    vals, idx = ops.masked_argmax(-D_rows, ~alive, backend=backend)
    none = vals <= NEG                    # only masked or -inf entries left
    return jnp.where(none, -INF, vals), jnp.where(none, 0, idx)


@functools.partial(jax.jit, static_argnames=("backend",))
def complete_linkage(D: jax.Array, *, backend: str = "jnp") -> jax.Array:
    """Complete-linkage HAC on a dense distance matrix.

    Returns a scipy-style linkage matrix (n-1, 4): (left id, right id,
    height, size); leaf ids < n, merge k creates id n+k.  Tie-breaking is
    lowest-flat-index, matching the numpy oracle in tmfg_ref.py.

    The global minimum is read off a per-row cache of each row's nearest
    alive neighbour.  A merge of (i, j) only raises row/column i (to the
    elementwise max) and kills column j, so a row whose cached neighbour
    is neither keeps it — ties included, since a raised entry cannot
    undercut a lower-index minimum — and only the rows that pointed at i
    or j, and row i itself, are rescanned, ``RESCAN_ROWS`` at a time.

    ``backend`` picks the row scan (DESIGN.md §11.3): the default
    ``"jnp"`` is the reference argmax; any other value routes it through
    ``kernels.ops.masked_argmax`` — the same gain-scan Pallas kernel the
    TMFG uses.  Both compare identical values with identical low-index
    tie-breaking, so the linkage is bitwise the same on every backend.
    """
    n = D.shape[0]
    D = D.astype(jnp.float32)
    D = jnp.where(jnp.eye(n, dtype=bool), INF, D)
    rows_n = jnp.arange(n, dtype=jnp.int32)

    class_ids = rows_n
    sizes = jnp.ones((n,), jnp.int32)
    alive = jnp.ones((n,), bool)
    Z = jnp.zeros((n - 1, 4), jnp.float32)
    nv, nc = _nearest(D, alive, backend)

    def rescan(D, alive, stale, nv, nc):
        def pending(c):
            return jnp.any(c[0])

        def step(c):
            stale, nv, nc = c
            rows = jnp.nonzero(stale, size=RESCAN_ROWS, fill_value=-1)[0]
            rows = jnp.where(rows >= 0, rows, rows[0])    # pad: repeat one
            v, col = _nearest(D[rows], alive, backend)
            return (stale.at[rows].set(False), nv.at[rows].set(v),
                    nc.at[rows].set(col))

        _, nv, nc = jax.lax.while_loop(pending, step, (stale, nv, nc))
        return nv, nc

    def body(k, carry):
        D, ids, sizes, alive, nv, nc, Z = carry
        vals = jnp.where(alive, nv, -INF)
        i = jnp.argmax(vals).astype(jnp.int32)    # lowest row, then column
        h = -vals[i]
        # no finite pair left: a flat argmin over all-inf pairs picks (0, 0)
        none = h == INF
        j = jnp.where(none, 0, nc[i])
        i = jnp.where(none, 0, i)
        i, j = jnp.minimum(i, j), jnp.maximum(i, j)
        Z = Z.at[k].set(jnp.stack([ids[i].astype(jnp.float32),
                                   ids[j].astype(jnp.float32), h,
                                   (sizes[i] + sizes[j]).astype(jnp.float32)]))
        # complete linkage: merged row/col is the elementwise max
        row = jnp.maximum(D[i], D[j])
        D = D.at[i, :].set(row).at[:, i].set(row)
        D = D.at[i, i].set(INF)
        alive = alive.at[j].set(False)
        ids = ids.at[i].set(n + k)
        sizes = sizes.at[i].set(sizes[i] + sizes[j])
        stale = alive & ((nc == i) | (nc == j) | (rows_n == i))
        nv, nc = rescan(D, alive, stale, nv, nc)
        return D, ids, sizes, alive, nv, nc, Z

    carry = jax.lax.fori_loop(
        0, n - 1, body, (D, class_ids, sizes, alive, nv, nc, Z))
    return carry[-1]


def hierarchical_offsets(D: jax.Array, bubble_of: jax.Array,
                         cluster_of: jax.Array) -> jax.Array:
    """Adjusted distances whose single-run complete linkage equals the
    three-level (intra-bubble, intra-cluster, inter-cluster) nested HAC.

    Complete linkage between two groups is max-pair distance, so adding a
    constant M to every cross-group pair adds exactly M to every cross-group
    merge height and keeps within-group merges strictly first whenever
    M > max(D).  Nesting two offsets (M1 for cross-bubble, M2 for
    cross-cluster, M2 > M1 + max(D)) yields the nested dendrogram.
    """
    finite = jnp.where(jnp.isfinite(D), D, 0.0)
    dmax = jnp.max(finite) + 1.0
    m1 = 2.0 * dmax
    m2 = 8.0 * dmax
    cross_bubble = bubble_of[:, None] != bubble_of[None, :]
    cross_cluster = cluster_of[:, None] != cluster_of[None, :]
    adj = jnp.where(jnp.isfinite(D), D, dmax)  # disconnected -> far
    adj = adj + jnp.where(cross_bubble, m1, 0.0)
    adj = adj + jnp.where(cross_cluster, m2 - m1, 0.0)
    return adj


def cut_linkage(Z, n: int, k: int):
    """Cut a linkage matrix into k flat clusters (numpy host op)."""
    import numpy as np

    Z = np.asarray(Z)
    k = int(max(1, min(k, n)))
    parent = np.arange(n + len(Z))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    order = np.argsort(Z[:, 2], kind="stable")
    clusters = n
    for idx in order:
        if clusters <= k:
            break
        a, b = int(Z[idx, 0]), int(Z[idx, 1])
        new = n + int(idx)
        parent[find(a)] = new
        parent[find(b)] = new
        clusters -= 1
    roots, labels = {}, np.zeros(n, dtype=np.int64)
    for v in range(n):
        r = find(v)
        labels[v] = roots.setdefault(r, len(roots))
    return labels
