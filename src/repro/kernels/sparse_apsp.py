"""Sparse APSP on the TMFG edge list: multi-source relaxation.

The TMFG is planar — exactly 3n-6 edges — so the APSP stage never needs
the dense (n, n) length matrix the min-plus kernels square (DESIGN.md
§14.1).  This module is the sparse counterpart of ``kernels/minplus.py``:
a CSR adjacency of the 2(3n-6) directed entries plus a frontier-style
relaxation kernel

    D[s, v]  <-  min(D[s, v],  min_{(u,v) in E}  D[s, u] + w(u, v))

iterated to a fixed point from a small set of source rows (the hub
vertices of ``core/apsp.apsp_hub``, DESIGN.md §14.2).  One round is a
gather of the tail distances along the edge list, an elementwise add of
the edge lengths, and a segmented min back into the head vertices —
O(s·E) work and O(s·n + E) memory, never (n, n).

One form on every platform: a gather + ``jax.ops.segment_min`` per
round (the CSR entries are row-sorted, so the segmented min is a linear
sweep).  The ``backend`` argument is accepted for the ``kernels/ops.py``
calling convention and changes nothing: Mosaic has no lane-axis gather
at these widths, so there is no Pallas form to select.

The fixed point is exact: ``min`` does not round, so the relaxation order
cannot change a single bit of the converged distances (pinned by
tests/test_sparse_apsp.py against a numpy reference).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

INF = jnp.inf


class CSRGraph(NamedTuple):
    """Row-sorted CSR adjacency of an undirected weighted graph.

    ``rows`` is kept explicitly (it is ``indptr`` run-length decoded) so
    the relaxation's segmented min and the hub-strength reduction are
    plain segment ops with ``indices_are_sorted=True`` — no searchsorted
    on the hot path.
    """

    indptr: jax.Array    # (n+1,) i32 — row start offsets
    rows: jax.Array      # (m,) i32 — head vertex per entry, ascending
    cols: jax.Array      # (m,) i32 — tail vertex per entry
    vals: jax.Array      # (m,) f32 — edge weight per entry

    @property
    def n(self) -> int:
        return self.indptr.shape[0] - 1


@functools.partial(jax.jit, static_argnums=0)
def csr_from_edges(n: int, edges: jax.Array, w: jax.Array) -> CSRGraph:
    """CSR adjacency from an undirected edge list (E, 2) + weights (E,).

    Both directions of every edge are materialized (2E entries), sorted
    by (row, col) — the layout every consumer assumes: the relaxation's
    segmented min, the hub-strength reduction, and the host-side
    direction stage's per-row range queries (core/sparse_dbht.py).
    """
    rows = jnp.concatenate([edges[:, 0], edges[:, 1]]).astype(jnp.int32)
    cols = jnp.concatenate([edges[:, 1], edges[:, 0]]).astype(jnp.int32)
    vals = jnp.concatenate([w, w]).astype(jnp.float32)
    order = jnp.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    counts = jnp.zeros((n,), jnp.int32).at[rows].add(1)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    return CSRGraph(indptr=indptr, rows=rows, cols=cols, vals=vals)


def hub_strength(graph: CSRGraph) -> jax.Array:
    """Weighted degree per vertex: sum of incident 1/(length + 1e-6).

    The same strength ``core/apsp.apsp_hub`` reduces over its dense rows
    (strong-similarity vertices attract shortest paths), expressed as a
    segmented sum over the CSR entries — the hub SELECTION machinery is
    shared, only the reduction layout differs (DESIGN.md §14.2).
    """
    return jax.ops.segment_sum(1.0 / (graph.vals + 1e-6), graph.rows,
                               num_segments=graph.n,
                               indices_are_sorted=True)


# ---------------------------------------------------------------------------
# one relaxation round, per backend
# ---------------------------------------------------------------------------

def sparse_relax(D: jax.Array, graph: CSRGraph, *,
                 backend: str = "auto") -> jax.Array:
    """One multi-source relaxation round: tropical SpMM against the CSR.

    Returns ``min(D, candidates)`` — monotone non-increasing, so iterating
    to a fixed point yields the (unique) single-source distances from
    every row's source set.  ``backend`` is accepted and ignored: this
    round has one XLA form on every platform (module docstring).
    """
    del backend
    cand = D[:, graph.cols] + graph.vals[None, :]              # (s, m)
    upd = jax.ops.segment_min(cand.T, graph.rows, num_segments=graph.n,
                              indices_are_sorted=True)         # (n, s)
    return jnp.minimum(D, upd.T)


# ---------------------------------------------------------------------------
# multi-source fixed point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("rounds", "backend"))
def sparse_apsp_sources(graph: CSRGraph, sources: jax.Array, *,
                        rounds: int = 0,
                        backend: str = "auto") -> jax.Array:
    """Distances (s, n) from ``sources`` by iterated sparse relaxation.

    Frontier-style early exit: the while_loop stops as soon as a round
    changes nothing (the fixed point) — the same convergence contract
    as ``apsp_hub``'s Bellman-Ford loop.  ``rounds=0`` (the default)
    caps at the true n-round bound; a nonzero cap truncates.  Unlike
    dense min-plus, each sparse round extends paths by ONE edge hop, so
    a fixed small cap (the old 32 default) left ``inf`` in every entry
    farther than 32 hops from its source — TMFG hop-diameters pass 32
    from n ≈ 1000, which shattered the sparse DBHT geometry downstream.
    ``backend`` is accepted and ignored (one XLA form, module docstring).
    """
    del backend
    return relax_to_fixed_point(graph, sources, rounds)[0]


def relax_to_fixed_point(graph: CSRGraph, sources: jax.Array, rounds: int):
    """:func:`sparse_apsp_sources`' loop, plus the rounds it ran:
    ``(D (s, n), rounds_run)``; traceable inside a caller's jit."""
    n = graph.n
    s = sources.shape[0]
    cap = rounds if rounds else n
    D0 = jnp.full((s, n), INF, jnp.float32)
    D0 = D0.at[jnp.arange(s), sources].set(0.0)

    def cond(carry):
        i, _, changed = carry
        return (i < cap) & changed

    def body(carry):
        i, D, _ = carry
        D2 = sparse_relax(D, graph)
        return i + 1, D2, jnp.any(D2 < D)

    i, D, _ = lax.while_loop(cond, body, (0, D0, jnp.bool_(True)))
    return D, i
