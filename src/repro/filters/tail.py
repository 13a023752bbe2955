"""Generic hierarchy tail for non-TMFG filters (DESIGN.md §18.4).

DBHT proper is NOT defined on an arbitrary filtered graph: its bubble
tree comes from the TMFG's 4-clique insertion log (the planar-graph
bubble decomposition), which an MST, asset graph, or even the greedy
PMFG reference does not carry — the DBHT-on-MST caveat.  What the
filter matrix shares is the tail's SHAPE: geodesic distances on the
filtered graph, a coarse partition, and a nested complete-linkage
dendrogram.  This module is that tail, built from the same stages the
TMFG path uses so parity and benchmarks stay comparable:

  * distances — ``apsp.edge_lengths``'s metric transform
    d = √(2(1-ρ)) on the filter's edges; ``apsp_method="exact"`` runs
    the dense min-plus squaring, while ``"hub"``/``"sparse"`` route
    through the PR 6 sparse edge-list machinery
    (``kernels.sparse_apsp.csr_from_edges`` + ``apsp.hub_factor_sparse``
    on the filter's edge list — MST's n-1 edges are the degenerate
    case) with the dispatcher's small-n exact fallback for ``"hub"``;
  * coarse partition — connected components by min-label propagation
    (an AG at a tight threshold shatters; components stand in for
    DBHT's converging bubbles, so ``ClusterResult.dbht.converging``
    counts components and the default ``k`` is the component count —
    pass ``k=`` explicitly for a finer cut);
  * dendrogram — ``hac.hierarchical_offsets`` + the same
    ``hac.complete_linkage`` program DBHT's nested HAC runs, with
    cross-component pairs pushed above every intra-component merge.

The whole tail is one traceable fixed-shape function returning the
same output dict ``dbht._result_from_device`` unpacks, so it drops
into ``pipeline.DeviceOutputs`` and the fused/staged/batched plumbing
with zero special cases.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import repro.core.apsp as apsp_mod
import repro.core.hac as hac_mod
from repro.kernels import ops
from repro.kernels import sparse_apsp as sparse_kernels

from .graph import FilterGraph


def _edge_metric(S: jax.Array, edges: jax.Array) -> jax.Array:
    """d = sqrt(2(1-rho)) per filter edge — the same Mantegna transform
    ``apsp.edge_lengths`` applies densely."""
    rho = jnp.clip(S[edges[:, 0], edges[:, 1]], -1.0, 1.0)
    return jnp.sqrt(jnp.maximum(2.0 * (1.0 - rho), 0.0))


def _distances(S: jax.Array, edges: jax.Array, *, apsp_method: str,
               apsp_hubs: int, apsp_rounds: int, backend: str):
    """Geodesic distances on the filtered graph, by ``apsp_method``, and
    the rounds they took (squarings, or relaxation rounds)."""
    n = S.shape[0]
    if apsp_method == "exact" or (apsp_method == "hub"
                                  and n < apsp_mod.HUB_MIN_N):
        W = apsp_mod.edge_lengths(n, edges, S)
        return apsp_mod.apsp_rounds(W, method="exact", backend=backend)
    # hub/sparse: the PR 6 edge-list factorization on the filter's edges
    d = _edge_metric(S, edges)
    graph = sparse_kernels.csr_from_edges(n, edges, d)
    _, D_h, rounds = apsp_mod.hub_factor_sparse_rounds(graph, apsp_hubs,
                                                       apsp_rounds)
    est = ops.minplus(D_h.T, D_h, backend=backend)
    est = est.at[edges[:, 0], edges[:, 1]].min(d)
    est = est.at[edges[:, 1], edges[:, 0]].min(d)
    est = jnp.minimum(est, est.T)
    return est.at[jnp.arange(n), jnp.arange(n)].set(0.0), rounds


def _components(n: int, edges: jax.Array) -> jax.Array:
    """Min-label connected components of the edge list: label[v] is the
    smallest vertex id in v's component (fixed point of propagate +
    pointer-jump compression)."""
    e0, e1 = edges[:, 0], edges[:, 1]

    def body(state):
        lab, _ = state
        l2 = lab.at[e0].min(lab[e1])
        l2 = l2.at[e1].min(l2[e0])
        l2 = l2[l2]                      # compression: labels only shrink
        return l2, jnp.any(l2 != lab)

    lab0 = jnp.arange(n, dtype=jnp.int32)
    lab, _ = lax.while_loop(lambda s: s[1], body, (lab0, jnp.bool_(True)))
    return lab


@functools.partial(jax.jit, static_argnames=("apsp_method", "apsp_hubs",
                                             "apsp_rounds", "backend"))
def filter_tail(S: jax.Array, fg: FilterGraph, *, apsp_method: str = "exact",
                apsp_hubs: int = 0, apsp_rounds: int = 0,
                backend: str = "auto") -> dict:
    """APSP + components + nested HAC on a :class:`FilterGraph`.

    Returns the device-core output dict (``direction``/``conv_mask``/
    ``cluster_of``/``bubble_of``/``D``/``Z``) in the
    ``dbht._result_from_device`` convention: ``conv_mask`` marks
    component representatives (lowest vertex id), ``cluster_of`` and
    ``bubble_of`` both hold the component id (there is no finer bubble
    level without a bubble tree), and ``direction`` is a length-1
    placeholder (its ``[1:]`` slice — the API surface — is empty).
    ``apsp_rounds`` and ``hac_rescans`` are the tail's loop counters
    (DESIGN.md §15.5).
    """
    n = S.shape[0]
    with jax.named_scope("apsp"):
        D, rounds = _distances(S, fg.edges, apsp_method=apsp_method,
                               apsp_hubs=apsp_hubs, apsp_rounds=apsp_rounds,
                               backend=backend)
    with jax.named_scope("dbht"):
        lab = _components(n, fg.edges)
        conv_mask = lab == jnp.arange(n, dtype=jnp.int32)
        comp_id = (jnp.cumsum(conv_mask.astype(jnp.int32))
                   - 1).astype(jnp.int32)
        cluster_of = comp_id[lab]
        adj = hac_mod.hierarchical_offsets(D, cluster_of, cluster_of)
    with jax.named_scope("hac"):
        Z, rescans = hac_mod.complete_linkage_rescans(adj, backend=backend)
    return dict(direction=jnp.zeros((1,), jnp.float32), conv_mask=conv_mask,
                cluster_of=cluster_of, bubble_of=cluster_of, D=D, Z=Z,
                apsp_rounds=rounds, hac_rescans=rescans)
