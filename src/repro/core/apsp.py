"""All-pairs shortest paths on the TMFG — exact and hub-approximate.

The paper's DBHT stage needs APSP over the filtered graph.  Its optimization
C3 replaces exact APSP with a hub-based approximation.  TPU adaptation
(DESIGN.md §2): priority queues don't vectorize, so both variants are
expressed in the tropical (min-plus) semiring on dense matrices, backed by
the ``kernels/minplus.py`` Pallas kernel:

  * exact:   ⌈log2(n-1)⌉ min-plus squarings of the length matrix.
  * hub:     R Bellman-Ford rounds restricted to h hub rows
             (each round one (h,n)x(n,n) min-plus), then composition
             ``D[u,v] ≈ min_h D[u,h] + D[h,v]`` — an (n,h)x(h,n) min-plus —
             taking a final elementwise min with the direct edge lengths.

Hubs are the highest weighted-degree TMFG vertices (h = ceil(sqrt(n)) by
default).  The approximation is an upper bound on the true distance, exact
for any pair whose shortest path passes a hub (TMFG's early-inserted
vertices are high-degree hubs, so in practice most paths do — measured in
benchmarks/bench_apsp.py).

A third variant (DESIGN.md §14) drops the dense matrix entirely:

  * sparse:  the same hub selection + Bellman-Ford rounds, but run as
             multi-source relaxation over the CSR adjacency of the
             3n-6 TMFG edges (``kernels/sparse_apsp.py``) — O(h·n)
             memory for the hub factor ``D_h`` instead of O(n²).
             :func:`hub_factor_sparse` returns the factor; the
             distance of any pair is ``min_h D_h[h,u] + D_h[h,v]``
             (floored by the direct edge, if one exists).
             :func:`apsp_sparse` densifies the factor back to (n, n)
             as a parity/interop surface — the sparse DBHT tail
             (core/sparse_dbht.py) consumes the factor directly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels import sparse_apsp as sparse_kernels

INF = jnp.inf

# Below this size ``apsp(method="hub")`` silently runs the exact program
# instead.  BENCH_5.json showed hub LOSING at every small n (speedup
# 0.15-0.87): the hub program — top_k + a 32-round scan of three kernel
# shapes — costs ~2.5x more to compile and dispatch than exact's
# ceil(log2(n-1)) squarings of one shape, and below ~200 vertices that
# overhead dominates the O(n³) work it saves.  Measured first-call
# (compile-inclusive) exact/hub ratios on this container: 0.42 @ n=48,
# 0.39 @ 96, 0.91 @ 192, 1.22 @ 256, 4.50 @ 512 — crossover between 192
# and 256.  Exact results are also strictly more accurate, so the
# fallback only ever improves answers (pinned in tests/test_sparse_apsp.py;
# n-scaling rows in benchmarks/bench_apsp.py).
HUB_MIN_N = 200


def hub_count(n: int, n_hubs: int = 0) -> int:
    """Number of hub sources: ``n_hubs`` or the paper's ceil(sqrt(n)) default
    (floored at 4), clamped to n.  Shared by the dense and sparse paths so
    ``apsp_hub`` and :func:`hub_factor_sparse` pick identical hub sets."""
    h = n_hubs if n_hubs > 0 else max(4, math.ceil(math.sqrt(n)))
    return min(h, n)


def edge_lengths(n: int, edges: jax.Array, S: jax.Array) -> jax.Array:
    """Dense length matrix of the TMFG: d = sqrt(2(1-rho)) on edges.

    Non-edges are +inf, the diagonal is 0.  This is the standard metric
    transform for correlation similarities (Mantegna 1999).
    """
    rho = jnp.clip(S[edges[:, 0], edges[:, 1]], -1.0, 1.0)
    w = jnp.sqrt(jnp.maximum(2.0 * (1.0 - rho), 0.0))
    W = jnp.full((n, n), INF, jnp.float32)
    W = W.at[edges[:, 0], edges[:, 1]].set(w)
    W = W.at[edges[:, 1], edges[:, 0]].set(w)
    W = W.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    return W


def _exact_steps(n: int) -> int:
    """Min-plus squarings :func:`apsp_exact` runs: ceil(log2(n-1))."""
    return max(1, math.ceil(math.log2(max(n - 1, 2))))


@functools.partial(jax.jit, static_argnames=("backend",))
def apsp_exact(W: jax.Array, *, backend: str = "auto") -> jax.Array:
    """Exact APSP by repeated min-plus squaring (assumes W symmetric, 0 diag)."""
    D = W

    def body(D, _):
        return ops.minplus(D, D, backend=backend), None

    D, _ = jax.lax.scan(body, D, None, length=_exact_steps(W.shape[0]))
    return D


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _apsp_hub_rounds(W: jax.Array, n_hubs: int, rounds: int, backend: str):
    """:func:`apsp_hub`'s program, plus the Bellman-Ford rounds it ran
    (the while_loop's counter, the last round being the one that changed
    nothing unless the cap stopped it)."""
    n = W.shape[0]
    h = hub_count(n, n_hubs)
    cap = rounds if rounds else n

    # hubs = highest weighted degree (sum of finite incident 1/length —
    # strong-similarity vertices attract shortest paths)
    finite = jnp.isfinite(W) & (W > 0)
    strength = jnp.sum(jnp.where(finite, 1.0 / (W + 1e-6), 0.0), axis=1)
    hubs = jax.lax.top_k(strength, h)[1]

    # Bellman-Ford on the h hub rows: D_h <- min(D_h, minplus(D_h, W)),
    # early-exited at the fixed point
    D_h0 = W[hubs]                                      # (h, n)

    def cond(carry):
        i, _, changed = carry
        return (i < cap) & changed

    def body(carry):
        i, D_h, _ = carry
        D2 = jnp.minimum(D_h, ops.minplus(D_h, W, backend=backend))
        return i + 1, D2, jnp.any(D2 < D_h)

    i, D_h, _ = jax.lax.while_loop(cond, body,
                                   (0, D_h0, jnp.bool_(True)))

    # composition through hubs + exact 1-hop floor
    est = ops.minplus(D_h.T, D_h, backend=backend)      # (n, n)
    est = jnp.minimum(est, W)
    est = jnp.minimum(est, est.T)
    est = est.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    return est, i


@functools.partial(jax.jit, static_argnames=("n_hubs", "rounds", "backend"))
def apsp_hub(W: jax.Array, *, n_hubs: int = 0, rounds: int = 0,
             backend: str = "auto") -> jax.Array:
    """Hub-based approximate APSP (paper optimization C3, TPU formulation).

    Args:
      W: dense (n, n) length matrix (inf off-graph, 0 diagonal).
      n_hubs: number of hub vertices; 0 means ceil(sqrt(n)).
      rounds: Bellman-Ford relaxation cap for the hub rows; 0 (the
        default) relaxes to the fixed point with the true n-round bound
        as the cap.  The loop exits as soon as a round changes nothing,
        so the generous cap costs nothing once converged — a fixed
        truncation (the old ``rounds=32`` default) silently left
        unreachable-looking ``inf`` distances whenever the TMFG's
        hop-diameter exceeded it, which real graphs hit from n ≈ 1000
        (the BENCH_9 sparse-tail shattering).
    """
    return _apsp_hub_rounds(W, n_hubs, rounds, backend)[0]


@functools.partial(jax.jit, static_argnames=("n_hubs", "rounds", "backend"))
def hub_factor_sparse(graph, *, n_hubs: int = 0, rounds: int = 0,
                      backend: str = "auto"):
    """Hub factorization of sparse APSP: ``(hubs (h,), D_h (h, n))``.

    The sparse counterpart of :func:`apsp_hub`'s first half — the same
    weighted-degree hub selection (``kernels.sparse_apsp.hub_strength``
    is the CSR form of the dense ``strength`` reduction above) and the
    same run-to-fixed-point Bellman-Ford contract (``rounds=0`` caps at
    n; a nonzero cap truncates, as in :func:`apsp_hub`), but O(h·n + E)
    memory: relaxation runs over the 2(3n-6) CSR entries, never a dense
    row of W.  Downstream, any pairwise distance is

        D[u, v] = min(min_h D_h[h, u] + D_h[h, v],  w(u, v) if edge)

    which the sparse DBHT tail evaluates in (panel, n) blocks
    (core/sparse_dbht.py) — the full (n, n) matrix never exists.
    """
    del backend           # sparse relaxation has one XLA form everywhere
    return hub_factor_sparse_rounds(graph, n_hubs, rounds)[:2]


def hub_factor_sparse_rounds(graph, n_hubs: int, rounds: int):
    """:func:`hub_factor_sparse` plus the relaxation rounds it ran:
    ``(hubs, D_h, rounds_run)``; traceable inside a caller's jit."""
    h = hub_count(graph.n, n_hubs)
    strength = sparse_kernels.hub_strength(graph)
    hubs = jax.lax.top_k(strength, h)[1]
    D_h, i = sparse_kernels.relax_to_fixed_point(graph, hubs, rounds)
    return hubs, D_h, i


def csr_from_dense(W) -> "sparse_kernels.CSRGraph":
    """CSR adjacency from a dense length matrix (finite off-diagonal
    entries are edges).  Host-side edge extraction — the parity/interop
    bridge for callers that already hold dense W; the pipeline builds
    the CSR from the TMFG edge list directly."""
    Wn = np.asarray(W)
    n = Wn.shape[0]
    iu, ju = np.triu_indices(n, k=1)
    keep = np.isfinite(Wn[iu, ju])
    edges = np.stack([iu[keep], ju[keep]], axis=1).astype(np.int32)
    w = Wn[iu[keep], ju[keep]].astype(np.float32)
    return sparse_kernels.csr_from_edges(n, jnp.asarray(edges),
                                         jnp.asarray(w))


def apsp_sparse(W: jax.Array, *, n_hubs: int = 0, rounds: int = 0,
                backend: str = "auto") -> jax.Array:
    """Sparse hub APSP, densified back to (n, n) for parity and interop.

    Runs :func:`hub_factor_sparse` on the CSR of W's finite entries and
    composes ``min_h D_h[:, u] + D_h[:, v]`` with the same direct-edge
    floor / symmetrization / zero-diagonal epilogue as :func:`apsp_hub`.
    This materializes (n, n) by construction — it exists so tests and
    benchmarks can compare the sparse kernel against the dense variants;
    the production sparse tail never calls it (DESIGN.md §14.3).
    """
    graph = csr_from_dense(W)
    _, D_h = hub_factor_sparse(graph, n_hubs=n_hubs, rounds=rounds,
                               backend=backend)
    n = graph.n
    est = ops.minplus(D_h.T, D_h, backend=backend)
    est = jnp.minimum(est, jnp.asarray(W, jnp.float32))
    est = jnp.minimum(est, est.T)
    est = est.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    return est


def apsp(W: jax.Array, *, method: str = "hub", n_hubs: int = 0,
         rounds: int = 0, backend: str = "auto") -> jax.Array:
    """Dispatch to exact / hub / sparse APSP by ``method``.

    The signature names every knob explicitly (no ``**kw`` grab bag):
    ``n_hubs``/``rounds`` only apply to the hub approximations and are
    simply not forwarded to the exact path.

    ``method="hub"`` requests the approximation, not the program shape:
    below :data:`HUB_MIN_N` vertices the hub program's compile+dispatch
    overhead exceeds the O(n³) it saves (BENCH_5.json regression), so
    the dispatcher runs :func:`apsp_exact` there — a strictly more
    accurate answer, faster.  Call :func:`apsp_hub` directly to force
    the hub program shape regardless of n.
    """
    if method == "sparse":
        return apsp_sparse(W, n_hubs=n_hubs, rounds=rounds, backend=backend)
    return apsp_rounds(W, method=method, n_hubs=n_hubs, rounds=rounds,
                       backend=backend)[0]


def apsp_rounds(W: jax.Array, *, method: str = "hub", n_hubs: int = 0,
                rounds: int = 0, backend: str = "auto"):
    """:func:`apsp` for the dense methods, plus the rounds it ran:
    ``(D, rounds_run)`` — the Bellman-Ford rounds of the hub program, or
    the fixed number of squarings of the exact one.  Traceable: the
    fused pipeline returns the count as a loop counter (DESIGN.md
    §15.5)."""
    if method == "exact" or (method == "hub" and W.shape[0] < HUB_MIN_N):
        return (apsp_exact(W, backend=backend),
                jnp.int32(_exact_steps(W.shape[0])))
    if method == "hub":
        return _apsp_hub_rounds(W, n_hubs, rounds, backend)
    raise ValueError(f"unknown APSP method {method!r}")
