"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(``tests/test_kernels_*.py`` sweeps shapes/dtypes and asserts allclose).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG = -jnp.inf

# Every Pearson dot (the kernels, these oracles and the XLA rescores) runs
# at full f32 precision.  The TPU's default is one bf16 pass, which moves
# similarities by up to 1.6e-3 and at n=2000 changes 23% of the TMFG's
# edges and the clustering (ARI 0.82 against the f32 answer; the
# reference phase of chip_smoke.py checks this).  On a CPU it changes
# nothing.
SIM_PRECISION = lax.Precision.HIGHEST


def minplus_ref(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Tropical (min-plus) matrix product: out[i,j] = min_k A[i,k] + B[k,j]."""
    return jnp.min(A[:, :, None] + B[None, :, :], axis=1)


def standardize_rows(X: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    """Center and L2-normalize rows so Z @ Z.T is Pearson correlation.

    Shared by the single-device oracle below and the row-sharded
    ``dist.sharding.pearson_shardmap`` wrapper (each device standardizes
    its local block with exactly this function)."""
    X = X.astype(jnp.float32)
    mu = X.mean(axis=1, keepdims=True)
    Z = X - mu
    denom = jnp.sqrt(jnp.sum(Z * Z, axis=1, keepdims=True)) + eps
    return Z / denom


def pearson_rows(Zr: jnp.ndarray, Z: jnp.ndarray) -> jnp.ndarray:
    """Pearson rows (m, n) of the standardized rows ``Zr (m, L)`` against
    ``Z (n, L)``: the one arithmetic of the dense matrix and of every
    row panel of the jnp top-K scan."""
    return jnp.clip(jnp.dot(Zr, Z.T, precision=SIM_PRECISION), -1.0, 1.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def pearson_ref(X: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    """Pearson correlation matrix of the rows of X (n, L) -> (n, n).

    Compiled whole, also where it is called outside a jit (the staged
    path): run op by op, its dot would read a materialized ``Z.T`` and
    round some entries one ulp away from the compiled form that the
    fused programs and the top-K scan's panels use."""
    Z = standardize_rows(X, eps)
    return pearson_rows(Z, Z)


def masked_argmax_ref(S: jnp.ndarray, mask: jnp.ndarray):
    """Per-row (max value, argmax index) of S with masked columns excluded.

    ``mask`` is (n,) bool; True columns are excluded.  Ties break low-index.
    """
    masked = jnp.where(mask[None, :], NEG, S)
    return jnp.max(masked, axis=1), jnp.argmax(masked, axis=1).astype(jnp.int32)


def gains_ref(S: jnp.ndarray, faces: jnp.ndarray, maxcorr: jnp.ndarray):
    """Best (vertex, gain) per face from a maxcorr table — oracle for the
    vectorized face-pair recompute (see core/tmfg.py:_all_face_pairs)."""
    cands = maxcorr[faces]                                    # (F, 3)
    g = S[faces[:, :, None], cands[:, None, :]].sum(axis=1)   # (F, 3)
    j = jnp.argmax(g, axis=1)
    best = jnp.take_along_axis(cands, j[:, None], 1)[:, 0].astype(jnp.int32)
    return best, jnp.take_along_axis(g, j[:, None], 1)[:, 0]
