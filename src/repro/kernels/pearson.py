"""Pallas TPU kernel: fused Pearson correlation matrix.

Computes ``corrcoef(X)`` for row-major time series X (n, L): the
normalization (mean-center, inverse-norm scale) is fused into the matmul
tiles so the standardized matrix Z is never materialized in HBM — each
(bm, bl) X-tile is standardized in VMEM right before it hits the MXU.

This is the similarity-matrix construction stage of the pipeline (the
paper computes Pearson correlations of all time-series pairs as input to
TMFG); it is a true MXU kernel with arithmetic intensity ~L/2 FLOP/byte.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import SIM_PRECISION


def _pearson_kernel(x_ref, y_ref, mx_ref, rx_ref, my_ref, ry_ref, o_ref):
    """Grid (i, j, l): o[i,j] += std(x[i,l]) @ std(y[j,l]).T"""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = (x_ref[...] - mx_ref[...]) * rx_ref[...]      # (bm, bl) standardized
    y = (y_ref[...] - my_ref[...]) * ry_ref[...]      # (bn, bl)
    o_ref[...] += jnp.dot(x, y.T, preferred_element_type=jnp.float32,
                          precision=SIM_PRECISION)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bl", "interpret"))
def pearson_pallas(X: jax.Array, *, bm: int = 128, bn: int = 128,
                   bl: int = 128, interpret: bool = False,
                   eps: float = 1e-12) -> jax.Array:
    """Pearson correlation of the rows of X via a fused Pallas matmul."""
    n, L = X.shape
    X = X.astype(jnp.float32)
    mu = X.mean(axis=1, keepdims=True)                         # (n, 1)
    ss = jnp.sum((X - mu) ** 2, axis=1, keepdims=True)
    rs = 1.0 / (jnp.sqrt(ss) + eps)                            # (n, 1)

    bm_, bn_, bl_ = min(bm, n), min(bn, n), min(bl, L)
    pn, pl_pad = (-n) % max(bm_, bn_), (-L) % bl_
    # pad the L axis with each row's mean so padded entries standardize to
    # exactly zero; padded rows have mu=0, rs=0 and contribute zeros too.
    if pl_pad:
        X = jnp.concatenate([X, jnp.broadcast_to(mu, (n, pl_pad))], axis=1)
    Xp = jnp.pad(X, ((0, pn), (0, 0)))
    mup = jnp.pad(mu, ((0, pn), (0, 0)))
    rsp = jnp.pad(rs, ((0, pn), (0, 0)))
    N, Lp = Xp.shape

    out = pl.pallas_call(
        _pearson_kernel,
        grid=(N // bm_, N // bn_, Lp // bl_),
        in_specs=[
            pl.BlockSpec((bm_, bl_), lambda i, j, l: (i, l)),   # x tile
            pl.BlockSpec((bn_, bl_), lambda i, j, l: (j, l)),   # y tile
            pl.BlockSpec((bm_, 1), lambda i, j, l: (i, 0)),     # mean(x)
            pl.BlockSpec((bm_, 1), lambda i, j, l: (i, 0)),     # rstd(x)
            pl.BlockSpec((bn_, 1), lambda i, j, l: (j, 0)),     # mean(y)
            pl.BlockSpec((bn_, 1), lambda i, j, l: (j, 0)),     # rstd(y)
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, N), jnp.float32),
        interpret=interpret,
    )(Xp, Xp, mup, rsp, mup, rsp)
    return jnp.clip(out[:n, :n], -1.0, 1.0)
