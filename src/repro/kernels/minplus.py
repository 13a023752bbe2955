"""Pallas TPU kernel: blocked min-plus (tropical) matrix multiplication.

The workhorse of APSP (DESIGN.md §4.3): exact APSP is ⌈log2 n⌉ tropical
squarings; hub-APSP composes ``(n,h)·(h,n)`` through hub rows.  On TPU the
inner ``min(a[i,k] + b[k,j])`` cannot use the MXU (no multiply-accumulate in
the tropical semiring), so the kernel is VPU-bound: the grid walks
``(bm, bn)`` output tiles with the k panel innermost, and inside a grid
step an in-kernel loop walks the panel eight k at a time.  Each step
loads an (8, bm) slab of ``A.T`` and an (8, bn) slab of ``B`` — both
sublane-aligned — turns the A slab into eight (bm, 1) columns and folds
``col + row`` into the (bm, bn) running minimum.  No (bm, bk, bn)
intermediate exists.

Block rule (Mosaic's (8, 128) tiling): A is passed transposed, so m and
n are lane axes — each is one full-width block when it fits the block,
else blocks of ``bm``/``bn`` (multiples of 128); k is the sublane axis
of both operands, padded with +inf (the tropical zero) to a multiple
of 8 and blocked by ``bk`` (a multiple of 8) when longer.

VMEM at the default (128, 128, 128) f32 blocks: A.T tile 64 KiB +
B tile 64 KiB + out 64 KiB, double-buffered inputs « 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _minplus_kernel(at_ref, b_ref, o_ref):
    """Grid (i, j, k): o[i,j] = min_k tropical_prod(a[i,k], b[k,j]).

    ``at_ref`` is the (bk, bm) tile of A.T, ``b_ref`` the (bk, bn) tile
    of B; the k panel is walked in sublane-aligned slabs of 8."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    def slab(c, acc):
        k0 = pl.multiple_of(c * 8, 8)
        a = at_ref[pl.ds(k0, 8), :].T                 # (bm, 8)
        b = b_ref[pl.ds(k0, 8), :]                    # (8, bn)
        for t in range(8):
            acc = jnp.minimum(acc, a[:, t:t + 1] + b[t:t + 1, :])
        return acc

    o_ref[...] = lax.fori_loop(0, at_ref.shape[0] // 8, slab, o_ref[...])


def _block(size: int, block: int, align: int) -> tuple[int, int]:
    """(block, padded size) for one axis: one block of the whole
    ``align``-padded axis when it fits, else ``block``-sized blocks."""
    full = -(-size // align) * align
    if full <= block:
        return full, full
    assert block % align == 0, (block, align)
    return block, -(-size // block) * block


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def minplus_pallas(A: jax.Array, B: jax.Array, *, bm: int = 128, bk: int = 128,
                   bn: int = 128, interpret: bool = False) -> jax.Array:
    """Tropical matmul via pallas_call.  Shapes need not divide the blocks;
    inputs are padded with +inf (the tropical zero) and the result cropped."""
    m, k = A.shape
    k2, n = B.shape
    assert k == k2, (A.shape, B.shape)
    bm_, M = _block(m, bm, 1)
    bn_, N = _block(n, bn, 1)
    bk_, K = _block(k, bk, 8)
    At = jnp.pad(A.astype(jnp.float32).T, ((0, K - k), (0, M - m)),
                 constant_values=jnp.inf)
    Bp = jnp.pad(B.astype(jnp.float32), ((0, K - k), (0, N - n)),
                 constant_values=jnp.inf)

    out = pl.pallas_call(
        _minplus_kernel,
        grid=(M // bm_, N // bn_, K // bk_),
        in_specs=[
            pl.BlockSpec((bk_, bm_), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(At, Bp)
    return out[:m, :n]


def minplus_jnp(A: jax.Array, B: jax.Array, *, panel: int = 128) -> jax.Array:
    """Pure-jnp blocked fallback with O(m·panel·n) peak memory.

    Used on the CPU dev container (Pallas interpret mode is a Python grid
    loop — far too slow for production paths) and as the XLA:TPU baseline
    the Pallas kernel is benchmarked against.
    """
    m, k = A.shape
    _, n = B.shape
    panel = min(panel, k)
    pk = (-k) % panel
    Ap = jnp.pad(A.astype(jnp.float32), ((0, 0), (0, pk)),
                 constant_values=jnp.inf)
    Bp = jnp.pad(B.astype(jnp.float32), ((0, pk), (0, 0)),
                 constant_values=jnp.inf)
    nk = Ap.shape[1] // panel

    def body(c, idx):
        a = jax.lax.dynamic_slice(Ap, (0, idx * panel), (m, panel))
        b = jax.lax.dynamic_slice(Bp, (idx * panel, 0), (panel, n))
        c = jnp.minimum(c, jnp.min(a[:, :, None] + b[None, :, :], axis=1))
        return c, None

    init = jnp.full((m, n), jnp.inf, jnp.float32)
    out, _ = jax.lax.scan(body, init, jnp.arange(nk))
    return out
