"""Incremental rolling-window co-moment state (DESIGN.md §10.1).

The streaming use case recomputes the Pearson similarity of an (n, L)
rolling window every time a tick arrives.  From scratch that is the full
O(n²L) ``ops.pearson``; here we keep the window's *co-moments* about a
per-series shift origin r (each series' first tick) —

    s1[i]    = Σ_t (x_i(t) − r_i)                  (n,)
    s2[i,j]  = Σ_t (x_i(t) − r_i)(x_j(t) − r_j)    (n, n)

— so appending one tick and evicting the oldest is a rank-1 update:
O(n²) work per tick, an L-fold reduction.  Covariance is
shift-invariant, so the Pearson matrix follows from the moment identity

    corr = (s2/m − μμᵀ) / sqrt(var varᵀ),   μ = s1/m

unchanged.  The shift is load-bearing for precision: price-like series
(level ≫ move size — the paper's canonical streaming input) would put
mean² ≫ var into the raw moments and the subtraction would cancel away
every significant digit of the variance in float32; anchored at the
first tick, the accumulated values are move-sized and the identity is
well-conditioned.

Accumulation is float64 when jax x64 is enabled, otherwise *compensated*
float32 (Kahan): every state sum carries a running compensation term, so
the error per entry stays O(ε·|sum|) instead of growing with the number
of push/evict cycles.  ``window_similarity`` is validated against
``ops.pearson`` on the materialized window to ≤1e-5 across fill, wrap,
long-run eviction, and high-mean/low-variance regimes
(tests/test_stream.py).

All state transitions are jit'd; the state is a NamedTuple of arrays so
it passes through jit/scan/device_put as a pytree.  The ring buffer is
kept alongside the moments — eviction needs the outgoing column, and
``materialize`` gives the validation/benchmark path the exact window.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.ref import SIM_PRECISION


class WindowState(NamedTuple):
    """Rolling-window ring buffer + compensated co-moment sums.

    Moments are accumulated about a per-series reference point ``ref``
    (the series' first tick): covariance is shift-invariant, and for
    price-like data (level ≫ move size) the shift is what keeps the
    moment-form ``s2/m − μμᵀ`` out of catastrophic float32 cancellation
    — raw second moments would carry mean² ≫ var and the subtraction
    would lose every significant digit of the variance.
    """

    buf: jax.Array     # (n, L) ring buffer of ticks, column ``head`` next
    head: jax.Array    # () int32 — next write slot
    count: jax.Array   # () int32 — valid ticks, ≤ L
    ref: jax.Array     # (n,)   per-series shift origin (first tick seen)
    s1: jax.Array      # (n,)   Σ (x − ref)
    c1: jax.Array      # (n,)   compensation for s1
    s2: jax.Array      # (n, n) Σ (x − ref)(x − ref)ᵀ
    c2: jax.Array      # (n, n) compensation for s2

    @property
    def n(self) -> int:
        return self.buf.shape[0]

    @property
    def capacity(self) -> int:
        return self.buf.shape[1]


def _acc_dtype() -> jnp.dtype:
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def window_init(n: int, capacity: int) -> WindowState:
    """Empty rolling window for n series with ``capacity`` ticks."""
    dt = _acc_dtype()
    return WindowState(
        buf=jnp.zeros((n, capacity), jnp.float32),
        head=jnp.int32(0), count=jnp.int32(0),
        ref=jnp.zeros((n,), jnp.float32),
        s1=jnp.zeros((n,), dt), c1=jnp.zeros((n,), dt),
        s2=jnp.zeros((n, n), dt), c2=jnp.zeros((n, n), dt))


def _kahan_add(s, c, d):
    """One compensated accumulation step: (s, c) += d."""
    y = d - c
    t = s + y
    return t, (t - s) - y


def _full_moments(buf: jax.Array, ref: jax.Array, dt):
    """Moments of the whole ring about a fresh origin — the re-anchor
    path.  Plain XLA sums/matmul: its one-shot error is the same class
    as ``ops.pearson``'s own accumulation, and the compensation terms
    restart at zero."""
    Z = (buf - ref[:, None]).astype(dt)
    s1 = jnp.sum(Z, axis=1)
    s2 = jnp.dot(Z, Z.T, precision=SIM_PRECISION)
    return ref, s1, jnp.zeros_like(s1), s2, jnp.zeros_like(s2)


def _push_step(st: WindowState, x: jax.Array) -> WindowState:
    """One append+evict transition — the body shared (bitwise) by
    ``window_push`` and the scan inside ``window_push_block``."""
    L = st.buf.shape[1]
    x = x.astype(jnp.float32)
    ref = jnp.where(st.count == 0, x, st.ref)
    old = jax.lax.dynamic_slice_in_dim(st.buf, st.head, 1, axis=1)[:, 0]
    dt = st.s1.dtype
    xd = (x - ref).astype(dt)
    od = jnp.where(st.count == L, (old - ref).astype(dt), 0.0)

    s1, c1 = _kahan_add(st.s1, st.c1, xd - od)
    s2, c2 = _kahan_add(st.s2, st.c2,
                        jnp.outer(xd, xd) - jnp.outer(od, od))

    buf = jax.lax.dynamic_update_slice_in_dim(
        st.buf, x[:, None], st.head, axis=1)
    head = (st.head + 1) % L
    count = jnp.minimum(st.count + 1, L)

    wrapped = (head == 0) & (count == L)       # completed one full pass
    ref, s1, c1, s2, c2 = jax.lax.cond(
        wrapped,
        lambda _: _full_moments(buf, x, dt),
        lambda _: (ref, s1, c1, s2, c2),
        None)
    return WindowState(buf=buf, head=head, count=count, ref=ref,
                       s1=s1, c1=c1, s2=s2, c2=c2)


@jax.jit
def window_push(st: WindowState, x: jax.Array) -> WindowState:
    """Append tick x (n,) — evicting the oldest when full — in O(n²)
    amortized.

    The shift origin ``ref`` starts at the first tick, and is
    *re-anchored to the newest tick* every time the ring completes a
    full pass: levels that random-walk away from the original anchor
    would otherwise re-grow the mean² ≫ var cancellation the shift
    exists to prevent.  The refresh recomputes the moments from the ring
    buffer — O(n²L) once every L ticks, i.e. O(n²) amortized, the same
    order as the incremental update — and also discards any error the
    rank-1 stream accumulated, so precision is bounded by the drift
    *within one window*, not the lifetime of the stream.

    Between refreshes the update is rank-1: the outgoing column at
    ``head`` contributes only once the ring has wrapped, and both
    contributions go through one compensated add per state sum.
    """
    return _push_step(st, x)


@jax.jit
def window_push_block(st: WindowState, X: jax.Array) -> WindowState:
    """Apply a block of B pending ticks (columns of X, (n, B), oldest
    first) in ONE device dispatch.

    Bitwise-identical to B sequential ``window_push`` calls — the block
    is a ``lax.scan`` over the same ``_push_step`` transition, so every
    Kahan compensation and ring re-anchor happens in the same order.
    What changes is the dispatch count: at bench scale the per-call
    launch overhead of tick-at-a-time pushes costs more than the
    clustering work itself (BENCH_7 ``stream/service*`` losing to
    scratch at 0.58–0.61×), so the service buffers ticks host-side and
    flushes them here before any state read.
    """
    def step(s, x):
        return _push_step(s, x), None
    out, _ = jax.lax.scan(step, st, X.T.astype(jnp.float32))
    return out


@jax.jit
def window_similarity(st: WindowState) -> jax.Array:
    """(n, n) Pearson matrix of the current window from the co-moments.

    O(n²) — no pass over the L time steps.  Matches ``ops.pearson`` on
    the materialized window to ≤1e-5 (exact identity in real arithmetic;
    the gap is float rounding, bounded by the compensated accumulation).

    Degenerate series — windowed variance below 1e-6 of the *shifted*
    second moment E[(x−ref)²], e.g. a halted instrument ticking a
    constant — get zero correlation everywhere *including the diagonal*,
    matching what ``pearson_ref`` produces for an exactly-constant row
    (its centered row is 0).  Below that threshold the moment-form
    variance is cancellation noise in float32, so no meaningful
    correlation exists to report anyway.
    """
    m = jnp.maximum(st.count, 1).astype(st.s1.dtype)
    mu = st.s1 / m
    ms = jnp.maximum(jnp.diagonal(st.s2) / m, 0.0)      # E[x²] per series
    cov = st.s2 / m - jnp.outer(mu, mu)
    var = jnp.maximum(jnp.diagonal(cov), 0.0)
    good = var > 1e-6 * jnp.maximum(ms, 1e-30)          # non-degenerate
    denom = jnp.sqrt(jnp.outer(var, var)) + 1e-12
    corr = jnp.clip(cov / denom, -1.0, 1.0)
    corr = jnp.where(jnp.outer(good, good), corr, 0.0)
    n = corr.shape[0]
    corr = corr.at[jnp.arange(n), jnp.arange(n)].set(
        jnp.where(good, 1.0, 0.0))
    return corr.astype(jnp.float32)


def window_delta(st: WindowState, S_prev, S_now=None) -> float:
    """mean |S_now − S_prev| — the similarity delta the warm-start cache
    thresholds on (DESIGN.md §10.3; mean rather than max because any
    single windowed-correlation entry carries O(1/√L) sampling noise —
    see stream/cache.py).  ``S_now`` defaults to the state's current
    similarity."""
    if S_now is None:
        S_now = window_similarity(st)
    return float(jnp.mean(jnp.abs(jnp.asarray(S_now) - jnp.asarray(S_prev))))


def materialize(st: WindowState) -> np.ndarray:
    """The window as an (n, count) array in arrival order (host-side;
    validation and benchmarking only — the O(n²) path never calls this)."""
    buf = np.asarray(st.buf)
    head, count = int(st.head), int(st.count)
    L = buf.shape[1]
    ordered = np.roll(buf, -head, axis=1)
    return ordered[:, L - count:]
