"""Seeded inputs of the benchmark: the yardstick's own copy, so that no
change to the program can move the data it is measured on.

* ``ucr_like``: synthetic stand-in for a UCR dataset of the paper's
  Table 1 (k smooth prototype curves, each sample a warped, scaled and
  noised copy).  A copy of the program's ``data.timeseries`` generator.
* ``sector_returns``: daily returns of an equity universe under a
  one-market, many-sector factor model with heavy-tailed shocks.
"""

from __future__ import annotations

import numpy as np

def _prototype(L: int, rng: np.random.Generator) -> np.ndarray:
    t = np.linspace(0.0, 1.0, L)
    y = np.zeros(L)
    for _ in range(rng.integers(2, 5)):
        f = rng.uniform(0.5, 6.0)
        ph = rng.uniform(0, 2 * np.pi)
        a = rng.uniform(0.5, 1.5)
        y += a * np.sin(2 * np.pi * f * t + ph)
    y += rng.uniform(-1, 1) * t
    return y


def ucr_like(n: int, L: int, k: int, *, noise: float, warp: float,
             seed: int) -> np.ndarray:
    """(n, L) float32 rows in k latent classes (labels are not kept)."""
    rng = np.random.default_rng(seed)
    protos = np.stack([_prototype(L, rng) for _ in range(k)])
    labels = rng.integers(0, k, size=n)
    t = np.linspace(0.0, 1.0, L)
    X = np.empty((n, L), np.float32)
    for i in range(n):
        shift = rng.uniform(-warp, warp)
        base = np.interp(np.clip(t + shift, 0, 1), t, protos[labels[i]])
        X[i] = (rng.uniform(0.7, 1.3) * base
                + noise * rng.normal(size=L)).astype(np.float32)
    return X


def sector_returns(n: int, days: int, sector_weights, *, market_vol: float,
                   sector_vol: float, idio_vol: float, tail_df: float,
                   seed: int) -> np.ndarray:
    """(n, days) float32 daily returns.

    r[i, t] = beta_i * m_t + gamma_i * f[s(i), t] + sigma_i * e[i, t], with
    sector s(i) drawn in proportion to ``sector_weights``, loadings and
    idiosyncratic scales drawn per name, and every shock Student-t with
    ``tail_df`` degrees of freedom scaled to unit variance."""
    rng = np.random.default_rng(seed)
    w = np.asarray(sector_weights, np.float64)
    sector = rng.choice(len(w), size=n, p=w / w.sum())

    def shocks(*shape):
        z = rng.standard_t(tail_df, size=shape)
        return z * np.sqrt((tail_df - 2.0) / tail_df)

    beta = rng.uniform(0.6, 1.4, size=n)
    gamma = rng.uniform(0.5, 1.5, size=n)
    sigma = idio_vol * rng.uniform(0.6, 1.6, size=n)
    m = market_vol * shocks(days)
    f = sector_vol * shocks(len(w), days)
    r = (beta[:, None] * m[None, :] + gamma[:, None] * f[sector]
         + sigma[:, None] * shocks(n, days))
    return r.astype(np.float32)
