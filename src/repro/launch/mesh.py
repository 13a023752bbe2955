"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""

from __future__ import annotations

import jax


def _make(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``pod`` — pure data parallelism across pods (DCN-connected);
    ``data`` — DP + FSDP/ZeRO-3 within a pod; ``model`` — TP (and EP for
    MoE experts).  The same mesh serves the clustering pipeline (S rows
    over (pod, data); see core/distributed.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh helper (tests, examples, elastic restarts)."""
    return _make(shape, axes, devices)
