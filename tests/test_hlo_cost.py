"""The trip-count-aware HLO cost model vs known-FLOP programs."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.launch import hlo_cost


def _cost(f, *sds):
    compiled = jax.jit(f).lower(*sds).compile()
    return hlo_cost.analyze(compiled.as_text()), compiled


def test_plain_matmul_flops():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    totals, _ = _cost(f, a, b)
    want = 2 * 128 * 256 * 64
    assert abs(totals.flops - want) / want < 0.05, totals.flops


def test_scan_multiplies_trip_count():
    """THE reason this module exists: XLA counts while bodies once."""

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = lax.scan(body, x, ws)
        return x.sum()

    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    totals, compiled = _cost(f, ws, x)
    want = 8 * 2 * 128 * 256 * 256
    xla = compiled.cost_analysis()["flops"]
    assert xla < want / 4, "XLA undercounts (that's the premise)"
    assert abs(totals.flops - want) / want < 0.10, \
        f"got {totals.flops}, want ~{want}"


def test_nested_scan():
    def f(ws, x):
        def outer(x, wpair):
            def inner(x, w):
                return x @ w, None
            x, _ = lax.scan(inner, x, wpair)
            return x, None
        x, _ = lax.scan(outer, x, ws)
        return x.sum()

    ws = jax.ShapeDtypeStruct((4, 2, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    totals, _ = _cost(f, ws, x)
    want = 8 * 2 * 32 * 64 * 64
    assert abs(totals.flops - want) / want < 0.15, totals.flops


def test_collective_accounting():
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_cost
        mesh = jax.make_mesh((8,), ("data",))
        def f(x):
            return x.sum()
        sds = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
        c = jax.jit(f, in_shardings=NamedSharding(mesh, P("data", None)),
                    out_shardings=NamedSharding(mesh, P())).lower(sds).compile()
        t = hlo_cost.analyze(c.as_text())
        assert t.collective_counts.get("all-reduce", 0) >= 1, t.collective_counts
        # scalar f32 all-reduce over 8 devices: wire = 2*(7/8)*4 bytes
        assert 0 < t.collective_wire_bytes < 1e4, t.collective_wire_bytes
        print("COLL-OK")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "COLL-OK" in proc.stdout


def test_bytes_nonzero_and_bounded():
    f = lambda a: (a * 2 + 1).sum()
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    totals, _ = _cost(f, a)
    nbytes = 1024 * 1024 * 4
    assert totals.hbm_bytes >= nbytes * 0.5
    assert totals.hbm_bytes <= nbytes * 10
