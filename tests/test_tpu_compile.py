"""The main-path kernels compile for a TPU v5e chip, at real widths.

No chip is needed: the TPU compiler compiles for a v5e:2x2 topology that is
described, not attached.  What interpret mode cannot show — a block that
breaks Mosaic's (8, 128) tiling, a primitive with no Mosaic lowering — fails
here.  The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.  The widths are
the paper's Crop dataset (n=19,412, L=46) and the hub factor h = ceil(sqrt n).
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.tmfg import build_tmfg
from repro.kernels.gainscan import masked_argmax_pallas
from repro.kernels.minplus import minplus_pallas
from repro.kernels.pearson import pearson_pallas
from repro.kernels.sparse_apsp import CSRGraph, sparse_relax
from repro.kernels.topk import topk_pearson_pallas, topk_rows_pallas

N, L, K = 19412, 46, 64
H = 140                                     # ceil(sqrt(19412))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_pearson_compiles(shape):
    _compiles_to_kernel(pearson_pallas, shape(N, L))


@pytest.mark.parametrize("rows", [4096, 4])
def test_masked_argmax_compiles(shape, rows):
    _compiles_to_kernel(masked_argmax_pallas, shape(rows, N),
                        shape(N, dtype=jnp.bool_))


@pytest.mark.parametrize("m,k,n", [
    (N, H, N),          # hub composition (n, h)·(h, n)
    (H, N, N),          # hub relaxation round (h, n)·(n, n)
    (2000, 45, 2000),   # the hub shapes at n=2,000
    (512, 512, 512),    # exact APSP squaring
])
def test_minplus_compiles(shape, m, k, n):
    _compiles_to_kernel(minplus_pallas, shape(m, k), shape(k, n))


def test_topk_compiles(shape):
    _compiles_to_kernel(lambda x: topk_pearson_pallas(x, K), shape(N, L))


def test_topk_row_panel_compiles(shape):
    """One device's panel of the four-chip sharded scan."""
    m = -(-N // 4)
    _compiles_to_kernel(lambda zr, z, rid: topk_rows_pallas(zr, z, rid, K),
                        shape(m, L), shape(N, L), shape(m, dtype=jnp.int32))


def test_sparse_relax_compiles_as_xla(shape):
    """sparse_relax has no Mosaic form (Mosaic cannot gather along the
    lane axis): its one XLA form compiles for the chip at Crop width,
    with no kernel call, whatever backend is asked for."""
    m = 2 * (3 * N - 6)
    graph = CSRGraph(indptr=shape(N + 1, dtype=jnp.int32),
                     rows=shape(m, dtype=jnp.int32),
                     cols=shape(m, dtype=jnp.int32), vals=shape(m))
    text = jax.jit(lambda d, g: sparse_relax(d, g, backend="pallas")).lower(
        shape(H, N), graph).compile().as_text()
    assert "tpu_custom_call" not in text


_CALLS = re.compile(r"(?:calls|to_apply|body|condition|true_computation"
                    r"|false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MOVE = re.compile(r"^\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\]\S*"
                   r"(?:, \S+)* (copy|copy-start|select)\(")


def _table_moves(hlo: str, n: int):
    """Copies (plain, or an async ``copy-start`` whose output is a tuple)
    and selects reached from a while loop's body whose output has the
    shape of one of the TMFG tables, (F, 3) faces, (E, 2) edges, (B, 4)
    and (B, 3) bubbles, 2-D or flat, batched or not."""
    F, E, B = 2 * n - 4, 3 * n - 6, n - 3
    tables = {(F, 3), (E, 2), (B, 4), (B, 3)}
    tables |= {(r * k,) for r, k in tables}
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            name = line.split()[line.startswith("ENTRY")].lstrip("%")
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)

    def callees(line):
        out = _CALLS.findall(line)
        for group in _BRANCHES.findall(line):
            out += [c.strip().lstrip("%") for c in group.split(",")]
        return out

    todo = [m for lines in comps.values() for line in lines
            if " while(" in line
            for m in re.findall(r"body=%([\w.\-]+)", line)]
    assert todo, "no while loop in the program"
    seen, moves = set(), []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += callees(line)
            m = _MOVE.match(line)
            if m:
                dims = tuple(int(d) for d in m.group(2).split(",") if d)
                if any(dims[-len(t):] == t for t in tables):
                    moves.append(f"{c}: {m.group(3)} %{m.group(1)} {dims}")
    return moves


@pytest.mark.parametrize("batch,n", [(None, 9236), (64, 500)])
def test_lazy_tmfg_loop_moves_no_table(shape, batch, n):
    """The lazy loop updates its carried tables in place: no copy and no
    select of a whole table per pop, alone (StarLightCurves' n) or under
    ``vmap`` (a backtest batch).  A table carried through a ``lax.cond``
    is copied out of the branch, and a batched ``while_loop`` selects
    every table after every pop; on this chip an (N, <=4) int table pads
    its minor dimension to 128 lanes, so each such move costs 32-64x the
    table.  A table staged between memory spaces on every pop (an async
    copy pair) is a move too."""
    build = lambda s: build_tmfg(s, method="lazy", topk=64)
    if batch is None:
        fn, arg = build, shape(n, n)
    else:
        fn, arg = jax.vmap(build), shape(batch, n, n)
    hlo = jax.jit(fn).lower(arg).compile().as_text()
    assert _table_moves(hlo, n) == []
