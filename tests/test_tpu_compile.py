"""The main-path kernels compile for a TPU v5e chip, at real widths.

No chip is needed: the TPU compiler compiles for a v5e:2x2 topology that is
described, not attached.  What interpret mode cannot show — a block that
breaks Mosaic's (8, 128) tiling, a primitive with no Mosaic lowering — fails
here.  The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.  The widths are
the paper's Crop dataset (n=19,412, L=46) and the hub factor h = ceil(sqrt n).
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

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
