"""Mesh-aware placement rules for every pytree the system moves (DESIGN.md §7.1).

One module owns the question "which mesh axis does each tensor dimension
map to?" for both workloads sharing the production mesh:

* **LM zoo** — :func:`param_specs` / :func:`param_shardings` give every
  parameter (and optimizer-state) leaf a legal, memory-sane
  ``PartitionSpec`` on any mesh built by launch/mesh.py: tensor-parallel
  over ``model``, FSDP/ZeRO-3 over ``(pod, data)``, experts
  expert-parallel when the expert count divides the ``model`` axis.
  :func:`batch_shardings` places token batches over the data axes.
* **Clustering pipeline** — :func:`timeseries_spec` /
  :func:`similarity_spec` / :func:`batch_matrix_spec` are the canonical
  layouts of the paper's arrays (X row-sharded, S column-sharded, batched
  S over the batch axis), and :func:`pearson_shardmap`,
  :func:`masked_argmax_shardmap`, :func:`minplus_shardmap` are the
  standalone sharded entry points for the three kernels
  (kernels/{pearson,gainscan,minplus}.py): each device works its block
  and the only cross-device traffic is the one collective the algorithm
  actually needs.  ``core/distributed.py`` routes its Pearson stage
  through the wrapper; its TMFG/APSP loops fuse more specialized
  shard_map bodies (column-sharded lookups, batched per-step
  collectives) that these row-sharded wrappers intentionally don't
  cover.

Every rule degrades gracefully: axes missing from the mesh are skipped,
dimensions that don't divide an axis stay replicated, and a 1-device mesh
produces fully-replicated specs — which is what keeps CPU CI identical to
the production path.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaves smaller than this many elements are simply replicated: sharding
# them saves nothing and costs a collective on every use
_MIN_SHARD_ELEMS = 1 << 16


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The pure-data-parallel axes present in ``mesh`` (pod before data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def axis_size(mesh: Mesh, axes) -> int:
    """Total extent of one axis name or a tuple of axis names."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def data_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh over (the first) ``n_devices`` for data-parallel batching.

    The clustering pipeline only needs one axis (DESIGN.md §4.4); LM
    launches build richer meshes with launch/mesh.py instead.
    """
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    return make_mesh((n,), (axis,), devices=devs[:n])


# ---------------------------------------------------------------------------
# clustering-pipeline layouts (the paper's arrays)
# ---------------------------------------------------------------------------

def timeseries_spec(axis="data") -> P:
    """X (n, L): rows (series) sharded, time replicated."""
    return P(axis, None)


def similarity_spec(axis="data") -> P:
    """S (n, n): column-sharded — every row scan becomes a local scan over
    n/d columns plus one tiny (value, index) all-gather (DESIGN.md §4.4)."""
    return P(None, axis)


def batch_matrix_spec(axis="data") -> P:
    """A batch (B, n, n) of similarity matrices: pure data parallelism over
    the batch axis; each matrix lives whole on one device."""
    return P(axis, None, None)


def batch_timeseries_spec(axis="data") -> P:
    """A batch (B, n, L) of datasets, batch-sharded."""
    return P(axis, None, None)


# ---------------------------------------------------------------------------
# shard-aware kernel wrappers
# ---------------------------------------------------------------------------

def pearson_shardmap(X: jax.Array, mesh: Mesh, axis="data") -> jax.Array:
    """Pearson similarity with X row-sharded; S returned column-sharded.

    Each device standardizes its local rows (kernels/ref.py
    ``standardize_rows`` — the same math the fused Pallas kernel uses),
    all-gathers the standardized block (the only collective), and runs
    the local (n, L) x (L, n/d) product as a plain XLA matmul: the
    cross-block product has no fusable normalization left, so there is
    no kernel to dispatch to and no ``backend`` knob here.
    """
    from repro.kernels import ref as kref  # local import: no cycle

    def f(xl):
        z = kref.standardize_rows(xl.astype(jnp.float32))
        zf = lax.all_gather(z, axis, tiled=True)          # (n, L)
        return jnp.clip(jnp.dot(zf, z.T, precision=kref.SIM_PRECISION),
                        -1.0, 1.0)                        # (n, n/d)

    return jax.shard_map(
        f, mesh=mesh, in_specs=timeseries_spec(axis),
        out_specs=similarity_spec(axis))(X)


def topk_pearson_sharded(X: jax.Array, k: int, mesh: Mesh, axis="data",
                         *, bm: int = 128, backend: str = "auto"):
    """Blocked top-K Pearson with X row-sharded (DESIGN.md §17.4).

    Each device owns a row panel: standardize local rows, all-gather
    the standardized series (the one collective), then run the
    single-device scan's own kernel (``kernels.ops.topk_rows``: the
    Pallas streaming kernel on a TPU, the jnp row-panel scan elsewhere)
    on the local rows against all n columns.  Each row's top-K depends
    on that row alone, so the table is bitwise the single-device table
    (value desc, index asc tie order) on every backend.

    Returns ``(values (n, k), indices (n, k), Z (n, L))`` — Z is the
    standardized series the sparse TMFG's exact-value fallback reads.
    Rows are padded to the axis size internally; pad rows never appear
    as candidates.
    """
    from repro.kernels import ops                # local import: no cycle
    from repro.kernels import ref as kref

    X = jnp.asarray(X, jnp.float32)
    n, L = X.shape
    k = min(int(k), n - 1)
    d = axis_size(mesh, axis)
    pad = (-n) % d
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, L), jnp.float32)])
    n_loc = (n + pad) // d

    def f(xl):
        z = kref.standardize_rows(xl)                       # (n_loc, L)
        zf = lax.all_gather(z, axis, tiled=True)            # (n_pad, L)
        rid = lax.axis_index(axis) * n_loc + jnp.arange(n_loc,
                                                        dtype=jnp.int32)
        v, i = ops.topk_rows(z, zf[:n], rid, k, backend=backend,
                             bm=min(bm, n_loc))
        return v, i, zf

    v, i, z = jax.shard_map(
        f, mesh=mesh, in_specs=timeseries_spec(axis),
        out_specs=(P(axis, None), P(axis, None), P()),
        check_vma=False)(X)
    return v[:n], i[:n], z[:n]


def masked_argmax_shardmap(S: jax.Array, mask: jax.Array, mesh: Mesh,
                           axis="data", *, backend: str = "auto"):
    """Per-row masked (max, argmax) with S *row*-sharded: the gain-scan
    kernel is embarrassingly parallel over rows, so each device scans its
    block with kernels.ops.masked_argmax and no collective is needed."""
    from repro.kernels import ops

    def f(sl):
        return ops.masked_argmax(sl, mask, backend=backend)

    return jax.shard_map(
        f, mesh=mesh, in_specs=P(axis, None), out_specs=(P(axis), P(axis)),
        check_vma=False)(S)


def minplus_shardmap(A: jax.Array, B: jax.Array, mesh: Mesh, axis="data", *,
                     backend: str = "auto") -> jax.Array:
    """Tropical matmul with A row-sharded and B replicated.

    out[i, j] = min_k A[i, k] + B[k, j]; the row blocks are independent,
    so each device runs the min-plus Pallas kernel on (n/d, n) x (n, n)
    and the result stays row-sharded — the layout apsp.py wants for the
    next squaring (DESIGN.md §4.3)."""
    from repro.kernels import ops

    def f(al, b):
        return ops.minplus(al, b, backend=backend)

    return jax.shard_map(
        f, mesh=mesh, in_specs=(P(axis, None), P(None, None)),
        out_specs=P(axis, None), check_vma=False)(A, B)


# ---------------------------------------------------------------------------
# LM parameter placement
# ---------------------------------------------------------------------------

def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        if name is None and hasattr(k, "idx"):
            name = str(k.idx)
        out.append(str(name))
    return tuple(out)


def _assign(spec, shape, dim_order, axes, size, taken):
    """Put ``axes`` on the first dim in ``dim_order`` it divides; mutate
    ``spec``/``taken`` and report success."""
    if size <= 1:
        return False
    for i in dim_order:
        if i in taken:
            continue
        if shape[i] % size == 0:
            spec[i] = axes if isinstance(axes, str) or len(axes) > 1 \
                else axes[0]
            taken.add(i)
            return True
    return False


def _fsdp_assign(spec, shape, dim_order, mesh, taken):
    """FSDP axis assignment with graceful narrowing: try the full
    (pod, data) product, then single axes widest-first (data before pod
    — the wide ICI axis beats the narrow cross-DCN one 16x on per-device
    memory when the full product doesn't divide)."""
    groups = [data_axes(mesh)]
    if len(groups[0]) > 1:
        groups += [(a,) for a in
                   sorted(groups[0], key=lambda a: -mesh.shape[a])]
    for axes in groups:
        if axes and _assign(spec, shape, dim_order, tuple(axes),
                            axis_size(mesh, axes), taken):
            return True
    return False


def _leaf_spec(names, shape, mesh, embed_mode, weights_mode) -> P:
    ndim = len(shape)
    if ndim == 0 or int(np.prod(shape)) < _MIN_SHARD_ELEMS:
        return P()

    model = axis_size(mesh, "model") if "model" in mesh.shape else 1
    spec = [None] * ndim
    taken = set()

    # never shard the stacked-layer leading axis: it is scanned over, and
    # slicing a scan operand across devices serializes the scan
    stacked = "layers" in names and ndim >= 2
    dims = list(range(1 if stacked else 0, ndim))

    if "embed" in names and ndim >= 2 and not stacked:
        # (vocab_padded, d_model); vocab is padded to a multiple of 128
        # exactly so both axes divide (configs/base.py vocab_padded)
        if embed_mode in ("2d", "dmodel") and model > 1:
            _assign(spec, shape, [ndim - 1], "model", model, taken)
        if embed_mode in ("2d", "vdata"):
            _fsdp_assign(spec, shape, [0], mesh, taken)
        return P(*spec)

    # tensor parallelism: the last dimension that divides the model axis
    # (output features for up-projections, d_model for down-projections;
    # for (L, E, d, ff) expert stacks this lands on ff and leaves E for
    # FSDP — expert-parallel serving instead pins layouts via dist.hints)
    if model > 1:
        _assign(spec, shape, list(reversed(dims)), "model", model, taken)

    # FSDP/ZeRO-3 over (pod, data): largest remaining divisible dim.
    # weights_mode="tp_only" (ZeRO-1) keeps parameters TP-sharded only;
    # the optimizer state still takes the full 2-D layout.
    if weights_mode != "tp_only":
        order = sorted((i for i in dims if i not in taken),
                       key=lambda i: -shape[i])
        _fsdp_assign(spec, shape, order, mesh, taken)
    return P(*spec)


def param_specs(params: Any, mesh: Mesh, *, embed_mode: str = "2d",
                weights_mode: str = "2d") -> Any:
    """``PartitionSpec`` for every leaf of a parameter/optimizer pytree.

    Args:
      params: pytree of arrays or ShapeDtypeStructs (eval_shape output).
      mesh: any mesh from launch/mesh.py; missing axes are skipped.
      embed_mode: "2d" (vocab over FSDP axes + d_model over model;
        default), "dmodel" (model only — pairs with the one-hot-embed
        hint), or "vdata" (vocab over data only).
      weights_mode: "2d" (TP + FSDP; default) or "tp_only" (ZeRO-1:
        params TP-sharded, optimizer state still fully sharded).

    Every produced spec is *legal* (each assigned axis divides the dim)
    and memory-sane: no leaf above a few hundred MB stays replicated on
    the production meshes (pinned by tests/test_sharding.py).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [
        _leaf_spec(_path_names(path), tuple(leaf.shape), mesh,
                   embed_mode, weights_mode)
        for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params: Any, mesh: Mesh, *, embed_mode: str = "2d",
                    weights_mode: str = "2d") -> Any:
    """:func:`param_specs` materialized as ``NamedSharding`` leaves."""
    specs = param_specs(params, mesh, embed_mode=embed_mode,
                        weights_mode=weights_mode)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch placement
# ---------------------------------------------------------------------------

def batch_specs(mesh: Mesh, batch: Any) -> Any:
    """Batch leaves shard dim 0 over the data axes when it divides.

    Meshes without a ``pod``/``data`` axis (user-supplied 1-D meshes with
    custom names) fall back to the mesh's first axis; leaves whose batch
    dim doesn't divide replicate.
    """
    axes = data_axes(mesh) or tuple(mesh.shape)[:1]
    total = axis_size(mesh, axes)

    def leaf(x):
        shape = tuple(x.shape)
        if (axes and shape and shape[0] > 1 and shape[0] % total == 0):
            first = axes if len(axes) > 1 else axes[0]
            return P(first, *([None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))

    return jax.tree.map(leaf, batch)


def batch_shardings(mesh: Mesh, batch: Any) -> Any:
    """:func:`batch_specs` as ``NamedSharding`` leaves (jit in_shardings)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        batch_specs(mesh, batch),
                        is_leaf=lambda x: isinstance(x, P))
