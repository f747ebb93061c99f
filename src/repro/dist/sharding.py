"""Named sharding rules for params / batches / caches.

A *rule* is `rule(name, shape, cfg, ax) -> PartitionSpec`, applied per leaf
by `with_shardings` (ShapeDtypeStruct trees, dry-run lowering) or
`tree_shardings` (concrete trees, device_put).  Rules are divisibility-
guarded so the same rule set covers every arch family: a dimension is only
sharded when the mesh axis divides it, otherwise it stays replicated.

CLAQ quantized leaves are NOT per-leaf shardable: a
`PreparedQuantizedTensor` is a *unit* — packed code planes, per-group
codebooks, outlier tables, and one fused gather index whose layouts are
coupled (kernels/plan.py).  Splitting its leaves independently (the
generic largest-dim pick) would shard planes along K, the gather index
along its only axis, and codebooks along the centroid axis — tearing the
plan apart.  `spec_for_quantized` shards the unit along N instead: planes
split on their packed-row axis (whole (bn, bk) tiles per shard, guarded by
`PreparedQuantizedTensor.shards_whole_tiles`), everything K-indexed or
row-index-valued replicated.  `tree_shardings` / `with_shardings` route
quantized units through this rule automatically.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from . import context as dctx


class MeshAxes:
    """Resolved logical axes of a mesh ("dp" spans pod x data when present)."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.dp_axes: Tuple[str, ...] = dctx.physical_axes(mesh, "dp")
        self.model_axes: Tuple[str, ...] = dctx.physical_axes(mesh, "model")
        self.dp_size: int = dctx._axis_size(mesh, "dp")
        self.model_size: int = dctx._axis_size(mesh, "model")

    @property
    def dp(self):
        return dctx.spec_entry(self.mesh, "dp")

    @property
    def model(self):
        return dctx.spec_entry(self.mesh, "model")


def _shardable(dim: int, size: int) -> bool:
    return size > 1 and dim >= size and dim % size == 0


def _quantized_types():
    """Lazy import: dist must stay importable before kernels/core finish
    initializing."""
    from repro.core.quantized import QuantizedTensor
    from repro.kernels.plan import PreparedQuantizedTensor
    return QuantizedTensor, PreparedQuantizedTensor


# leaf fields of QuantizedTensor / PreparedQuantizedTensor / PlanGroup — a
# per-leaf rule must never invent a spec for these (see spec_for_quantized)
_QUANT_LEAF_MARKERS = (".groups[", ".planes[", ".gather_idx", ".codebook",
                      ".out_idx", ".out_val", ".out_win", ".stripes[",
                      ".col_perm", ".out_count", ".packed")


def spec_for_quantized(q, ax: MeshAxes):
    """Spec *tree* (same pytree structure as `q`) for one quantized unit.

    PreparedQuantizedTensor: sharded as a unit along N over "model" —
      * code planes split on their packed-row axis (axis -2; one packed
        word = 32/width consecutive rows of one column, and bn is a
        multiple of the 32-row word, so a bn-aligned split is word-aligned
        and every shard keeps whole (bn, bk) tiles);
      * `codebook` / `out_idx` / `out_val` are K-indexed (and outlier idx
        *values* are global row numbers), `gather_idx` indexes the
        activation's K axis — all replicated;
      * `out_win`, the outlier slot windows per (bn-row tile, K block),
        flat and tile-major, splits on its last axis with the planes'
        rows, so each shard holds the windows of its own tiles;
      * guarded by `shards_whole_tiles(model_size)`: when the tile count
        does not divide, the WHOLE unit stays replicated — never torn;
      * stacked (L, ...) / (L, E, ...) leaves (launch.quantize stacks
        per-layer results; the plan vmaps, so meta is per-matrix) shard
        the same axis -2, leading stack dims untouched.

    Raw QuantizedTensor: replicated as a unit.  It is the pre-deployment
    format (3-bit packs two planes concatenated along packed rows, so no
    row split is tile-clean); serving prepares leaves before sharding, and
    the row-sharded *quantizer* manages its own mesh explicitly.
    """
    QuantizedTensor, PreparedQuantizedTensor = _quantized_types()

    if (isinstance(q, PreparedQuantizedTensor)
            and ax.model_size > 1
            and q.shards_whole_tiles(ax.model_size)):
        model = ax.model

        def one(path, leaf):
            # the leaf's field: its last attribute key (a plane sits one
            # tuple index below `planes`)
            field = next((k.name for k in reversed(path)
                          if hasattr(k, "name")), None)
            axis = {"planes": -2, "out_win": -1}.get(field)
            if axis is None:
                return PartitionSpec()
            entries = [None] * np.ndim(leaf)
            entries[axis] = model
            return PartitionSpec(*entries)

        return jax.tree_util.tree_map_with_path(one, q)

    if not isinstance(q, (QuantizedTensor, PreparedQuantizedTensor)):
        raise TypeError(f"not a quantized unit: {type(q)}")
    return jax.tree_util.tree_map(lambda _: PartitionSpec(), q)


def sharded_qmatmul(x, pqt, mesh, *, act_dtype=None):
    """The fused kernel on a device mesh.  XLA cannot partition a Mosaic
    kernel, so each device runs `kernels.ops.qmatmul` under shard_map on
    its own share: `pqt`'s N tiles as `spec_for_quantized` splits them
    over "model" (a unit that does not split whole stays replicated) and
    x's leading rows over "dp" where they divide.  Outlier row ids are
    global, so each shard shifts them into its local rows; ids of other
    shards then match no local row.  The shift keeps each column's slots
    in row order, and each shard holds the slot windows of its own tiles,
    so the windows stay valid.  Every output element is the same
    contraction as on one device, so the result is bitwise the unsharded
    kernel's."""
    import dataclasses

    from repro.kernels import ops as kops

    ax = MeshAxes(mesh)
    split = ax.model_size > 1 and pqt.shards_whole_tiles(ax.model_size)
    n_local = pqt.n_padded // ax.model_size if split else pqt.n_padded
    dp = ax.dp if x.ndim > 1 and x.shape[0] % ax.dp_size == 0 else None
    mid = [None] * (x.ndim - 2)

    def body(xl, p):
        if split:
            off = jax.lax.axis_index(ax.model_axes) * n_local
            p = dataclasses.replace(
                p, shape=(n_local, p.cols), n_padded=n_local,
                groups=tuple(
                    g if g.out_idx is None else dataclasses.replace(
                        g, out_idx=jnp.where(g.out_idx >= 0,
                                             g.out_idx - off, -1))
                    for g in p.groups))
        return kops.qmatmul(xl, p, use_kernel=True, act_dtype=act_dtype)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PartitionSpec(dp, *mid, None), spec_for_quantized(pqt, ax)),
        out_specs=PartitionSpec(dp, *mid, ax.model if split else None),
        check_vma=False)(x, pqt)
    return y[..., :pqt.rows]


def spec_for_param(name: str, shape, cfg, ax: MeshAxes) -> PartitionSpec:
    """Tensor-parallel params: shard the largest model-divisible dimension
    over "model"; everything else replicated.  Covers dense kernels
    (in, out), stacked (L, in, out), and embeddings (vocab, d).  Quantized
    leaves are NOT covered here — `tree_shardings` / `with_shardings`
    route whole QuantizedTensor / PreparedQuantizedTensor units through
    `spec_for_quantized`; if a caller maps this rule over raw quantized
    internals anyway, they are replicated rather than torn."""
    if not shape or ax.model_size <= 1:
        return PartitionSpec()
    if any(m in name for m in _QUANT_LEAF_MARKERS):
        return PartitionSpec()
    candidates = [d for d, dim in enumerate(shape)
                  if _shardable(dim, ax.model_size)]
    if not candidates:
        return PartitionSpec()
    best = max(candidates, key=lambda d: shape[d])
    entries = [None] * len(shape)
    entries[best] = ax.model
    return PartitionSpec(*entries)


def spec_for_param_serve(name: str, shape, cfg, ax: MeshAxes) -> PartitionSpec:
    """Serving keeps the training TP layout (decode is weight-bound; the
    all-gather of a replicated layout would dominate the step)."""
    return spec_for_param(name, shape, cfg, ax)


def spec_for_batch(name: str, shape, cfg, ax: MeshAxes) -> PartitionSpec:
    """Batches shard their leading (global batch) dimension over "dp"."""
    if not shape or not _shardable(shape[0], ax.dp_size):
        return PartitionSpec()
    return PartitionSpec(ax.dp)


def spec_for_cache(name: str, shape, cfg, ax: MeshAxes) -> PartitionSpec:
    """KV/state caches in the engine/dry-run layout: leaves are stacked
    along a leading layer axis — (L, B, ...) data, (L, B) fill counters —
    so the batch (serving slot) axis is axis 1, sharded over "dp".  The KV
    head axis of plain attention caches ((L, B, S, KH, D) leaves named
    k/v, and the encdec cross_k/cross_v banks) additionally shards over
    "model", matching the head-parallel attention constraint; every other
    axis (layer, sequence, feature / state dims that decode indexes
    dynamically) stays replicated."""
    if not shape:
        return PartitionSpec()
    field = name.rsplit(".", 1)[-1] if "." in name else name
    # Paged layout (models/layers.py PagedKVCache, mla.py PagedMLACache):
    # pool leaves are (L, n_pages+1, page_size, ...) — axis 1 is the PAGE
    # axis, not a slot axis, and any slot's table row may name any page, so
    # pages shard over "dp" (gathers cross shards; XLA inserts the
    # collective) while the tiny (L, B, max_pages) tables replicate —
    # the default batch-axis rule would wrongly split their slot axis.
    if field == "table":
        return PartitionSpec()
    entries = [None] * len(shape)
    if field in ("kp", "vp", "cp", "pp",
                 "k_scale", "v_scale", "c_scale", "p_scale"):
        if len(shape) >= 2 and _shardable(shape[1], ax.dp_size):
            entries[1] = ax.dp
        if (field in ("kp", "vp") and len(shape) == 5
                and _shardable(shape[-2], ax.model_size)):
            entries[-2] = ax.model
        return PartitionSpec(*entries)
    batch_axis = 1 if len(shape) >= 2 else 0
    if _shardable(shape[batch_axis], ax.dp_size):
        entries[batch_axis] = ax.dp
    if (field in ("k", "v", "cross_k", "cross_v") and len(shape) == 5
            and _shardable(shape[-2], ax.model_size)):
        entries[-2] = ax.model
    return PartitionSpec(*entries)


def _leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def _is_quantized_unit(leaf) -> bool:
    QuantizedTensor, PreparedQuantizedTensor = _quantized_types()
    return isinstance(leaf, (QuantizedTensor, PreparedQuantizedTensor))


def tree_shardings(tree, rule, cfg, mesh):
    """Tree of NamedShardings for `tree` (concrete or SDS leaves).
    Quantized units expand to a matching sub-tree via spec_for_quantized,
    so the result stays leaf-congruent with `tree` (device_put-ready)."""
    ax = MeshAxes(mesh)

    def one(path, leaf):
        if _is_quantized_unit(leaf):
            return jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec),
                spec_for_quantized(leaf, ax))
        return NamedSharding(mesh, rule(_leaf_name(path), np.shape(leaf),
                                        cfg, ax))

    return jax.tree_util.tree_map_with_path(one, tree,
                                            is_leaf=_is_quantized_unit)


def with_shardings(tree, rule, cfg, mesh):
    """ShapeDtypeStruct tree re-annotated with NamedShardings (dry-run)."""
    ax = MeshAxes(mesh)

    def sds(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    def one(path, leaf):
        if _is_quantized_unit(leaf):
            return jax.tree_util.tree_map(sds, leaf,
                                          spec_for_quantized(leaf, ax))
        return sds(leaf, rule(_leaf_name(path), leaf.shape, cfg, ax))

    return jax.tree_util.tree_map_with_path(one, tree,
                                            is_leaf=_is_quantized_unit)
