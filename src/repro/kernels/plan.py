"""Ahead-of-time inference plans: compile a QuantizedTensor into the exact
padded, fused layout the Pallas dequant-GEMM consumes.

`kernels/ops.qmatmul` on a raw QuantizedTensor re-derives padding, plane
splits, the stripe column permutation, and outlier validity masks inside
every traced matmul, and issues one `pallas_call` per stripe.  All of that
is per-*tensor* work, not per-*token* work.  `prepare_for_inference` does
it once, at load/quantize time:

  (a) code planes, codebooks, and outlier tables are padded to kernel
      block multiples (K to the group's bk, N to bn) — padding K slots
      carry zero codebooks and idx=-1 outliers, so they contribute exactly
      zero and never need masking at matmul time;
  (b) the per-stripe column slicing is folded into ONE gather index over
      the activation's K axis (`gather_idx`: the XLA
      `jnp.take(..., mode="fill")` that puts a permuted group's columns in
      fused order, and the dequantize oracle) — plus a static per-group
      alignment analysis: when a group's fused K order is exactly original
      column order (single-bit-width tensors; `build_quantized_tensor`
      emits an identity permutation), `x_start` is set and the kernel
      fetches raw x blocks with NO indexing at all (DESIGN.md §9);
  (c) outlier slots are pre-validated: the per-column count is converted
      to idx=-1 padding once, instead of a mask per matmul — and put in an
      order the kernel can window: within each column the slots ascend by
      row (padding last), and a gathered group's columns descend by
      outlier count, so one K block holds columns of like counts.  Per
      (bn-row tile, K block) the plan records the slot window `out_win`
      outside which no slot of the block can hold a row of the tile; the
      kernel's epilogue visits only that window (DESIGN.md §4);
  (d) stripes are grouped by bit-width and concatenated along K, so a
      matmul issues ONE fused `pallas_call` per distinct bit-width
      (typically 1-3) instead of one per stripe, with each group's output
      accumulated into the same VMEM-resident block via the kernel's `acc`
      operand.

The prepared tensor is a registered pytree: it can replace QuantizedTensor
leaves inside a params tree and flow through jit/pjit with zero per-trace
preparation (serve/engine.py prepares every leaf at construction).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.quantized import QuantizedTensor

from . import dequant_matmul as dm

Array = jax.Array


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _launch_unit(bits: int) -> int:
    """Rows a multi-tile kernel launch must cover in multiples of: Mosaic
    tiles a block's last two axes by (8, 128) unless the block spans the
    whole axis.  The N tile is the output block's lane extent (a multiple
    of 128), and a code plane of width w packs 32 // w rows per word, so
    its block has tile // (32 // w) word rows (a multiple of 8): 256 rows
    where a width-1 plane exists, else 128."""
    return max(128, 256 // min(packing.plane_widths(bits)))


def padded_rows(rows: int, bn: int, bit_widths) -> int:
    """`rows` padded to whole `bn` tiles and, where one launch tile would
    not span them all, to whole launch units — so `launch_bn` always finds
    a legal tile without launching over every row of a large matrix."""
    unit = max(_launch_unit(b) for b in bit_widths)
    return _round_up(rows, bn if rows <= unit else math.lcm(bn, unit))


def launch_bn(bn: int, n_padded: int, bits: int) -> int:
    """N tile of one kernel launch over `n_padded` rows stored in `bn`-row
    tiles: the fewest whole tiles whose blocks Mosaic accepts
    (`_launch_unit`), or one tile over every row, which is always legal.
    The plan's `bn` stays the unit of padding and of sharding; only the
    launch grid coarsens."""
    unit = _launch_unit(bits)
    tiles = n_padded // bn
    for d in range(1, tiles):
        if tiles % d == 0 and (bn * d) % unit == 0:
            return bn * d
    return n_padded


@dataclasses.dataclass(frozen=True)
class PlanGroup:
    """All same-bit-width stripes, concatenated along K and block-padded."""
    planes: Tuple[Array, ...]   # per plane-width: (n_padded//cpw, k_padded) u32
    codebook: Array             # (2**bits, k_padded) f32, zero at padding
    out_idx: Optional[Array]    # (k_out, k_padded) int32, -1 = no outlier;
    #                             each column's slots ascend by row
    out_val: Optional[Array]    # (k_out, k_padded) f32
    out_win: Optional[Array]    # (n_tiles * (k_padded // bk) * 2,) int32:
    #                             per (bn-row tile, K block) the slots
    #                             [lo, hi), multiples of dm.SLOT_CHUNK, that
    #                             can hold a row of the tile, flat
    #                             (`slot_windows`)
    bits: int                   # static
    bk: int                     # static — K block size for this group
    k_cols: int                 # static — unpadded fused K of the group
    x_start: Optional[int] = None   # static — set iff the fused K order is
    #                             original columns [x_start, x_start+k_cols)
    #                             with x_start % bk == 0: the kernel reads
    #                             raw x blocks, no per-column indexing

    @property
    def k_padded(self) -> int:
        return self.codebook.shape[-1]

    def unpack_codes(self, rows: int) -> Array:
        """Recombine the group's split planes -> (rows, k_padded) int32."""
        codes = None
        shift = 0
        for w, p in zip(packing.plane_widths(self.bits), self.planes):
            part = packing._unpack_plane(p, w, rows) << shift
            codes = part if codes is None else codes | part
            shift += w
        return codes


jax.tree_util.register_dataclass(
    PlanGroup,
    data_fields=["planes", "codebook", "out_idx", "out_val", "out_win"],
    meta_fields=["bits", "bk", "k_cols", "x_start"])


@dataclasses.dataclass(frozen=True)
class PreparedQuantizedTensor:
    """Deployment format: one gather index + one padded group per bit-width."""
    groups: Tuple[PlanGroup, ...]
    gather_idx: Array        # (sum k_padded,) int32 original-col per fused
    #                          K slot; == cols for padding (gathers 0.0)
    shape: Tuple[int, int]   # static (rows, cols) of the logical matrix
    n_padded: int            # static — rows padded to the N block
    bn: int                  # static — N block size (shared by all groups)

    @property
    def rows(self) -> int:
        return self.shape[0]

    @property
    def cols(self) -> int:
        return self.shape[1]

    @property
    def n_tiles(self) -> int:
        """Whole (bn, ·) output tiles along N — the unit in which the plan
        may be split across devices."""
        return self.n_padded // self.bn

    @property
    def x_gather_free(self) -> bool:
        """True iff every group fetches raw x blocks without per-column
        indexing (all groups aligned) — the fused matmul then contains no
        gather of the activations."""
        return all(g.x_start is not None for g in self.groups)

    def shards_whole_tiles(self, parts: int) -> bool:
        """True iff splitting N into `parts` equal contiguous shards keeps
        whole (bn, bk) tiles on every shard.  The plan layout packs codes
        along the row axis in 32/width-code words and pads N to bn, so an
        N split at a bn boundary is word-aligned for every plane width iff
        bn is a multiple of the full 32-row packing word — plans built
        with a smaller or unaligned bn cap replicate (a width-1 plane
        packs 32 rows per word, so e.g. bn=16 tile boundaries fall
        mid-word).  Each shard's rows must also launch as a legal kernel
        tile that is not all of a large shard: within one launch unit, or
        whole units (`launch_bn`).  dist/sharding.spec_for_quantized uses
        this as the divisibility guard: shard the unit along N only when
        every shard keeps whole word-aligned tiles, otherwise replicate
        the whole unit — never tear it."""
        if not (parts > 1 and self.bn % 32 == 0
                and self.n_tiles % parts == 0):
            return False
        local = self.n_padded // parts
        return all(local <= unit or local % unit == 0
                   for unit in (_launch_unit(g.bits) for g in self.groups))

    def dequantize(self, dtype=jnp.float32) -> Array:
        """Reference dequantization from the *prepared* layout (oracle for
        plan-vs-tensor parity tests; also serves materialize_kernel)."""
        rows, cols = self.shape
        W = jnp.zeros((rows, cols + 1), jnp.float32)   # last col: padding sink
        off = 0
        for g in self.groups:
            Wg = jnp.take_along_axis(g.codebook, g.unpack_codes(rows), axis=0)
            if g.out_idx is not None:
                safe = jnp.where(g.out_idx >= 0, g.out_idx, rows)
                colk = jnp.broadcast_to(
                    jnp.arange(g.k_padded)[None, :], g.out_idx.shape)
                Wg = Wg.at[safe, colk].set(g.out_val, mode="drop")
            idx = self.gather_idx[off:off + g.k_padded]
            W = W.at[:, idx].set(Wg)
            off += g.k_padded
        return W[:, :cols].astype(dtype)

    def effective_bits(self, include_codebooks: bool = False) -> float:
        """Storage cost of the *unpadded* payload (parity with
        QuantizedTensor.effective_bits up to outlier-count rounding)."""
        rows, cols = self.shape
        total = 0.0
        for g in self.groups:
            total += packing.storage_bits_per_element(g.bits) * rows * g.k_cols
            if g.out_idx is not None:
                total += 32.0 * float(jnp.sum(g.out_idx[:, :g.k_cols] >= 0))
            if include_codebooks:
                total += g.k_cols * 2 ** g.bits * 16.0
        return total / (rows * cols)


jax.tree_util.register_dataclass(
    PreparedQuantizedTensor,
    data_fields=["groups", "gather_idx"],
    meta_fields=["shape", "n_padded", "bn"])


def validated_outliers(qt: QuantizedTensor):
    """Outlier planes in stripe-permuted column order, invalid slots idx=-1.
    (Shared with the unprepared kernel dispatch in ops.py — the -1 contract
    must match the kernel epilogue in both paths.)"""
    if qt.out_idx.shape[0] == 0:
        return None, None
    k = qt.out_idx.shape[0]
    idx_p = qt.out_idx[:, qt.col_perm]
    val_p = qt.out_val[:, qt.col_perm]
    cnt_p = qt.out_count[qt.col_perm]
    valid = jnp.arange(k)[:, None] < cnt_p[None, :]
    return (jnp.where(valid, idx_p, -1).astype(jnp.int32),
            jnp.where(valid, val_p, 0.0).astype(jnp.float32))


def row_ordered(idx: Array, val: Array):
    """Each column's outlier slots in ascending row order, padding (-1)
    last.  Bitwise neutral: a column holds each row at most once, so the
    order in which its slots override the dequantized tile is moot."""
    key = jnp.where(idx >= 0, idx, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, axis=0, stable=True)
    return (jnp.take_along_axis(idx, order, axis=0),
            jnp.take_along_axis(val, order, axis=0))


def count_order(idx: Array, k_cols: int) -> Array:
    """Fused K order for a gathered group: its `k_cols` columns by
    descending outlier count (stable), the block-padding columns last.
    A K block then holds columns of like counts, so one slot window fits
    them all."""
    count = jnp.sum(idx[:, :k_cols] >= 0, axis=0)
    order = jnp.argsort(-count, stable=True).astype(jnp.int32)
    return jnp.concatenate(
        [order, jnp.arange(k_cols, idx.shape[1], dtype=jnp.int32)])


def slot_windows(idx: Array, bn: int, n_tiles: int, bk: int) -> Array:
    """Slot windows of row-ordered outliers `idx` (k_out, k_padded): a
    flat int32 table indexed (tile t, K block b, lo/hi), flat so that it
    is stored and handed to the kernel's scalar memory without padding
    or relayout.  Over the columns of K block b that
    hold any outlier, lo is the least count of a column's outliers on rows
    < t*bn and hi the greatest on rows < (t+1)*bn, rounded outward to
    whole `dm.SLOT_CHUNK`-slot chunks; [0, 0) where no column holds one.
    Every slot holding a row of tile t lies in [lo, hi) (slots ascend by
    row), and neither bound decreases down the tiles, so the window of a
    run of tiles is the first's lo and the last's hi."""
    k_out, k_padded = idx.shape
    key = jnp.where(idx >= 0, idx, jnp.iinfo(jnp.int32).max)
    bounds = jnp.arange(n_tiles + 1, dtype=jnp.int32) * bn
    before = jax.vmap(lambda col: jnp.searchsorted(col, bounds))(key.T)
    held = (idx[0] >= 0)[:, None]       # padding sorts last: slot 0 is
    #                                     valid iff the column has any
    blocks = (k_padded // bk, bk, n_tiles)
    lo = jnp.min(jnp.where(held, before[:, :-1], k_out).reshape(blocks),
                 axis=1)
    hi = jnp.max(before[:, 1:].reshape(blocks), axis=1)
    c = dm.SLOT_CHUNK
    lo = jnp.minimum(lo, hi) // c * c
    hi = (hi + c - 1) // c * c
    return jnp.stack([lo.T, hi.T], axis=-1).astype(jnp.int32).reshape(-1)


def _static_group_layout(stripes, bk: int):
    """Per-bit-width group layout derived from static stripe metadata only
    (bits + column counts) — identical for every member of a layer stack.
    Returns [(bits, [(perm_offset, stripe_index), ...], k_cols, g_bk,
    k_padded)]; members are INDICES so `_build_plan` resolves them against
    its own (possibly vmapped) argument, never against closure constants.
    """
    offsets = []
    off = 0
    for s in stripes:
        offsets.append(off)
        off += s.n_cols
    layout = []
    for bits in sorted({s.bits for s in stripes}):
        members = tuple((o, si) for si, (o, s)
                        in enumerate(zip(offsets, stripes)) if s.bits == bits)
        k_cols = sum(stripes[si].n_cols for _, si in members)
        g_bk = min(bk, _round_up(k_cols, 128))
        layout.append((bits, members, k_cols, g_bk, _round_up(k_cols, g_bk)))
    return tuple(layout)


def _aligned_x_starts(qt: QuantizedTensor, layout):
    """Per-group x_start, or None where the group's activations must be
    gathered into fused order.  A group is *aligned* when its fused K
    order is exactly the original columns [s0, s0 + k_cols) with s0 a bk
    multiple — true for
    every single-bit-width tensor (`build_quantized_tensor` sorts columns
    ascending within each bit-class, so one class == identity).  Decided
    from concrete col_perm values at plan time; under tracing (prepare
    inside jit) it conservatively falls back to the gather.  For layer
    stacks the whole stack must agree (the flag is static, shared by every
    member)."""
    try:
        perm = np.asarray(qt.col_perm)
    except (jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        # traced col_perm (prepare under jit/vmap): no static analysis —
        # every group conservatively takes the gathered path.  Anything
        # else np.asarray raises is a real defect and must propagate.
        return [None] * len(layout)
    flat = perm.reshape(-1, perm.shape[-1])
    starts = []
    for bits, members, k_cols, g_bk, _k_padded in layout:
        idx = np.concatenate(
            [flat[:, o:o + qt.stripes[si].n_cols] for o, si in members],
            axis=1)
        s0 = int(idx[0, 0])
        ok = (s0 % g_bk == 0 and np.array_equal(
            idx, np.broadcast_to(np.arange(k_cols) + s0, idx.shape)))
        starts.append(s0 if ok else None)
    return starts


def prepare_for_inference(
    qt: QuantizedTensor,
    *,
    bn: int = dm.DEFAULT_BN,
    bk: int = dm.DEFAULT_BK,
) -> PreparedQuantizedTensor:
    """Compile `qt` into the fused deployment layout (see module docstring).

    bn/bk are *upper bounds*; each is shrunk to the tensor (bn to N rounded
    to the 32-row packing word, bk per group to its fused K rounded to the
    128-lane tile) so small matrices don't pay full-block padding.

    Layer-stacked tensors (launch.quantize stacks per-layer results, so
    data leaves carry leading (L,) or (L, E) dims while `shape` stays the
    per-matrix (rows, cols)) are prepared by vmapping over the stack: the
    AP/OR allocations depend only on (rows, cols), so every member shares
    one static plan layout, and the stacked prepared leaves slice per
    layer through scan / tree_map exactly like the stacked input did.
    The x alignment analysis runs on the whole stack BEFORE the vmap
    (x_start is static meta, so all members must agree on it).
    """
    layout = _static_group_layout(qt.stripes, bk)
    x_starts = tuple(_aligned_x_starts(qt, layout))
    stack_dims = qt.stripes[0].packed.ndim - 2
    return _plan_builder(bn, layout, x_starts, stack_dims)(qt)


@functools.cache
def _plan_builder(bn: int, layout, x_starts, stack_dims: int):
    """`_build_plan` vmapped over the stack dims and jitted: one program
    per plan layout, shared by a tree's leaves of that layout until
    `prepare_tree` drops it (the persistent compile cache keeps it), in
    place of a compile for each of its many small ops."""
    build = functools.partial(_build_plan, bn=bn, layout=layout,
                              x_starts=x_starts)
    for _ in range(stack_dims):
        build = jax.vmap(build)
    return jax.jit(build)


def _build_plan(qt: QuantizedTensor, *, bn: int, layout,
                x_starts) -> PreparedQuantizedTensor:
    rows = qt.rows
    bn = min(bn, _round_up(rows, 32))
    n_padded = padded_rows(rows, bn, [bits for bits, *_ in layout])

    oi, ov = validated_outliers(qt)

    groups = []
    idx_parts = []
    for (bits, members, k_cols, g_bk, k_padded), x_start \
            in zip(layout, x_starts):
        widths = packing.plane_widths(bits)
        planes = []
        for wi, w in enumerate(widths):
            cpw = 32 // w
            parts = [packing.split_planes(qt.stripes[si].packed, bits,
                                          rows)[wi]
                     for _, si in members]
            p = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
            p = jnp.pad(p, ((0, n_padded // cpw - p.shape[0]),
                            (0, k_padded - k_cols)))
            planes.append(p)

        cb = jnp.concatenate(
            [qt.stripes[si].codebook for _, si in members], axis=0) \
            if len(members) > 1 else qt.stripes[members[0][1]].codebook
        cb = jnp.pad(cb.astype(jnp.float32).T, ((0, 0), (0, k_padded - k_cols)))

        idx = jnp.concatenate(
            [jax.lax.slice_in_dim(qt.col_perm, o, o + qt.stripes[si].n_cols)
             for o, si in members]) if len(members) > 1 \
            else jax.lax.slice_in_dim(
                qt.col_perm, members[0][0],
                members[0][0] + qt.stripes[members[0][1]].n_cols)
        idx = jnp.pad(idx.astype(jnp.int32), (0, k_padded - k_cols),
                      constant_values=qt.cols)

        g_oi = g_ov = win = None
        if oi is not None:
            g_oi = jnp.concatenate(
                [jax.lax.slice_in_dim(oi, o, o + qt.stripes[si].n_cols,
                                      axis=1)
                 for o, si in members], axis=1)
            g_ov = jnp.concatenate(
                [jax.lax.slice_in_dim(ov, o, o + qt.stripes[si].n_cols,
                                      axis=1)
                 for o, si in members], axis=1)
            g_oi = jnp.pad(g_oi, ((0, 0), (0, k_padded - k_cols)),
                           constant_values=-1)
            g_ov = jnp.pad(g_ov, ((0, 0), (0, k_padded - k_cols)))
            g_oi, g_ov = row_ordered(g_oi, g_ov)
            if x_start is None:
                # a gathered group's K order is free: one permutation of
                # every K-indexed array, the gather index included
                order = count_order(g_oi, k_cols)
                planes = [jnp.take(p, order, axis=1) for p in planes]
                cb, g_oi, g_ov = (jnp.take(a, order, axis=1)
                                  for a in (cb, g_oi, g_ov))
                idx = jnp.take(idx, order)
            win = slot_windows(g_oi, bn, n_padded // bn, g_bk)
        idx_parts.append(idx)

        groups.append(PlanGroup(
            planes=tuple(planes), codebook=cb, out_idx=g_oi, out_val=g_ov,
            out_win=win, bits=bits, bk=g_bk, k_cols=k_cols,
            x_start=x_start))

    return PreparedQuantizedTensor(
        groups=tuple(groups),
        gather_idx=jnp.concatenate(idx_parts) if len(idx_parts) > 1
        else idx_parts[0],
        shape=qt.shape, n_padded=n_padded, bn=bn)


def or_slot_share(params) -> Optional[float]:
    """Share of the outlier slots the kernel's epilogue visits over every
    prepared leaf of `params`, in %: per launch tile and K block, the
    window's width (capped at k_out) times the tile's elements, summed,
    over k_out times the same elements.  None where no leaf has outliers.
    Reads the small window tables to the host."""
    visited = every = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda l: isinstance(l, PreparedQuantizedTensor)):
        if not isinstance(leaf, PreparedQuantizedTensor):
            continue
        for g in leaf.groups:
            if g.out_win is None:
                continue
            k_out = g.out_idx.shape[-2]
            tile = launch_bn(leaf.bn, leaf.n_padded, g.bits)
            d = tile // leaf.bn
            win = np.asarray(g.out_win).reshape(
                g.out_win.shape[:-1] + (leaf.n_tiles, g.k_padded // g.bk, 2))
            lo, hi = win[..., ::d, :, 0], win[..., d - 1::d, :, 1]
            width = np.clip(np.minimum(hi, k_out) - lo, 0, None)
            visited += int(width.sum()) * tile * g.bk
            every += k_out * width.size * tile * g.bk
    return 100.0 * visited / every if every else None


def prepare_tree(params, *, bn: int = dm.DEFAULT_BN, bk: int = dm.DEFAULT_BK):
    """Replace every QuantizedTensor leaf in a params tree with its prepared
    form (identity on dense leaves).  Engines call this once at load.

    The plan builders are dropped once the tree is built: a loaded program
    keeps its code in device memory (the sorts make each 5-16 MB on a TPU,
    ~54 MB at glm4_9b widths), and a tree is prepared once."""
    def one(leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf.prepare(bn=bn, bk=bk)
        return leaf

    prepared = jax.tree_util.tree_map(
        one, params,
        is_leaf=lambda l: isinstance(l, (QuantizedTensor,
                                         PreparedQuantizedTensor)))
    _plan_builder.cache_clear()
    return prepared
