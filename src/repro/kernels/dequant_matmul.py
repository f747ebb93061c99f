"""Pallas TPU kernel: fused CLAQ dequant GEMM with outlier-reservation epilogue.

Computes  y = x @ W^T  where W (N=out, K=in) is stored as:
  * packed code planes (uint32 words along the N axis, one stream/column),
  * a per-column codebook stored level-major, (2**bits, K): one centroid
    level per sublane row, columns along lanes,
  * structured outliers: up to `k_out` (row-index, fp-value) pairs per
    column overriding the dequantized value (Outlier Reservation, §3.4).

TPU adaptation (DESIGN.md §4):
  * codes unpack with shift/mask on the VPU; centroid lookup is done as a
    2**bits-way select-accumulate (no gather — codebooks are <=16 entries,
    so a select chain beats any gather on TPU and vectorizes across the
    whole tile);
  * outliers apply inside the dequant epilogue as masked selects against
    the tile's global row ids — no scatter, shape-static — over only the
    slots that can hold a row of the tile: each column's slots ascend by
    row, and a scalar-prefetched window table (`out_win`, built by
    kernels/plan.py) gives per (N tile, K block) the slots [lo, hi) to
    visit, in aligned SLOT_CHUNK-slot chunks.  Without a table the window
    is every slot;
  * the weight tile feeds the MXU directly from VMEM; full-width W never
    exists in HBM.

Grid: (M/bm, N/bn, K/bk), K innermost; the (bm, bn) f32 output block stays
resident in VMEM across the K sweep (revisited accumulation).

Activation fetch (`x_mode`, DESIGN.md §9) — how each (bm, bk) x tile
reaches the MXU:
  * "blocked": x arrives gathered into the group's fused K order and
    K-padded by the caller (permuted groups and the per-stripe unprepared
    dispatch); the tile is a plain (i, k) block.
  * "aligned": x is the RAW activation matrix; the plan proved the group's
    fused K order IS original column order (single-bit-width tensors —
    `build_quantized_tensor` emits an identity permutation), so the tile
    is the raw (i, x_base + k) block, and only the padded K tail past
    `k_cols` is masked to zero in-kernel (the tail's codebooks/outliers
    are zero/-1, but interpret-mode Pallas pads out-of-bounds blocks with
    NaN, and NaN * 0 would poison the accumulator).
  A permuted group's columns are not taken inside the kernel: Mosaic has
  no lane gather over a VMEM-resident x block, so kernels/ops.py takes
  them with one XLA gather per group and launches "blocked".

Per-token int8 activations ride any mode: x may be int8 (cast to the
compute dtype after the fetch) with an optional (M, 1) f32 `x_scale`
operand folded into the output block at the LAST K step — one multiply
per output element after the integer-valued accumulation, so the MXU
consumes unscaled int8-exact values.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
# outlier slots per epilogue step: one aligned (8, bk) sublane tile of
# out_idx / out_val, then 8 unrolled selects; window bounds are multiples
SLOT_CHUNK = 8


def _platform() -> str:
    """The backend quantized matmuls are traced for (tests steer it)."""
    return jax.default_backend()


def _unpack_tile(words, width: int, bn: int):
    """(bn//cpw, bk) uint32 words -> (bn, bk) int32 codes of `width` bits."""
    cpw = 32 // width
    mask = jnp.uint32((1 << width) - 1)
    rep = jnp.repeat(words, cpw, axis=0)                      # (bn, bk)
    shift = (jax.lax.broadcasted_iota(jnp.uint32, (bn, 1), 0) % cpw) * width
    return ((rep >> shift) & mask).astype(jnp.int32)


def _dequant_tile(codes, cb, n_levels: int):
    """codes (bn, bk) + cb (n_levels, bk) -> f32 W tile (bn, bk).

    n_levels-way select-accumulate: for <=16 centroids this is a handful of
    vectorized VPU ops per element — cheaper and more TPU-natural than a
    gather from VMEM.  Level c is the sublane row cb[c] broadcast down the
    tile (a lane-axis index into the codebook would be a lane gather, which
    Mosaic rejects).  The chain selects in f32 whatever the compute dtype:
    Mosaic cannot relayout the i1 mask of a 16-bit select, and selecting
    before the single cast yields the same bits as selecting cast values.
    """
    w = jnp.zeros(codes.shape, jnp.float32)
    for c in range(n_levels):
        w = jnp.where(codes == c, cb[c:c + 1, :], w)
    return w


def _outlier_epilogue(wt_ref, idx_ref, val_ref, lo, hi, row_ids,
                      k_out: int):
    """Override the dequantized tile in `wt_ref` with the outliers of the
    slots [lo, hi) (multiples of SLOT_CHUNK; hi may pass k_out).  Whole
    chunks run in a loop over the window; the last k_out % SLOT_CHUNK
    slots, which no aligned chunk covers, run once where the window
    reaches them.  Every slot outside the window matches no row of the
    tile, so the result is bitwise that of visiting every slot."""
    def apply(idx, val):
        w = wt_ref[...]
        for r in range(idx.shape[0]):
            w = jnp.where(idx[r:r + 1, :] == row_ids, val[r:r + 1, :], w)
        wt_ref[...] = w

    c = SLOT_CHUNK
    whole = k_out // c * c
    if whole:
        def chunk(i, carry):
            s = pl.multiple_of(i * c, c)
            apply(idx_ref[pl.ds(s, c), :], val_ref[pl.ds(s, c), :])
            return carry

        jax.lax.fori_loop(lo // c, jnp.minimum(hi, whole) // c, chunk, 0)
    if whole < k_out:
        @pl.when(hi > whole)
        def _tail():
            apply(idx_ref[whole:, :], val_ref[whole:, :])


def _kernel(*refs, bits: int, plane_widths: Sequence[int], bn: int,
            bk: int, k_out: int, tiles_per_launch: int, n_levels: int,
            has_acc: bool, compute_dtype, x_mode: str, k_cols: int,
            has_scale: bool):
    if k_out > 0:
        win_ref, x_ref, *rest, wt_ref = refs
    else:
        x_ref, *rest = refs
    nplanes = len(plane_widths)
    plane_refs = rest[:nplanes]
    rest = rest[nplanes:]
    cb_ref, rest = rest[0], rest[1:]
    if k_out > 0:
        idx_ref, val_ref, rest = rest[0], rest[1], rest[2:]
    if has_scale:
        scale_ref, rest = rest[0], rest[1:]
    if has_acc:
        acc_ref, rest = rest[0], rest[1:]
    (o_ref,) = rest

    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        # seed the VMEM-resident output block: zeros, or the running
        # accumulator when fusing multiple bit-width groups into one output
        o_ref[...] = acc_ref[...] if has_acc else jnp.zeros_like(o_ref)

    # --- fetch the (bm, bk) x tile (see module docstring: x_mode) -----------
    xt = x_ref[...]
    if x_mode == "aligned" and k_cols % bk != 0:
        # the group's padded K tail: weights there are zero, but the
        # raw-x block read past `cols` is NaN-padded in interpret mode
        fused = k_step * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        xt = jnp.where(fused < k_cols, xt, jnp.zeros((), xt.dtype))

    # --- unpack code planes -> (bn, bk) int32 codes -------------------------
    codes = None
    shift = 0
    for w, ref in zip(plane_widths, plane_refs):
        part = _unpack_tile(ref[...], w, bn) << shift
        codes = part if codes is None else codes | part
        shift += w
    # --- centroid lookup -----------------------------------------------------
    wt = _dequant_tile(codes, cb_ref[...], n_levels)

    # --- outlier-reservation epilogue ---------------------------------------
    if k_out > 0:
        # the window table is flat (plan tile, K block, lo/hi); this launch
        # tile spans `tiles_per_launch` plan tiles, whose window is the lo
        # of the first and the hi of the last (neither decreases down N)
        j = pl.program_id(1)
        n_kb = pl.num_programs(2)
        first = j * tiles_per_launch
        lo = win_ref[(first * n_kb + k_step) * 2]
        hi = win_ref[((first + tiles_per_launch - 1) * n_kb + k_step) * 2 + 1]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + j * bn
        wt_ref[...] = wt
        _outlier_epilogue(wt_ref, idx_ref, val_ref, lo, hi, row_ids, k_out)
        wt = wt_ref[...]

    # --- MXU ------------------------------------------------------------------
    # f32 compute asks for f32 products outright rather than leaving the
    # pass count of an f32 matmul to Mosaic's default
    x = xt.astype(compute_dtype)
    f32 = jnp.dtype(compute_dtype) == jnp.float32
    o_ref[...] += jax.lax.dot_general(
        x, wt.astype(compute_dtype), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if f32 else None,
        preferred_element_type=jnp.float32)

    if has_scale:
        # per-token (row) activation scale, folded in ONCE after the full
        # K sweep — the last launch of a multi-group matmul carries it, so
        # the whole accumulated sum (acc seed included) is scaled exactly
        # once: y = scale_m * sum_k xq[m,k] * w[n,k]
        @pl.when(k_step == pl.num_programs(2) - 1)
        def _fold_scale():
            o_ref[...] = o_ref[...] * scale_ref[...].astype(jnp.float32)


# pallas_call dispatches issued from python since process start (trace-time
# under jit).  Tests and benchmarks read deltas of this to assert the fused
# plan path launches exactly one kernel per distinct stripe bit-width.
launch_count = 0


@functools.partial(
    jax.jit,
    static_argnames=("bits", "n", "bm", "bn", "bk", "interpret",
                     "compute_dtype", "x_mode", "x_base", "k_cols"),
)
def _dequant_matmul(x, planes, codebook, out_idx, out_val, out_win, acc,
                    x_scale, *, bits, n, bm, bn, bk, interpret,
                    compute_dtype, x_mode, x_base, k_cols):
    from repro.core import packing

    widths = packing.plane_widths(bits)
    m = x.shape[0]
    k_padded = planes[0].shape[-1]     # fused, block-padded K of the group
    assert m % bm == 0 and n % bn == 0 and k_padded % bk == 0
    for w, p in zip(widths, planes):
        assert p.shape == (n // (32 // w), k_padded), (p.shape, n, k_padded, w)
    grid = (m // bm, n // bn, k_padded // bk)
    n_levels = 2 ** bits

    k_out = 0 if out_idx is None else out_idx.shape[0]
    tiles_per_launch = 1
    prefetch = []
    if k_out > 0:
        per_tile = grid[2] * 2          # (K block, lo/hi) entries a tile
        if out_win is None:
            # no plan: every launch tile visits every slot
            out_win = jnp.tile(jnp.array(
                [0, -(-k_out // SLOT_CHUNK) * SLOT_CHUNK], jnp.int32),
                n // bn * grid[2])
        assert out_win.ndim == 1 and out_win.shape[0] % (
            n // bn * per_tile) == 0, (out_win.shape, n, bn, grid)
        tiles_per_launch = out_win.shape[0] // (n // bn * per_tile)
        prefetch = [out_win]

    # index maps take the grid indices and then the scalar-prefetch refs
    if x_mode == "blocked":
        assert x.shape[1] == k_padded, (x.shape, k_padded)
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k))]
    elif x_mode == "aligned":
        # raw x; the group's fused K order IS original columns starting at
        # block offset x_base — a plain shifted block fetch, no indexing
        in_specs = [pl.BlockSpec((bm, bk),
                                 lambda i, j, k, *_, xb=x_base: (i, xb + k))]
    else:
        raise ValueError(f"unknown x_mode {x_mode!r}")
    operands = [x]
    for w, p in zip(widths, planes):
        cpw = 32 // w
        in_specs.append(pl.BlockSpec((bn // cpw, bk),
                                     lambda i, j, k, *_: (j, k)))
        operands.append(p)
    in_specs.append(pl.BlockSpec((n_levels, bk), lambda i, j, k, *_: (0, k)))
    operands.append(codebook)
    if k_out > 0:
        in_specs.append(pl.BlockSpec((k_out, bk), lambda i, j, k, *_: (0, k)))
        in_specs.append(pl.BlockSpec((k_out, bk), lambda i, j, k, *_: (0, k)))
        operands.extend([out_idx, out_val])
    if x_scale is not None:
        assert x_scale.shape == (m, 1), (x_scale.shape, m)
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k, *_: (i, 0)))
        operands.append(x_scale)
    if acc is not None:
        assert acc.shape == (m, n), (acc.shape, m, n)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)))
        operands.append(acc)

    kernel = functools.partial(
        _kernel, bits=bits, plane_widths=widths, bn=bn, bk=bk, k_out=k_out,
        tiles_per_launch=tiles_per_launch, n_levels=n_levels,
        has_acc=acc is not None, compute_dtype=compute_dtype, x_mode=x_mode,
        k_cols=k_cols, has_scale=x_scale is not None)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            # the dequantized tile, kept in VMEM across the epilogue loop
            scratch_shapes=([pltpu.VMEM((bn, bk), jnp.float32)]
                            if k_out > 0 else [])),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)


def dequant_matmul(
    x: Array,                     # (M, K) — fused-padded ("blocked") or raw
    planes: tuple,                # per-plane (n_words, K) uint32
    codebook: Array,              # (2**bits, K) f32
    out_idx: Optional[Array],     # (k_out, K) int32 global row ids, -1 pad
    out_val: Optional[Array],     # (k_out, K)
    *,
    out_win: Optional[Array] = None,  # (n // bn * t * K // bk * 2,) int32
    #                                   slot windows per tile of bn // t rows
    bits: int,
    n: int,                       # N = out features (rows of W)
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
    compute_dtype=jnp.float32,
    acc: Optional[Array] = None,  # (M, N) f32 running accumulator to fold in
    x_mode: str = "blocked",      # "blocked" | "aligned"
    x_base: int = 0,              # aligned: x block offset (x_start // bk)
    k_cols: int = 0,              # aligned: unpadded fused K (tail mask)
    x_scale: Optional[Array] = None,  # (M, 1) f32 per-token act scale
) -> Array:
    """y = [acc +] x @ W^T for one uniform-bit-width CLAQ group.  Plane /
    codebook / outlier shapes must be padded to block multiples by the
    caller (kernels/ops.py / kernels/plan.py do this); in "aligned" mode
    (module docstring) x itself needs only its rows padded to bm.  `acc`
    seeds the output block at the first K step, so multi-group
    (mixed-precision) matmuls accumulate inside the kernel instead of
    through an XLA add per group; `x_scale` folds a
    per-token int8 activation scale into the output at the last K step
    (pass it on the LAST launch of a multi-group chain only).

    `out_win` (kernels/plan.slot_windows) bounds the outlier slots each
    (N tile, K block) visits; it needs each column's slots in ascending
    row order, and its tiles may be any whole fraction bn // t of the
    launch's.  Without it
    every tile visits every slot, in any slot order.

    `interpret=True` runs the kernel through the Pallas interpreter, for
    hosts without a TPU; on a TPU it raises, so the chip only ever runs
    the compiled kernel."""
    if interpret and _platform() == "tpu":
        raise ValueError("on a TPU the kernel runs compiled; Pallas "
                         "interpret mode is for hosts without one")
    global launch_count
    launch_count += 1
    return _dequant_matmul(x, tuple(planes), codebook, out_idx, out_val,
                           out_win, acc, x_scale,
                           bits=bits, n=n, bm=bm, bn=bn, bk=bk,
                           interpret=interpret, compute_dtype=compute_dtype,
                           x_mode=x_mode, x_base=x_base, k_cols=k_cols)
