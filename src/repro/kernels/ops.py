"""Jit'd wrappers dispatching QuantizedTensor matmuls to the Pallas kernel
(compiled on TPU, interpreted elsewhere) or the XLA reference path.

`qmatmul(x, qt)` computes x @ dequantize(qt)^T for the full multi-stripe,
outlier-carrying format; the kernel path never materializes W in HBM.
`kernel_mode` is the one place that decides, from the platform, which of
the two runs and whether Pallas interprets the kernel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.quantized import QuantizedTensor

from . import dequant_matmul as dm
from . import ref as ref_lib
from .plan import (PreparedQuantizedTensor, launch_bn, padded_rows,
                   validated_outliers)

Array = jax.Array


def kernel_mode(mode: Optional[str] = None) -> Tuple[bool, bool]:
    """(use_kernel, interpret) for a quantized matmul traced now.

    On a TPU the answer is always the compiled Pallas kernel: asking for
    the XLA reference (mode="ref") there raises, so a served program on
    the chip can never fall back to it.  Elsewhere Pallas TPU kernels can
    only run interpreted, and the XLA reference is the default
    (mode=None); mode="kernel" selects the interpreted kernel.
    """
    if mode not in (None, "ref", "kernel"):
        raise ValueError(f"unknown quantized-matmul mode {mode!r}")
    if dm._platform() == "tpu":
        if mode == "ref":
            raise ValueError(
                "on a TPU quantized matmuls run the compiled kernel; the "
                "XLA reference is for hosts without one")
        return True, False
    return mode == "kernel", True


def _pad_to(arr: Array, axis: int, mult: int, value=0) -> Array:
    size = arr.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


def stripe_matmul(
    x: Array,
    stripe_packed: Array,
    codebook: Array,
    out_idx: Optional[Array],
    out_val: Optional[Array],
    *,
    bits: int,
    n: int,
    bm: int = dm.DEFAULT_BM,
    bn: int = dm.DEFAULT_BN,
    bk: int = dm.DEFAULT_BK,
    compute_dtype=jnp.float32,
) -> Array:
    """Single-stripe kernel call with all padding handled. x: (M, K)."""
    interpret = kernel_mode("kernel")[1]
    m, k_dim = x.shape
    bm = min(bm, _round_up(m, 8))
    bn = min(bn, _round_up(n, 32))
    bk = min(bk, _round_up(k_dim, 128))

    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    kp = xp.shape[1]
    n_padded = padded_rows(n, bn, (bits,))

    planes = []
    for w, p in zip(packing.plane_widths(bits),
                    packing.split_planes(stripe_packed, bits, n)):
        cpw = 32 // w
        p = _pad_to(p, 0, n_padded // cpw)  # pad rows for padded N
        p = p[: n_padded // cpw]
        planes.append(_pad_to(p, 1, bk))

    cb = _pad_to(codebook.astype(jnp.float32).T, 1, bk)
    oi = ov = None
    if out_idx is not None and out_idx.shape[0] > 0:
        oi = _pad_to(out_idx.astype(jnp.int32), 1, bk, value=-1)
        ov = _pad_to(out_val.astype(jnp.float32), 1, bk)

    y = dm.dequant_matmul(
        xp, tuple(planes), cb, oi, ov,
        bits=bits, n=n_padded, bm=bm, bn=launch_bn(bn, n_padded, bits),
        bk=bk, interpret=interpret, compute_dtype=compute_dtype)
    return y[:m, :n]


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def quantize_activations(x: Array):
    """Per-token (row) dynamic absmax int8 quantization of activations:
    x (..., K) f32 -> (xq (..., K) int8, scale (..., 1) f32) with
    x ≈ xq * scale.  |x/scale| <= 127 exactly at the row max, so round
    never clips; all-zero rows get scale 1 (0/0 would mint NaNs).  The
    quantization error per element is <= scale/2 (round-to-nearest), which
    bounds the matmul error at scale_m/2 * ||W_n||_1 per output element
    (kernels/ref.ref_act_int8_bound, DESIGN.md §9)."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    xq = jnp.round(x.astype(jnp.float32) / scale).astype(jnp.int8)
    return xq, scale


def normalize_act_dtype(act_dtype):
    """None/'f32' -> None (full precision); 'int8' passes through;
    anything else raises.  The single validation point for the activation
    quantization knob — the ServingEngine calls it too."""
    if act_dtype in (None, "f32"):
        return None
    if act_dtype != "int8":
        raise ValueError(f"unsupported act_dtype {act_dtype!r} "
                         "(expected 'f32' or 'int8')")
    return act_dtype


def prepared_qmatmul(
    x: Array,
    pqt: PreparedQuantizedTensor,
    *,
    bm: int = dm.DEFAULT_BM,
    compute_dtype=jnp.float32,
    act_dtype=None,
) -> Array:
    """Fused hot path: x (..., K) @ dequantize(pqt)^T -> (..., N).

    The plan did all per-tensor work offline, so this is exactly ONE
    `pallas_call` per distinct stripe bit-width, each accumulating into
    the same output block via the kernel's acc operand.  Aligned groups
    read plain (i, k) blocks of the RAW x — no gather, no padded
    activation copy (only rows pad to the M block); a permuted group
    takes its columns with one XLA gather of its slice of `gather_idx`
    (Mosaic has no lane gather to do it in-kernel) and launches
    "blocked".  Each launch tiles N by `plan.launch_bn`.

    act_dtype="int8": per-token dynamic absmax quantization of x; the
    kernel consumes int8 activations and the (m, 1) f32 scales fold into
    the output block at the last K step of the LAST group's launch.
    """
    act_dtype = normalize_act_dtype(act_dtype)
    interpret = kernel_mode("kernel")[1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    scale = None
    if act_dtype == "int8":
        x2, scale = quantize_activations(x2)
    m = x2.shape[0]
    bm = min(bm, _round_up(m, 8))

    xp = _pad_to(x2, 0, bm)
    sp = _pad_to(scale, 0, bm) if scale is not None else None
    y = None
    off = 0
    for gi, g in enumerate(pqt.groups):
        if g.x_start is not None:
            xg, fetch = xp, dict(x_mode="aligned", x_base=g.x_start // g.bk,
                                 k_cols=g.k_cols)
        else:
            xg = jnp.take(xp, pqt.gather_idx[off:off + g.k_padded],
                          axis=1, mode="fill", fill_value=0)
            fetch = dict(x_mode="blocked")
        y = dm.dequant_matmul(
            xg, g.planes, g.codebook, g.out_idx, g.out_val,
            out_win=g.out_win, bits=g.bits, n=pqt.n_padded, bm=bm,
            bn=launch_bn(pqt.bn, pqt.n_padded, g.bits), bk=g.bk,
            interpret=interpret, compute_dtype=compute_dtype, acc=y,
            x_scale=sp if gi == len(pqt.groups) - 1 else None, **fetch)
        off += g.k_padded
    y = y[:m, :pqt.rows]
    return y.reshape(lead + (pqt.rows,)).astype(x.dtype)


def _prepared_ref_qmatmul(x: Array, pqt: PreparedQuantizedTensor,
                          act_dtype=None) -> Array:
    """XLA path over the prepared layout.  Unlike ref_qmatmul it never
    scatters W back into original column order: the gather index already
    aligned the activations with the fused group layout, so the matmul is a
    plain per-group dequant + dot accumulation (padded K slots have zero
    codebooks and idx=-1 outliers, so they contribute exactly zero).
    act_dtype="int8" applies the same per-token absmax quantization as the
    kernel path (int8-exact values through the dot, one scale multiply at
    the end)."""
    rows = pqt.rows
    xf = x.astype(jnp.float32)
    scale = None
    if normalize_act_dtype(act_dtype) == "int8":
        xq, scale = quantize_activations(xf)
        xf = xq.astype(jnp.float32)
    xg = jnp.take(xf, pqt.gather_idx, axis=-1, mode="fill", fill_value=0)
    y = jnp.zeros(x.shape[:-1] + (rows,), jnp.float32)
    off = 0
    for g in pqt.groups:
        Wg = jnp.take_along_axis(g.codebook, g.unpack_codes(rows), axis=0)
        Wg = ref_lib.ref_apply_outliers(Wg, g.out_idx, g.out_val)
        # XLA doesn't need the kernel's K padding — slice to the unpadded
        # group so total contraction is exactly `cols` (parity with the
        # dense dot; padded slots are zero anyway).
        xs = jax.lax.slice_in_dim(xg, off, off + g.k_cols, axis=-1)
        y = y + jnp.einsum("...k,nk->...n", xs, Wg[:, :g.k_cols],
                           preferred_element_type=jnp.float32)
        off += g.k_padded
    if scale is not None:
        y = y * scale
    return y


def qmatmul(
    x: Array,
    qt,
    *,
    use_kernel: bool = False,
    compute_dtype=None,
    act_dtype=None,
) -> Array:
    """x (..., K) @ dequantize(qt)^T -> (..., N) for a QuantizedTensor or a
    PreparedQuantizedTensor.

    use_kernel=False: XLA reference path (gather-dequant + dot). This is what
    the CPU dry-run lowers (Pallas TPU kernels can't lower on the CPU
    backend); its HLO cost is the *baseline* the kernel improves on.
    use_kernel=True: the Pallas kernel (compiled on TPU, interpreted
    elsewhere — `kernel_mode`).  Prepared tensors take the fused path, one
    launch per distinct bit-width (`prepared_qmatmul`; under a device mesh
    `dist.sharding.sharded_qmatmul` runs it per shard).  act_dtype="int8"
    opts activations into per-token dynamic int8 quantization (prepared
    tensors only).
    """
    if compute_dtype is None:
        compute_dtype = jnp.float32 if x.dtype == jnp.float32 else jnp.bfloat16
    if isinstance(qt, PreparedQuantizedTensor):
        if not use_kernel:
            return _prepared_ref_qmatmul(x, qt,
                                         act_dtype=act_dtype).astype(x.dtype)
        return prepared_qmatmul(x, qt, compute_dtype=compute_dtype,
                                act_dtype=act_dtype)
    if normalize_act_dtype(act_dtype) is not None:
        raise ValueError(
            "act_dtype quantization needs an ahead-of-time plan — prepare "
            "the tensor first (QuantizedTensor.prepare / prepare_tree; "
            "ServingEngine does this at init unless prepare=False)")
    if not use_kernel:
        return ref_lib.ref_qmatmul(x, qt).astype(x.dtype)

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xp = jnp.take(x2, qt.col_perm, axis=1)     # stripe order
    oi, ov = validated_outliers(qt)

    y = jnp.zeros((x2.shape[0], qt.rows), jnp.float32)
    off = 0
    for s in qt.stripes:
        nc = s.n_cols
        xs = jax.lax.slice_in_dim(xp, off, off + nc, axis=1)
        soi = sov = None
        if oi is not None:
            soi = jax.lax.slice_in_dim(oi, off, off + nc, axis=1)
            sov = jax.lax.slice_in_dim(ov, off, off + nc, axis=1)
        y = y + stripe_matmul(
            xs, s.packed, s.codebook, soi, sov,
            bits=s.bits, n=qt.rows, compute_dtype=compute_dtype)
        off += nc
    return y.reshape(lead + (qt.rows,)).astype(x.dtype)
