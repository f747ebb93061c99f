"""CLAQ orchestration: plan -> quantize -> package, per matrix and per model.

This is the host-level driver (quantization is an offline pipeline); the
inner loops (`gptq.gptq_quantize_matrix`, `kmeans`) are jit-compiled.  A
row-sharded variant runs the same engine under `shard_map` for mesh-parallel
quantization of large matrices (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import gptq, kmeans as kmeans_lib, outlier as outlier_lib, policy
from .policy import APConfig, CLAQConfig, ORConfig  # re-export  # noqa: F401
from .quantized import QuantizedTensor, build_quantized_tensor

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MatrixPlan:
    """Host-side allocation decisions for one matrix (all static)."""
    column_bits: np.ndarray      # (cols,) int
    reserve_counts: np.ndarray   # (cols,) int
    achieved_code_bits: float
    achieved_extra_bits: float
    outlier_ratio: np.ndarray    # (cols,) float — the Outlier Order metric


@dataclasses.dataclass(frozen=True)
class QuantStats:
    proxy_loss: float          # tr((W-Q) H (W-Q)^T) / rows
    mse: float
    effective_bits: float      # codes + reserved outliers
    effective_bits_with_codebooks: float
    code_bits: float
    extra_bits: float


def plan_matrix(W: Array, cfg: CLAQConfig,
                metric: str = "outlier_order",
                act_norm: Optional[Array] = None) -> MatrixPlan:
    """Compute per-column bit-widths and reservation counts.

    metric: 'outlier_order' (paper) or 'magnitude_mp' (Table 3 baseline).
    """
    rows, cols = W.shape
    if metric == "outlier_order":
        R = outlier_lib.outlier_ratio(W, cfg.outlier_standard)
        # Tie-break by normalized column peak magnitude: R_j is quantized in
        # steps of 1/rows, so a term < 1/(2*rows) can never reorder distinct
        # ratios, but it keeps the Outlier Order total when no entry clears
        # S*mean (small calibration-free matrices, near-Gaussian weights) —
        # the ranking limit of Eq. 3 as the outlier standard decreases.
        peak = jnp.max(jnp.abs(W.astype(jnp.float32)), axis=0)
        R = R + peak / (jnp.max(peak) + 1e-30) * (0.5 / rows)
    elif metric == "magnitude_mp":
        R = policy.magnitude_mp_metric(W, act_norm)
    else:
        raise ValueError(metric)

    if cfg.ap is not None:
        bits, code_bits = policy.ap_column_bits(R, cfg.ap)
    else:
        bits = jnp.full((cols,), cfg.bits, jnp.int32)
        code_bits = float(cfg.bits)

    if cfg.orr is not None:
        counts, extra_bits = policy.or_reserve_counts(R, rows, cfg.orr)
    else:
        counts = jnp.zeros((cols,), jnp.int32)
        extra_bits = 0.0

    return MatrixPlan(
        column_bits=np.asarray(bits),
        reserve_counts=np.asarray(counts),
        achieved_code_bits=float(code_bits),
        achieved_extra_bits=float(extra_bits),
        outlier_ratio=np.asarray(R),
    )


def _pad_cols(arr: Array, cols_p: int, value=0):
    pad = cols_p - arr.shape[-1]
    if pad == 0:
        return arr
    widths = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
    return jnp.pad(arr, widths, constant_values=value)


def quantize_matrix(
    W: Array,
    H: Optional[Array],
    cfg: CLAQConfig,
    plan: Optional[MatrixPlan] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    shard_axis: str = "model",
) -> Tuple[QuantizedTensor, Array, QuantStats]:
    """Quantize one (rows=out, cols=in) matrix with the full CLAQ recipe.

    H=None falls back to an identity Hessian (pure weight-space rounding;
    used for ablations and when no calibration data is available).
    Returns (deployable QuantizedTensor, dequantized matrix, stats).
    """
    W = jnp.asarray(W, jnp.float32)
    rows, cols = W.shape
    if plan is None:
        plan = plan_matrix(W, cfg, metric=cfg.metric)
    if H is None:
        H = jnp.eye(cols, dtype=jnp.float32)

    reserved = outlier_lib.topk_per_column_mask(
        W, jnp.asarray(plan.reserve_counts, jnp.int32))

    # Pad the column axis to the GPTQ blocksize (identity-extended Hessian).
    B = cfg.gptq_blocksize
    cols_p = ((cols + B - 1) // B) * B
    Wp = _pad_cols(W, cols_p)
    Hp = jnp.eye(cols_p, dtype=jnp.float32).at[:cols, :cols].set(
        H.astype(jnp.float32))
    bits_p = _pad_cols(jnp.asarray(plan.column_bits, jnp.int32), cols_p,
                       value=int(plan.column_bits.min(initial=cfg.bits)))
    res_p = _pad_cols(reserved, cols_p, value=False)

    U = gptq.prepare_hinv_cholesky(Hp, cfg.percdamp)

    frozen = None
    if cfg.codebook_mode == "frozen":
        weight = jnp.where(res_p, 0.0, 1.0)
        frozen, _ = kmeans_lib.kmeans_columns(
            Wp, k_max=2 ** cfg.p_max, k_valid=2 ** bits_p,
            iters=cfg.kmeans_iters, weight=weight)

    kwargs = dict(
        k_max=2 ** cfg.p_max, blocksize=B, method=cfg.method,
        kmeans_iters=cfg.kmeans_iters, codebook_mode=cfg.codebook_mode,
        frozen_codebooks=frozen,
    )
    if mesh is not None:
        result = _quantize_rowsharded(Wp, U, bits_p, res_p, kwargs, mesh, shard_axis)
    else:
        result = gptq.gptq_quantize_matrix(Wp, U, bits_p, res_p, **kwargs)

    Q = result.Q[:, :cols]
    qt = build_quantized_tensor(
        codes=result.codes[:, :cols],
        codebooks=result.codebooks[:cols],
        column_bits=plan.column_bits,
        reserve_counts=plan.reserve_counts,
        Q=Q,
        reserved_mask=reserved,
    )
    stats = QuantStats(
        proxy_loss=float(gptq.proxy_loss(W, Q, H)),
        mse=float(jnp.mean((W - Q) ** 2)),
        effective_bits=qt.effective_bits(),
        effective_bits_with_codebooks=qt.effective_bits(include_codebooks=True),
        code_bits=plan.achieved_code_bits,
        extra_bits=plan.achieved_extra_bits,
    )
    return qt, Q, stats


def _quantize_rowsharded(Wp, U, bits_p, res_p, kwargs, mesh, shard_axis):
    """Run the GPTQ loop with matrix rows sharded over `shard_axis`."""
    from jax.sharding import PartitionSpec as P

    def body(Wl, Ul, bl, rl):
        return gptq.gptq_quantize_matrix(
            Wl, Ul, bl, rl, axis_name=shard_axis, **kwargs)

    out_specs = gptq.QuantizeResult(
        Q=P(shard_axis, None), codes=P(shard_axis, None),
        codebooks=P(None, None), reserved=P(shard_axis, None))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(shard_axis, None), P(None, None), P(None), P(shard_axis, None)),
        out_specs=out_specs,
        check_vma=False,
    )
    res = fn(Wp, U, bits_p, res_p)
    # codebooks are computed replicated per shard; shard_map stacks them —
    # they are identical, so out_specs P(None, None) keeps one copy.
    return res


# ---------------------------------------------------------------------------
# Whole-model quantization
# ---------------------------------------------------------------------------

def default_quantize_predicate(path: str, leaf: Any) -> bool:
    """Quantize 2-D matmul weights; leave embeddings, norms, biases, and
    tiny recurrence parameters (SSM decay vectors, conv kernels) in fp."""
    if not hasattr(leaf, "ndim") or leaf.ndim != 2:
        return False
    name = path.lower()
    if any(k in name for k in ("embed", "norm", "bias", "a_log", "dt_bias",
                               "decay", "conv", "pos", "router")):
        return False
    return min(leaf.shape) >= 32


def quantize_model(
    params: Dict[str, Any],
    hessians: Dict[str, Array],
    cfg: CLAQConfig,
    predicate: Callable[[str, Any], bool] = default_quantize_predicate,
    metric: str = "outlier_order",
    dense_output: bool = False,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[Dict[str, Any], Dict[str, QuantStats]]:
    """Quantize every eligible kernel in a params pytree.

    Weights are stored in JAX kernel layout (in, out); the engine works in
    paper layout (out, in), so kernels are transposed on the way in/out.
    ``hessians`` maps tap names (the dense() call path) to (in,in) Hessians;
    missing entries fall back to identity.
    Returns (new params with QuantizedTensor (or dense) leaves, stats dict).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out_leaves = []
    stats: Dict[str, QuantStats] = {}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if not predicate(name, leaf):
            out_leaves.append(leaf)
            continue
        H = hessians.get(name)
        qt, Q, st = quantize_matrix(jnp.asarray(leaf).T, H, cfg, mesh=mesh)
        stats[name] = st
        out_leaves.append(Q.T.astype(leaf.dtype) if dense_output else qt)
    return jax.tree_util.tree_unflatten(treedef, out_leaves), stats
