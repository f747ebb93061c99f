"""Operations and bytes the algorithm needs, computed from shapes.

Kernel side: one call of the CLAQ dequant-GEMM (`kernels/dequant_matmul`)
covers one bit-width group of one prepared matrix.  It needs the group's
unpadded code planes (bits/8 bytes a weight), its codebook (2**bits f32
levels a column), its share of the reserved outliers (an int32 row and an
f32 value each), the activation rows it contracts (bf16) and its f32
output, each once; and 2*M*N*K operations.  Padding, tile re-reads and the
VPU work of dequantization are not counted: they are what a faster kernel
may remove.  On a (data, model) mesh every chip makes its own calls: the
matrix's rows split over `model`, the activation rows over `data`, each
chip reading its share of the planes, outliers and output, the whole
codebook and its activation rows; the calls of every chip are counted.

Model side: operations per token are 2 x the matmul parameters (the head
included) plus attention over the live context (QK^T and PV, 4 x layers x
heads x head_dim per attended position).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import cell
from bench import weights as W

ACT_BYTES = 2      # bf16 activations
OUT_BYTES = 4      # the kernel accumulates and writes f32
CB_BYTES = 4       # f32 codebook levels
OUTLIER_BYTES = 8  # int32 row + f32 value


def kernel_calls(conf: Dict, m: int) -> List[Tuple[float, float]]:
    """(operations, bytes) of every kernel call in one forward pass over
    `m` activation rows (the rows the call is given, padding included),
    on every chip of the configuration's mesh."""
    data, model = cell.mesh_shape(conf)
    m_chip = m / data
    out = []
    for rows, cols in W.matrix_shapes(conf).values():
        lay = W.layout(rows, cols, conf["claq"])
        outliers = lay.n_top * lay.k_top + (cols - lay.n_top) * lay.k_rest
        rows_chip = rows / model
        for bits, k in ((lay.p_lo, cols - lay.n_hi), (lay.p_hi, lay.n_hi)):
            if k == 0:
                continue
            ops = 2.0 * m_chip * rows_chip * k
            nbytes = (rows_chip * k * bits / 8 + (1 << bits) * k * CB_BYTES
                      + outliers * k / cols / model * OUTLIER_BYTES
                      + m_chip * k * ACT_BYTES
                      + m_chip * rows_chip * OUT_BYTES)
            out.append((ops, nbytes))
    return out * (conf["num_hidden_layers"] * data * model)


def least_time_s(calls, peaks: Dict) -> Tuple[float, float, float]:
    """Least time of a list of calls at the chip's peaks, summed call by
    call, and the parts of it bound by operations and by bytes."""
    t_ops = t_bytes = 0.0
    for ops, nbytes in calls:
        a, b = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
        if a >= b:
            t_ops += a
        else:
            t_bytes += b
    return t_ops + t_bytes, t_ops, t_bytes


def matmul_params(conf: Dict) -> int:
    per_layer = sum(r * c for r, c in W.matrix_shapes(conf).values())
    return (conf["num_hidden_layers"] * per_layer
            + conf["hidden_size"] * conf["vocab_size"])


def attention_ops(conf: Dict, positions: float) -> float:
    """QK^T and PV over `positions` attended positions, all layers."""
    return (4.0 * conf["num_hidden_layers"] * conf["num_attention_heads"]
            * conf["head_dim"] * positions)


def decode_token_ops(conf: Dict, context: int) -> float:
    """One decode token that attends over `context` positions (itself
    included)."""
    return 2.0 * matmul_params(conf) + attention_ops(conf, context)
