"""The fused dequant-GEMM compiles for a TPU v5e at qwen2_1p5b's widths.

No chip is needed: the TPU compiler is installed and compiles for a
described `v5e:2x2` topology.  These compiles catch what interpret mode
cannot — Mosaic's block-shape rules, lowerings it lacks (a lane gather, a
16-bit select mask) — at no chip time.  Nothing runs, so nothing here is
a result or a time.  The topology is described inside a module fixture,
never at import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.outlier import topk_per_column_mask
from repro.core.quantized import build_quantized_tensor
from repro.kernels import dequant_matmul as dm
from repro.kernels import ops
from repro.models import modules as nn

# qwen2_1p5b projection shapes (rows = out features, cols = in features)
QKV, GATE_UP, DOWN = (1536, 1536), (8960, 1536), (1536, 8960)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def on_tpu(monkeypatch):
    """Trace quantized matmuls as the chip does: the platform reads "tpu",
    so the kernel is compiled, never interpreted."""
    monkeypatch.setattr(dm, "_platform", lambda: "tpu")


def _quantized(shape, bit_widths, k_out=0, seed=0):
    """A CLAQ deployment-layout tensor from random codes: one bit-width is
    an identity column order ("aligned" groups), several are permuted."""
    rng = np.random.default_rng(seed)
    rows, cols = shape
    col_bits = np.resize(np.asarray(bit_widths), cols)
    rng.shuffle(col_bits)
    k_max = 2 ** int(col_bits.max())
    codes = rng.integers(0, 2 ** col_bits[None, :], size=(rows, cols))
    cbs = np.sort(rng.normal(size=(cols, k_max)), axis=1).astype(np.float32)
    W = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    counts = rng.integers(0, k_out + 1, size=cols).astype(np.int32)
    return build_quantized_tensor(
        jnp.asarray(codes, jnp.int32), jnp.asarray(cbs), col_bits, counts,
        W, topk_per_column_mask(W, jnp.asarray(counts)))


def _compiled_text(fn, one_chip, x_shape, x_dtype, pqt):
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct(x_shape, x_dtype, sharding=one_chip)
    return jax.jit(fn).lower(x, jax.tree_util.tree_map(sds, pqt)) \
        .compile().as_text()


CASES = [  # (id, shape, column bit-widths, outliers/col, x dtype, act_dtype,
    #         plan bn)
    ("2bit", GATE_UP, (2,), 0, jnp.float32, None, 128),
    ("3bit", DOWN, (3,), 0, jnp.float32, None, 128),
    ("4bit", QKV, (4,), 0, jnp.float32, None, 128),
    ("8bit", QKV, (8,), 0, jnp.float32, None, 128),
    ("ap2.5-gathered", GATE_UP, (2, 3), 0, jnp.float32, None, 128),
    ("4bit-int8-act", DOWN, (4,), 0, jnp.float32, "int8", 128),
    ("3bit-or-bf16", GATE_UP, (3,), 4, jnp.bfloat16, None, 128),
    ("ap2.5-or-bf16", DOWN, (2, 4), 4, jnp.bfloat16, None, 128),
    # OR windows past one 8-slot chunk: the chunk loop and the tail
    ("ap2.5-or12-bf16", GATE_UP, (2, 4), 12, jnp.bfloat16, None, 128),
    # user-set plan tiles below a legal block: launches coarsen them
    ("2bit-bn32", GATE_UP, (2,), 0, jnp.float32, None, 32),
    ("ap2.5-or-bn32", GATE_UP, (2, 3), 2, jnp.bfloat16, None, 32),
]


@pytest.mark.parametrize("m", [8, 128])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_prepared_kernel_compiles_for_v5e(one_chip, case, m):
    name, shape, widths, k_out, x_dtype, act, bn = case
    pqt = _quantized(shape, widths, k_out).prepare(bn=bn)
    assert pqt.x_gather_free == (len(widths) == 1)
    assert pqt.bn == bn and pqt.n_tiles > 1
    text = _compiled_text(
        lambda x, p: ops.prepared_qmatmul(x, p, compute_dtype=x_dtype,
                                          act_dtype=act),
        one_chip, (m, shape[1]), x_dtype, pqt)
    assert "tpu_custom_call" in text


def test_unprepared_stripe_kernel_compiles_for_v5e(one_chip):
    """The per-stripe path (`ServingEngine(prepare=False)`) shares the
    launch tile rule, so a 3-bit stripe's width-1 plane compiles too."""
    qt = _quantized(DOWN, (3,), k_out=2)
    text = _compiled_text(
        lambda x, q: ops.qmatmul(x, q, use_kernel=True),
        one_chip, (8, DOWN[1]), jnp.float32, qt)
    assert "tpu_custom_call" in text


def test_dense_on_tpu_compiles_the_kernel(one_chip):
    """The served path's dispatch: with the platform reading "tpu", a
    quantized dense() lowers to the compiled kernel, not the reference."""
    pqt = _quantized(QKV, (2, 4), k_out=2).prepare()
    text = _compiled_text(lambda x, p: nn.dense({"kernel": p}, x),
                          one_chip, (4, QKV[1]), jnp.bfloat16, pqt)
    assert "tpu_custom_call" in text
