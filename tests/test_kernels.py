"""Pallas dequant-GEMM kernel vs the pure-jnp oracle (interpret mode):
shape/dtype/bit-width sweeps, outlier epilogue, multi-stripe AP tensors."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import APConfig, CLAQConfig, ORConfig, quantize_matrix
from repro.core import packing
from repro.kernels import dequant_matmul as dm
from repro.kernels import ops, ref as ref_lib

jax.config.update("jax_platform_name", "cpu")


def _make_stripe(rng, n, k_dim, bits, k_out=0):
    codes = rng.integers(0, 2 ** bits, size=(n, k_dim)).astype(np.int32)
    cb = np.sort(rng.normal(size=(k_dim, 2 ** bits)).astype(np.float32), axis=1)
    packed = packing.pack_codes(jnp.asarray(codes), bits)
    oi = ov = None
    if k_out:
        # distinct row ids per column (CLAQ reserves distinct top-k rows);
        # some slots invalid (-1)
        oi = np.stack([rng.permutation(n)[:k_out] for _ in range(k_dim)],
                      axis=1).astype(np.int32)
        oi[rng.random(oi.shape) < 0.2] = -1
        ov = rng.normal(size=(k_out, k_dim)).astype(np.float32)
    return packed, jnp.asarray(cb), (None if oi is None else jnp.asarray(oi)), \
        (None if ov is None else jnp.asarray(ov))


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("m,n,k_dim", [(4, 32, 64), (17, 96, 160), (1, 40, 128)])
def test_stripe_matmul_matches_oracle(bits, m, n, k_dim):
    rng = np.random.default_rng(bits * 1000 + m)
    packed, cb, _, _ = _make_stripe(rng, n, k_dim, bits)
    x = jnp.asarray(rng.normal(size=(m, k_dim)).astype(np.float32))
    y_ref = ref_lib.ref_dequant_matmul(x, packed, cb, None, None,
                                       bits=bits, n=n)
    y = ops.stripe_matmul(x, packed, cb, None, None, bits=bits, n=n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("k_out", [1, 3, 8])
def test_outlier_epilogue(k_out):
    rng = np.random.default_rng(k_out)
    n, k_dim = 64, 96
    packed, cb, oi, ov = _make_stripe(rng, n, k_dim, 2, k_out=k_out)
    x = jnp.asarray(rng.normal(size=(5, k_dim)).astype(np.float32))
    y_ref = ref_lib.ref_dequant_matmul(x, packed, cb, oi, ov, bits=2, n=n)
    y = ops.stripe_matmul(x, packed, cb, oi, ov, bits=2, n=n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    rng = np.random.default_rng(9)
    n, k_dim = 64, 128
    packed, cb, _, _ = _make_stripe(rng, n, k_dim, 4)
    x = jnp.asarray(rng.normal(size=(8, k_dim)).astype(np.float32)).astype(dtype)
    y_ref = ref_lib.ref_dequant_matmul(x.astype(jnp.float32), packed, cb,
                                       None, None, bits=4, n=n)
    y = ops.stripe_matmul(x.astype(jnp.float32), packed, cb, None, None,
                          bits=4, n=n,
                          compute_dtype=jnp.float32 if dtype == jnp.float32
                          else jnp.bfloat16)
    tol = 1e-3 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref), rtol=tol, atol=tol * 10)


def test_full_quantized_tensor_qmatmul():
    """End-to-end: CLAQ-quantized matrix (AP stripes + OR outliers) through
    the kernel path equals the reference dequant matmul."""
    rng = np.random.default_rng(0)
    rows, cols = 96, 160
    W = rng.normal(size=(rows, cols)).astype(np.float32)
    W[:, :10] += rng.standard_t(df=2, size=(rows, 10)) * 4
    X = rng.normal(size=(256, cols)).astype(np.float32)
    H = jnp.asarray(2 * X.T @ X)
    qt, _, _ = quantize_matrix(jnp.asarray(W), H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=5, gptq_blocksize=32,
        ap=APConfig(2.5, 2, 4), orr=ORConfig(0.15)))
    x = jnp.asarray(rng.normal(size=(7, cols)).astype(np.float32))
    y_ref = ref_lib.ref_qmatmul(x, qt)
    y_ker = ops.qmatmul(x, qt, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)
    # XLA ref path agrees too
    y_xla = ops.qmatmul(x, qt, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def _small_prepared(rng, rows=64, cols=128):
    from repro.core.quantized import build_quantized_tensor
    codes = rng.integers(0, 4, size=(rows, cols))
    cbs = np.sort(rng.normal(size=(cols, 4)), axis=1).astype(np.float32)
    W = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    return build_quantized_tensor(
        jnp.asarray(codes, jnp.int32), jnp.asarray(cbs), np.full(cols, 2),
        np.zeros(cols, np.int32), W, jnp.zeros((rows, cols), bool)).prepare()


def _record_kernel_calls(monkeypatch):
    """Replace the kernel entry with a recorder of its keywords."""
    calls = []

    def fake(x, planes, codebook, out_idx, out_val, *, n, **kw):
        calls.append(kw)
        return jnp.zeros((x.shape[0], n), jnp.float32)

    monkeypatch.setattr(dm, "dequant_matmul", fake)
    return calls


def test_tpu_platform_picks_the_compiled_kernel(monkeypatch):
    """The served path's dispatch: off a TPU a quantized dense() runs the
    XLA reference unless asked for the (interpreted) kernel; on a TPU it
    always runs the compiled kernel, with no flag from the caller."""
    from repro.models import modules as nn
    pqt = _small_prepared(np.random.default_rng(3))
    x = jnp.ones((2, pqt.cols), jnp.float32)
    calls = _record_kernel_calls(monkeypatch)

    assert ops.kernel_mode() == (False, True)
    assert ops.kernel_mode("kernel") == (True, True)
    nn.dense({"kernel": pqt}, x)
    assert calls == []                      # CPU default: the reference
    with nn.quant_mode("kernel"):
        nn.dense({"kernel": pqt}, x)
    assert calls[-1]["interpret"] is True

    monkeypatch.setattr(dm, "_platform", lambda: "tpu")
    assert ops.kernel_mode() == ops.kernel_mode("kernel") == (True, False)
    nn.dense({"kernel": pqt}, x)
    assert len(calls) == 2 and calls[-1]["interpret"] is False


@pytest.mark.parametrize("via", ["reference", "interpret"])
def test_tpu_platform_refuses_interpret_and_reference(monkeypatch, via):
    """On a TPU, asking for the XLA reference (at the decision point and
    through dense()) or running the kernel in Pallas interpret mode
    raises."""
    from repro.models import modules as nn
    pqt = _small_prepared(np.random.default_rng(4))
    x = jnp.ones((2, pqt.cols), jnp.float32)
    monkeypatch.setattr(dm, "_platform", lambda: "tpu")
    if via == "reference":
        with pytest.raises(ValueError, match="compiled kernel"):
            ops.kernel_mode("ref")
        with nn.quant_mode("ref"), \
                pytest.raises(ValueError, match="compiled kernel"):
            nn.dense({"kernel": pqt}, x)
    else:
        g = pqt.groups[0]
        with pytest.raises(ValueError, match="interpret mode"):
            dm.dequant_matmul(x, g.planes, g.codebook, None, None,
                              bits=g.bits, n=pqt.n_padded, bm=8, bn=pqt.bn,
                              bk=g.bk, interpret=True)


def test_block_shape_sweep():
    rng = np.random.default_rng(2)
    n, k_dim = 128, 256
    packed, cb, _, _ = _make_stripe(rng, n, k_dim, 2)
    x = jnp.asarray(rng.normal(size=(16, k_dim)).astype(np.float32))
    y_ref = ref_lib.ref_dequant_matmul(x, packed, cb, None, None, bits=2, n=n)
    for bm, bn, bk in [(8, 32, 128), (16, 64, 256), (128, 128, 128)]:
        y = ops.stripe_matmul(x, packed, cb, None, None, bits=2, n=n,
                              bm=bm, bn=bn, bk=bk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-3)


# --------------------------------------------- Outlier-Reservation windows

def _boundary_qt(rng, rows, bn, stripe_spec, k_out, aligned=False):
    """A QuantizedTensor whose outliers sit on the first and last row of
    the bn-row tiles and on random rows, in shuffled slot order, with a
    quarter of the columns holding none and stale row ids past each
    column's count.  `aligned`: identity column order (one bit-width)."""
    from repro.core.quantized import QuantStripe, QuantizedTensor
    cols = sum(n for _, n in stripe_spec)
    stripes = []
    for bits, n_cols in stripe_spec:
        codes = rng.integers(0, 2 ** bits, size=(rows, n_cols))
        cb = np.sort(rng.normal(size=(n_cols, 2 ** bits)), axis=1)
        stripes.append(QuantStripe(
            packed=packing.pack_codes(jnp.asarray(codes, jnp.int32), bits),
            codebook=jnp.asarray(cb, jnp.float32), bits=bits))
    edges = np.unique([r for t in range(0, rows, bn) for r in (t, t + bn - 1)
                       if r < rows])
    oi = np.empty((k_out, cols), np.int32)
    for c in range(cols):
        first = rng.permutation(edges)[:k_out // 2]
        rest = rng.permutation(np.setdiff1d(np.arange(rows), first))
        oi[:, c] = rng.permutation(
            np.concatenate([first, rest[:k_out - len(first)]]))
    cnt = rng.integers(1, k_out + 1, size=cols)
    cnt[rng.random(cols) < 0.25] = 0
    perm = np.arange(cols) if aligned else rng.permutation(cols)
    return QuantizedTensor(
        stripes=tuple(stripes), col_perm=jnp.asarray(perm, jnp.int32),
        out_idx=jnp.asarray(oi),
        out_val=jnp.asarray(rng.normal(size=(k_out, cols)), jnp.float32),
        out_count=jnp.asarray(cnt, jnp.int32), shape=(rows, cols))


def _full_window(pqt):
    """The same plan with no window table: every tile visits every slot."""
    import dataclasses
    return dataclasses.replace(pqt, groups=tuple(
        dataclasses.replace(g, out_win=None) for g in pqt.groups))


# (id, rows, plan bn, stripes, outliers/col, aligned): one aligned group;
# gathered groups with whole chunks and a tail, one launch tile over
# three plan tiles; 3-bit launch tiles of two plan tiles
WINDOW_LAYOUTS = [
    ("aligned-2bit", 200, 128, [(2, 256)], 12, True),
    ("ap-gathered-bn32", 96, 32, [(2, 160), (4, 96)], 12, False),
    ("ap-3bit-whole-chunks", 300, 128, [(3, 100), (2, 60)], 16, False),
]


@pytest.mark.parametrize("layout", WINDOW_LAYOUTS,
                         ids=[w[0] for w in WINDOW_LAYOUTS])
def test_windowed_epilogue_parity(layout):
    """Outliers on tile-boundary rows, columns with none, count-ordered
    gathered groups: the windowed kernel matches the reference, and is
    bitwise the kernel that visits every slot of the same plan."""
    name, rows, bn, spec, k_out, aligned = layout
    rng = np.random.default_rng(len(name))
    qt = _boundary_qt(rng, rows, bn, spec, k_out, aligned)
    pqt = qt.prepare(bn=bn)
    assert pqt.x_gather_free == aligned
    x = jnp.asarray(rng.normal(size=(9, qt.cols)).astype(np.float32))
    y = ops.prepared_qmatmul(x, pqt)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref_lib.ref_qmatmul(x, qt)),
                               rtol=1e-4, atol=1e-3)
    full = ops.prepared_qmatmul(x, _full_window(pqt))
    assert np.array_equal(np.asarray(y), np.asarray(full))


def test_windowed_epilogue_parity_layer_stacked():
    """A layer-stacked plan (prepared under vmap): each layer's slice
    runs its own windows, bitwise the full-window kernel and within
    tolerance of that layer's reference."""
    rng = np.random.default_rng(11)
    spec = [(2, 160), (4, 96)]
    layers = [_boundary_qt(rng, 96, 32, spec, 12) for _ in range(2)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
    pqt = stacked.prepare(bn=32)
    x = jnp.asarray(rng.normal(size=(5, layers[0].cols)).astype(np.float32))
    for i, qt in enumerate(layers):
        one = jax.tree_util.tree_map(lambda a: a[i], pqt)
        y = ops.prepared_qmatmul(x, one)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ref_lib.ref_qmatmul(x, qt)),
                                   rtol=1e-4, atol=1e-3)
        full = ops.prepared_qmatmul(x, _full_window(one))
        assert np.array_equal(np.asarray(y), np.asarray(full))


def test_windowed_epilogue_parity_n_sharded(subproc):
    """An N-sharded plan (dist.sharding.sharded_qmatmul on a 2x2 mesh):
    each model shard takes the window rows of its own tiles, whose launch
    tile there spans both of its plan tiles; with outliers on every
    tile-boundary row it is bitwise the single-device windowed kernel."""
    subproc('''
import inspect, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, %r)
from test_kernels import _boundary_qt, _full_window
from repro.dist.sharding import MeshAxes, sharded_qmatmul, spec_for_quantized
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import modules as nn

rng = np.random.default_rng(5)
qt = _boundary_qt(rng, 128, 32, [(2, 160), (4, 96)], 12)
pqt = qt.prepare(bn=32)                 # 4 tiles: 2 per model shard
mesh = make_mesh((2, 2), ("data", "model"))
assert pqt.shards_whole_tiles(2)
specs = spec_for_quantized(pqt, MeshAxes(mesh))
assert all(s.out_win[-1] == "model" for s in specs.groups)
x = jnp.asarray(rng.normal(size=(4, qt.cols)).astype(np.float32))
with nn.quant_mode("kernel"):
    one = jax.jit(ops.prepared_qmatmul)(x, pqt)
    full = jax.jit(ops.prepared_qmatmul)(x, _full_window(pqt))
    with mesh:
        got = jax.jit(lambda a, p: sharded_qmatmul(a, p, mesh))(x, pqt)
assert np.array_equal(np.asarray(one), np.asarray(full))
assert np.array_equal(np.asarray(got), np.asarray(one)), float(
    jnp.max(jnp.abs(got - one)))
print("sharded windows bitwise OK")
''' % os.path.dirname(os.path.abspath(__file__)), devices=4, timeout=600)
