"""Ahead-of-time inference plans (kernels/plan.py): prepared-vs-reference
parity across bit-widths, mixed-precision stripe layouts, outlier configs,
and odd shapes; plus the launch-count contract — a prepared matmul issues
exactly one pallas_call per distinct stripe bit-width."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import APConfig, CLAQConfig, ORConfig, quantize_matrix
from repro.core import packing
from repro.core.quantized import QuantStripe, QuantizedTensor
from repro.kernels import dequant_matmul as dm
from repro.kernels import ops, ref as ref_lib
from repro.kernels.plan import (PreparedQuantizedTensor, launch_bn,
                                padded_rows, prepare_for_inference,
                                prepare_tree)

jax.config.update("jax_platform_name", "cpu")


def _make_qt(rng, rows, stripe_spec, k_out=0):
    """Synthetic multi-stripe QuantizedTensor.  stripe_spec: [(bits, n_cols)].
    Covers layouts build_quantized_tensor never emits (duplicate bit-widths,
    arbitrary stripe order) so the plan's grouping is exercised directly."""
    cols = sum(n for _, n in stripe_spec)
    stripes = []
    for bits, n_cols in stripe_spec:
        codes = rng.integers(0, 2 ** bits, size=(rows, n_cols)).astype(np.int32)
        cb = np.sort(rng.normal(size=(n_cols, 2 ** bits)).astype(np.float32),
                     axis=1)
        stripes.append(QuantStripe(
            packed=packing.pack_codes(jnp.asarray(codes), bits),
            codebook=jnp.asarray(cb), bits=bits))
    col_perm = jnp.asarray(rng.permutation(cols).astype(np.int32))
    if k_out > 0:
        oi = np.stack([rng.permutation(rows)[:k_out] for _ in range(cols)],
                      axis=1).astype(np.int32)
        ov = rng.normal(size=(k_out, cols)).astype(np.float32)
        cnt = rng.integers(0, k_out + 1, size=(cols,)).astype(np.int32)
    else:
        oi = np.zeros((0, cols), np.int32)
        ov = np.zeros((0, cols), np.float32)
        cnt = np.zeros((cols,), np.int32)
    return QuantizedTensor(
        stripes=tuple(stripes), col_perm=col_perm,
        out_idx=jnp.asarray(oi), out_val=jnp.asarray(ov),
        out_count=jnp.asarray(cnt), shape=(rows, cols))


def _gathered(pqt):
    """`pqt` with every group taking its x columns through the XLA gather
    (as a permuted group does) instead of reading raw x blocks."""
    return dataclasses.replace(pqt, groups=tuple(
        dataclasses.replace(g, x_start=None) for g in pqt.groups))


def _check_parity(qt, m=7, seed=0, atol=1e-3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, qt.cols)).astype(np.float32))
    pqt = prepare_for_inference(qt)
    np.testing.assert_allclose(np.asarray(pqt.dequantize()),
                               np.asarray(qt.dequantize()),
                               rtol=1e-6, atol=1e-6)
    y_ref = ref_lib.ref_qmatmul(x, qt)
    y = ops.qmatmul(x, pqt, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=atol)
    # aligned groups read raw x blocks; they must be BITWISE the same
    # groups fed x through the XLA gather, at every layout in this suite
    y_pre = ops.prepared_qmatmul(x, _gathered(pqt))
    assert np.array_equal(np.asarray(y), np.asarray(y_pre)), \
        "aligned x fetch diverged bitwise from the XLA-gather path"
    y_xla = ops.qmatmul(x, pqt, use_kernel=False)
    # both sides sum the same cols f32 products in different orders; each
    # lies within gamma_K * sum|x||W| of the exact sum (gamma_K = K u /
    # (1 - K u), u = eps32 / 2), so they differ by at most twice that
    u = np.finfo(np.float32).eps / 2
    gamma = qt.cols * u / (1 - qt.cols * u)
    mag = np.abs(np.asarray(x, np.float64)) @ np.abs(
        np.asarray(qt.dequantize(), np.float64)).T
    gap = np.abs(np.asarray(y_xla, np.float64) - np.asarray(y_ref, np.float64))
    assert np.all(gap <= 2 * gamma * mag), (gap.max(), (gap / mag).max())
    return pqt


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("k_out", [0, 3])
def test_single_bitwidth_parity(bits, k_out):
    rng = np.random.default_rng(bits * 10 + k_out)
    qt = _make_qt(rng, rows=64, stripe_spec=[(bits, 96)], k_out=k_out)
    pqt = _check_parity(qt)
    assert len(pqt.groups) == 1


@pytest.mark.parametrize("spec", [
    [(2, 80), (4, 48)],              # the layout build_quantized_tensor emits
    [(2, 40), (3, 56), (4, 32)],     # three distinct bit-widths
    [(2, 24), (4, 40), (2, 32)],     # duplicate bit-width stripes fuse
])
@pytest.mark.parametrize("k_out", [0, 2])
def test_mixed_precision_parity(spec, k_out):
    rng = np.random.default_rng(len(spec) * 100 + k_out)
    qt = _make_qt(rng, rows=96, stripe_spec=spec, k_out=k_out)
    pqt = _check_parity(qt)
    assert len(pqt.groups) == len({b for b, _ in spec})


def test_non_multiple_of_block_shapes():
    rng = np.random.default_rng(5)
    # rows not a multiple of 32, stripe columns not multiples of 128
    qt = _make_qt(rng, rows=40, stripe_spec=[(2, 72), (4, 19)], k_out=2)
    pqt = _check_parity(qt, m=17)
    assert pqt.n_padded % 32 == 0
    for g in pqt.groups:
        assert g.k_padded % g.bk == 0


def test_end_to_end_claq_tensor_parity():
    """Full CLAQ recipe (AP stripes + OR outliers) through the plan."""
    rng = np.random.default_rng(0)
    rows, cols = 96, 160
    W = rng.normal(size=(rows, cols)).astype(np.float32)
    W[:, :10] += rng.standard_t(df=2, size=(rows, 10)) * 4
    X = rng.normal(size=(256, cols)).astype(np.float32)
    H = jnp.asarray(2 * X.T @ X)
    qt, _, _ = quantize_matrix(jnp.asarray(W), H, CLAQConfig(
        bits=2, method="kmeans", kmeans_iters=5, gptq_blocksize=32,
        ap=APConfig(2.5, 2, 4), orr=ORConfig(0.15)))
    pqt = _check_parity(qt)
    # the paper layout: one stripe per bit-class -> one group per bit-class
    assert len(pqt.groups) == len({s.bits for s in qt.stripes})


def test_launch_count_is_distinct_bitwidths():
    """Regression: the fused dispatch issues exactly one pallas_call per
    distinct stripe bit-width — NOT one per stripe."""
    rng = np.random.default_rng(9)
    spec = [(2, 40), (4, 56), (2, 24), (3, 32)]   # 4 stripes, 3 bit-widths
    qt = _make_qt(rng, rows=64, stripe_spec=spec, k_out=1)
    x = jnp.asarray(rng.normal(size=(5, qt.cols)).astype(np.float32))

    before = dm.launch_count
    y_unprepared = ops.qmatmul(x, qt, use_kernel=True)
    unprepared_launches = dm.launch_count - before
    assert unprepared_launches == len(spec)

    pqt = prepare_for_inference(qt)
    before = dm.launch_count
    y_prepared = ops.qmatmul(x, pqt, use_kernel=True)
    prepared_launches = dm.launch_count - before
    assert prepared_launches == len({b for b, _ in spec}) == 3

    # how x reaches the kernel must not change the launch contract:
    # gathered groups issue one launch per distinct bit-width too
    before = dm.launch_count
    y_pre = ops.prepared_qmatmul(x, _gathered(pqt))
    assert dm.launch_count - before == prepared_launches
    assert np.array_equal(np.asarray(y_prepared), np.asarray(y_pre))

    np.testing.assert_allclose(np.asarray(y_prepared),
                               np.asarray(y_unprepared),
                               rtol=1e-4, atol=1e-3)


def _with_identity_perm(qt):
    return QuantizedTensor(
        stripes=qt.stripes, col_perm=jnp.arange(qt.cols, dtype=jnp.int32),
        out_idx=qt.out_idx, out_val=qt.out_val, out_count=qt.out_count,
        shape=qt.shape)


def test_identity_perm_plans_are_x_aligned():
    """Single-bit-width tensors carry an identity column permutation
    (build_quantized_tensor sorts within each bit-class), so their plans
    must drop per-column indexing entirely: x_start set — the kernel then
    reads raw x blocks and the matmul is gather-free."""
    rng = np.random.default_rng(21)
    qt = _with_identity_perm(
        _make_qt(rng, rows=64, stripe_spec=[(3, 200)], k_out=2))
    pqt = _check_parity(qt)
    assert pqt.x_gather_free
    assert pqt.groups[0].x_start == 0

    # the end-to-end integer-bit recipe really hits this path
    W = rng.normal(size=(64, 96)).astype(np.float32)
    qte, _, _ = quantize_matrix(jnp.asarray(W), None, CLAQConfig(
        bits=3, method="kmeans", kmeans_iters=3, gptq_blocksize=32))
    assert prepare_for_inference(qte).x_gather_free


def test_permuted_plans_carry_block_index_tables():
    """Permuted / mixed-precision layouts are not x-aligned: each group's
    slice of gather_idx is exactly its stripes' original columns in fused
    order (the one XLA take that feeds the kernel in "blocked" mode) —
    for a gathered group with outliers, by descending outlier count, ties
    in stripe order — padding slots = cols, and every slice fills whole
    bk blocks."""
    rng = np.random.default_rng(22)
    qt = _make_qt(rng, rows=64, stripe_spec=[(2, 80), (4, 48)], k_out=2)
    pqt = _check_parity(qt)
    assert not pqt.x_gather_free
    perm = np.asarray(qt.col_perm)
    off = 0
    for g, (lo, hi) in zip(pqt.groups, [(0, 80), (80, 128)]):
        assert g.x_start is None and g.k_padded % g.bk == 0
        idx = np.asarray(pqt.gather_idx[off:off + g.k_padded])
        count = np.asarray(qt.out_count)[perm[lo:hi]]
        np.testing.assert_array_equal(
            idx[:g.k_cols], perm[lo:hi][np.argsort(-count, kind="stable")])
        # padded slots point at `cols` (the zero fill), never a real column
        assert (idx[g.k_cols:] == qt.cols).all()
        off += g.k_padded


def test_plan_cached_on_tensor_and_prepare_tree():
    rng = np.random.default_rng(3)
    qt = _make_qt(rng, rows=32, stripe_spec=[(2, 48)])
    assert qt.prepare() is qt.prepare()

    params = {"layer": {"kernel": qt, "bias": jnp.zeros((32,))},
              "norm": {"scale": jnp.ones((48,))}}
    prepared = prepare_tree(params)
    assert isinstance(prepared["layer"]["kernel"], PreparedQuantizedTensor)
    assert prepared["norm"]["scale"].shape == (48,)
    # idempotent: preparing an already-prepared tree is the identity
    again = prepare_tree(prepared)
    assert again["layer"]["kernel"] is prepared["layer"]["kernel"]


def test_layer_stacked_tensor_preparation():
    """launch.quantize stacks per-layer QuantizedTensors (leading L dim on
    every data leaf, per-matrix `shape` meta).  Preparation must vmap over
    the stack and slice back per layer — the ServingEngine path."""
    rng = np.random.default_rng(11)
    spec = [(2, 48), (4, 32)]
    qts = [_make_qt(np.random.default_rng(100 + i), rows=64,
                    stripe_spec=spec, k_out=2) for i in range(3)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *qts)
    assert stacked.stripes[0].packed.ndim == 3

    pst = prepare_for_inference(stacked)
    assert pst.gather_idx.shape[0] == 3
    x = jnp.asarray(rng.normal(size=(5, qts[0].cols)).astype(np.float32))
    for i, qt in enumerate(qts):
        layer = jax.tree_util.tree_map(lambda a: a[i], pst)
        np.testing.assert_allclose(np.asarray(layer.dequantize()),
                                   np.asarray(qt.dequantize()),
                                   rtol=1e-6, atol=1e-6)
        y = ops.qmatmul(x, layer, use_kernel=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ref_lib.ref_qmatmul(x, qt)),
                                   rtol=1e-4, atol=1e-3)
    # prepare_tree hits stacked leaves too (what the engine actually does)
    tree = prepare_tree({"blocks": {"kernel": stacked}})
    assert isinstance(tree["blocks"]["kernel"], PreparedQuantizedTensor)


def test_prepared_expert_weight_dequant():
    """MoE expert leaves (leading E axis) prepared by the engine must still
    materialize through models.moe._expert_weight."""
    from repro.models.moe import _expert_weight
    qts = [_make_qt(np.random.default_rng(200 + e), rows=32,
                    stripe_spec=[(2, 24), (4, 24)], k_out=1)
           for e in range(2)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *qts)
    prepared = prepare_for_inference(stacked)
    w = _expert_weight(prepared, jnp.float32)       # (E, in, out)
    assert w.shape == (2, 48, 32)
    for e, qt in enumerate(qts):
        np.testing.assert_allclose(np.asarray(w[e]),
                                   np.asarray(qt.dequantize()).T,
                                   rtol=1e-6, atol=1e-6)


def test_prepared_tensor_is_a_pytree():
    """Prepared leaves must flow through jit (the serving engine's params)."""
    rng = np.random.default_rng(4)
    qt = _make_qt(rng, rows=64, stripe_spec=[(2, 64), (4, 64)], k_out=2)
    pqt = prepare_for_inference(qt)
    x = jnp.asarray(rng.normal(size=(3, qt.cols)).astype(np.float32))

    @jax.jit
    def f(x, p):
        return ops.qmatmul(x, p, use_kernel=True)

    y = f(x, pqt)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref_lib.ref_qmatmul(x, qt)),
                               rtol=1e-4, atol=1e-3)
    leaves = jax.tree_util.tree_leaves(pqt)
    assert all(isinstance(l, jax.Array) for l in leaves)


@pytest.mark.parametrize("bn,n_padded,bits,want", [
    (128, 1536, 2, 128), (128, 1536, 4, 128), (128, 1536, 3, 256),
    (32, 8960, 2, 128), (32, 8960, 3, 256), (32, 128, 8, 128),
    (32, 96, 3, 96), (128, 896, 3, 896), (32, 64, 2, 64)])
def test_launch_tile_is_a_legal_mosaic_block(bn, n_padded, bits, want):
    """A launch tiles N by the fewest whole plan tiles whose blocks Mosaic
    accepts: a lane extent that is a multiple of 128 and a multiple of 8
    packed words in every code plane — or one tile over every row."""
    got = launch_bn(bn, n_padded, bits)
    assert got == want and n_padded % got == 0 and got % bn == 0
    if got != n_padded:
        assert got % 128 == 0
        for w in packing.plane_widths(bits):
            assert (got // (32 // w)) % 8 == 0


@pytest.mark.parametrize("rows,bn,widths,want", [
    (4480, 128, (3,), 4608), (8960, 128, (2, 4), 8960),
    (1536, 32, (2,), 1536), (128, 32, (2, 4), 128), (96, 16, (3,), 96),
    (200, 32, (3,), 224), (300, 32, (2,), 384)])
def test_plan_rows_pad_to_whole_launch_units(rows, bn, widths, want):
    """Rows pad to whole bn tiles; past one launch unit they pad to whole
    units, so every launch of the plan takes a legal tile of at most a
    unit's lcm with bn rows, never all of a large matrix's rows."""
    n = padded_rows(rows, bn, widths)
    assert n == want and n % bn == 0
    for bits in widths:
        tile = launch_bn(bn, n, bits)
        assert n % tile == 0
        assert tile <= (n if rows <= 256 else math.lcm(bn, 256))


# (rows, plan bn, stripes, outliers/col, layer stack): single and mixed
# bit-widths, slot counts below, at and past one chunk, launch tiles of
# one and of several plan tiles, a layer stack
WINDOW_PLANS = [
    (64, 128, [(2, 96)], 3, 0),
    (200, 32, [(2, 80), (4, 48)], 12, 0),
    (300, 128, [(3, 100), (2, 60)], 16, 0),
    (96, 32, [(2, 40), (3, 56), (4, 32)], 20, 2),
]


@pytest.mark.parametrize("case", WINDOW_PLANS)
def test_plan_slot_windows_cover_every_outlier(case):
    """The windowed epilogue's plan invariant: each column's slots ascend
    by row with the padding last, a gathered group's columns descend by
    outlier count, and for every (bn tile, K block) each outlier whose
    row lies in the tile has its slot inside [lo, hi) — bounds whole
    chunks, lo <= hi, neither decreasing down the tiles, and no wider
    than the definition (columns without outliers do not pull lo)."""
    rows, bn, spec, k_out, stack = case
    rng = np.random.default_rng(rows + k_out)
    layers = [_make_qt(rng, rows, spec, k_out) for _ in range(max(stack, 1))]
    qt = (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
          if stack else layers[0])
    pqt = prepare_for_inference(qt, bn=bn)
    c = dm.SLOT_CHUNK
    for g in pqt.groups:
        idx = np.asarray(g.out_idx).reshape(-1, k_out, g.k_padded)
        win = np.asarray(g.out_win).reshape(
            -1, pqt.n_tiles, g.k_padded // g.bk, 2)
        for li in range(idx.shape[0]):
            ix, w = idx[li], win[li]
            valid = ix >= 0
            assert (np.diff(valid.astype(int), axis=0) <= 0).all()
            assert (np.diff(np.where(valid, ix, rows), axis=0)[
                valid[1:]] > 0).all()
            if g.x_start is None:
                assert (np.diff(valid[:, :g.k_cols].sum(0)) <= 0).all()
            lo, hi = w[..., 0], w[..., 1]
            assert (lo % c == 0).all() and (hi % c == 0).all()
            assert (lo <= hi).all() and (hi <= -(-k_out // c) * c).all()
            assert (np.diff(lo, axis=0) >= 0).all()
            assert (np.diff(hi, axis=0) >= 0).all()
            s, col = np.nonzero(valid)
            t, b = ix[s, col] // pqt.bn, col // g.bk
            assert (lo[t, b] <= s).all() and (s < hi[t, b]).all()
            held = valid.any(0)
            for t in range(pqt.n_tiles):
                first = (valid & (ix < t * pqt.bn)).sum(0)
                end = (valid & (ix < (t + 1) * pqt.bn)).sum(0)
                for b in range(g.k_padded // g.bk):
                    blk = slice(b * g.bk, (b + 1) * g.bk)
                    top = end[blk].max()
                    least = (min(first[blk][held[blk]].min(), top)
                             if held[blk].any() else 0)
                    assert (lo[t, b], hi[t, b]) == (
                        least // c * c, -(-top // c) * c)


def test_or_slot_share_weighs_windows_by_launch_tile():
    """`or_slot_share` over a tree: 100% when every window is the whole
    slot range, the launch tiles' mean window over k_out otherwise, and
    nothing for a tree without outliers."""
    from repro.kernels.plan import or_slot_share
    rng = np.random.default_rng(8)
    qt = _make_qt(rng, rows=256, stripe_spec=[(2, 128)], k_out=16)
    pqt = prepare_for_inference(qt, bn=32)
    (g,) = pqt.groups
    d = launch_bn(pqt.bn, pqt.n_padded, g.bits) // pqt.bn
    w = np.asarray(g.out_win).reshape(pqt.n_tiles, g.k_padded // g.bk, 2)
    width = np.minimum(w[d - 1::d, :, 1], 16) - w[::d, :, 0]
    assert or_slot_share({"a": pqt, "b": jnp.ones(3)}) == pytest.approx(
        100 * width.mean() / 16)
    whole = dataclasses.replace(pqt, groups=(dataclasses.replace(
        g, out_win=jnp.tile(jnp.array([0, 16], jnp.int32), w.size // 2)),))
    assert or_slot_share([whole]) == pytest.approx(100.0)
    plain = prepare_for_inference(_make_qt(rng, 64, [(2, 96)]))
    assert or_slot_share({"a": plain}) is None


def test_prepare_tree_unloads_its_plan_builders():
    """`prepare_tree` leaves no plan-building program loaded once the
    tree is built (each holds its code in device memory); the plans it
    built are those of `prepare_for_inference`."""
    rng = np.random.default_rng(9)
    qt = _make_qt(rng, rows=48, stripe_spec=[(2, 72), (4, 40)], k_out=5)
    params = {"w": qt, "b": jnp.ones(3)}
    client = jax.devices()[0].client
    loaded = len(client.live_executables())
    tree = prepare_tree(params)
    # no more than before: builders cached by earlier direct
    # prepare_for_inference calls go with the tree's own
    assert len(client.live_executables()) <= loaded
    want = prepare_for_inference(qt)
    for a, b in zip(jax.tree_util.tree_leaves(tree["w"]),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
