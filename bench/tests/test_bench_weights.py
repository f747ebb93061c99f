"""bench/weights.py: the on-device CLAQ layout equals what the program's
own `build_quantized_tensor` makes from the same codes."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ROOT, TINY

from bench import weights as W
from repro.core import APConfig, ORConfig, policy
from repro.core.quantized import build_quantized_tensor

CLAQ = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())["claq"]


@pytest.mark.parametrize("rows,cols", [(256, 128), (64, 128), (128, 256)])
def test_layout_matches_program_build(rows, cols):
    lay = W.layout(rows, cols, CLAQ)
    raw = W.raw_matrix(W.matrix_key(W.base_key(2**40 + 3), 1, 2), lay)
    qt = W.quantized_matrix(W.matrix_key(W.base_key(2**40 + 3), 1, 2), lay)
    cnt = np.asarray(raw["counts"])
    mask = np.zeros((rows, cols), bool)
    rows_np = np.asarray(raw["out_rows"])
    for c in range(cols):
        mask[rows_np[:cnt[c], c], c] = True
    dq = W.dequantize(raw)
    ref = build_quantized_tensor(jnp.asarray(raw["codes"], jnp.int32),
                                 raw["codebook"], np.asarray(raw["bits"]),
                                 cnt, dq, jnp.asarray(mask))
    assert [s.bits for s in qt.stripes] == [s.bits for s in ref.stripes]
    for a, b in zip(qt.stripes, ref.stripes):
        np.testing.assert_array_equal(a.packed, b.packed)
        np.testing.assert_array_equal(a.codebook, b.codebook)
    np.testing.assert_array_equal(qt.col_perm, ref.col_perm)
    np.testing.assert_array_equal(qt.out_count, ref.out_count)
    valid = np.arange(lay.k_max)[:, None] < cnt[None, :]
    np.testing.assert_array_equal(np.where(valid, qt.out_idx, -1),
                                  np.where(valid, ref.out_idx, -1))
    np.testing.assert_array_equal(np.where(valid, qt.out_val, 0),
                                  np.where(valid, ref.out_val, 0))
    np.testing.assert_array_equal(qt.dequantize(), ref.dequantize())
    np.testing.assert_array_equal(ref.dequantize(), dq)


def test_layout_counts_match_policy():
    rows, cols = 256, 128
    lay = W.layout(rows, cols, CLAQ)
    R = jax.random.uniform(jax.random.PRNGKey(0), (cols,))
    bits, _ = policy.ap_column_bits(R, APConfig(CLAQ["target_bits"],
                                                CLAQ["p_lo"], CLAQ["p_hi"]))
    assert int((np.asarray(bits) == CLAQ["p_hi"]).sum()) == lay.n_hi
    counts, _ = policy.or_reserve_counts(R, rows, ORConfig(
        CLAQ["or_extra_bits"], CLAQ["or_o1"], CLAQ["or_o2"],
        CLAQ["or_top_frac"]))
    counts = np.sort(np.asarray(counts))[::-1]
    assert counts[0] == lay.k_top and counts[-1] == lay.k_rest
    assert int((counts == lay.k_top).sum()) == lay.n_top


def test_served_tree_is_the_program_tree():
    """Same tree, shapes and dtypes as api.init_params, quantized where
    launch.quantize would quantize."""
    from bench import cell
    from repro.models import api

    conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
    conf.update(TINY, rotary_dim=16)
    cfg = cell.model_config(conf, strict=False)
    want = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    got = W.served_params(5, conf)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    flat_g = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            got, is_leaf=lambda x: hasattr(x, "stripes"))[0]:
        flat_g[path] = leaf
    assert set(flat_w) == set(flat_g)
    for path, leaf in flat_w.items():
        g = flat_g[path]
        if path[-1].key == "kernel" and path[0].key == "blocks":
            L, cols, rows = leaf.shape
            assert g.shape == (rows, cols)
            assert g.col_perm.shape == (L, cols)
        else:
            assert (g.shape, g.dtype) == (leaf.shape, leaf.dtype), path


def test_seed_past_32_bits_changes_the_weights():
    lay = W.layout(64, 128, CLAQ)
    a = W.raw_matrix(W.base_key(5), lay)["codes"]
    b = W.raw_matrix(W.base_key(5 + 2**32), lay)["codes"]
    assert not np.array_equal(a, b)
