"""bench/weights.py: the on-device CLAQ layout equals what the program's
own `build_quantized_tensor` makes from the same codes."""
import functools
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


def test_served_tree_with_qk_norm_is_the_program_tree():
    """A qk-norm configuration adds the program's per-head norm scales,
    near 1, one row per layer, in the served dtype as the program's
    attention block holds them."""
    from bench import cell
    from repro.models import api

    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    conf.update(TINY, qk_norm=True)
    cfg = cell.model_config(conf, strict=False)
    want = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    got = W.served_params(5, conf)
    for m in ("q_norm", "k_norm"):
        w = want["blocks"]["attn"][m]["scale"]
        g = got["blocks"]["attn"][m]["scale"]
        assert (g.shape, g.dtype) == (w.shape, w.dtype) == (
            (TINY["num_hidden_layers"], TINY["head_dim"]), jnp.bfloat16)
        assert 0.5 < float(g.min()) and float(g.max()) < 1.5
    plain = {k: v for k, v in conf.items() if k != "qk_norm"}
    assert "q_norm" not in W.served_params(5, plain)["blocks"]["attn"]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _parents_build(key, mc_items, claq_items):
    """The one-device build as the benchmark had it before weights could
    be drawn per chip, kept to show that path unchanged."""
    mc, claq = dict(mc_items), dict(claq_items)
    dtype = jnp.bfloat16
    L = mc["num_hidden_layers"]
    params = {}
    for name, (shape, dt) in W.dense_leaf_specs(mc, dtype).items():
        W._set(params, name, W.raw_dense_leaf(key, name, shape, dt))
    for m_i, (name, (rows, cols)) in enumerate(W.matrix_shapes(mc).items()):
        lay = W.layout(rows, cols, claq)
        qt = jax.lax.map(
            lambda l, m_i=m_i, lay=lay: W._assemble(
                W.raw_matrix(W.matrix_key(key, l, m_i), lay), lay),
            jnp.arange(L))
        W._set(params, f"blocks.{name}.kernel", qt)
    return params


def test_one_device_build_is_unchanged():
    """served_params without a mesh is, leaf for leaf and bit for bit, the
    build the glm4_9b cell has always served (at tiny widths)."""
    conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
    conf.update(TINY, rotary_dim=16)
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "attention_bias", "tie_word_embeddings")
    seed = 2**33 + 17
    want = _parents_build(W.base_key(seed), tuple((k, conf[k]) for k in keys),
                          tuple(sorted(conf["claq"].items())))
    got = W.served_params(seed, conf)
    flat_w = jax.tree_util.tree_leaves_with_path(want)
    flat_g = jax.tree_util.tree_leaves_with_path(got)
    assert [p for p, _ in flat_w] == [p for p, _ in flat_g]
    for (path, a), (_, b) in zip(flat_w, flat_g):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
