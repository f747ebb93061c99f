"""Unit tests for the quantized-leaf sharding rules (dist/sharding.py).

A PreparedQuantizedTensor is sharded AS A UNIT along N: packed code planes
split on their packed-row axis in whole (bn, bk) tiles, codebooks /
outlier tables / gather index replicated.  These tests pin the
PartitionSpecs at model sizes that do and do not divide the tile count
(``n_padded // bn``) — a non-dividing mesh must replicate the WHOLE unit,
never tear it — including stacked (L, ...) leaves, plus the stacked-cache
rule and the spec_for_param guard against quantized internals.

Rules are pure `(name, shape | unit, ax) -> PartitionSpec` functions, so
they are tested with a duck-typed MeshAxes stand-in — no multi-device
runtime needed (tests/test_dist_serving.py covers real execution).
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.core.quantized import QuantStripe, QuantizedTensor
from repro.dist import sharding as shd
from repro.kernels.plan import PreparedQuantizedTensor, prepare_for_inference
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")


def _ax(model=1, dp=1):
    return types.SimpleNamespace(model_size=model, dp_size=dp,
                                 model="model" if model > 1 else None,
                                 dp="data" if dp > 1 else None)


def _make_qt(rng, rows, stripe_spec, k_out=0):
    """Synthetic multi-stripe QuantizedTensor (same shape family as
    tests/test_plan.py)."""
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


def _specs_by_field(pqt, specs):
    """{field_name: [spec, ...]} for a prepared unit's spec tree."""
    out = {}
    for path, spec in jax.tree_util.tree_flatten_with_path(specs)[0]:
        name = jax.tree_util.keystr(path)
        for field in ("planes", "codebook", "out_idx", "out_val",
                      "gather_idx"):
            if f".{field}" in name:
                out.setdefault(field, []).append(spec)
    return out


# ------------------------------------------------------- prepared unit rule

def test_prepared_unit_shards_planes_along_n_when_tiles_divide():
    rng = np.random.default_rng(0)
    # rows=128, bn=32 -> 4 whole (bn, bk) tiles: divides model_size=4
    pqt = prepare_for_inference(
        _make_qt(rng, 128, [(2, 80), (4, 48)], k_out=3), bn=32)
    assert pqt.n_tiles == 4 and pqt.shards_whole_tiles(4)
    specs = _specs_by_field(pqt, shd.spec_for_quantized(pqt, _ax(model=4)))
    assert specs["planes"] and all(s == P("model", None)
                                   for s in specs["planes"])
    for field in ("codebook", "out_idx", "out_val", "gather_idx"):
        assert specs[field] and all(s == P() for s in specs[field])


@pytest.mark.parametrize("rows,model", [
    (96, 4),    # 3 tiles % 4 != 0
    (128, 3),   # 4 tiles % 3 != 0
    (32, 4),    # single tile
])
def test_prepared_unit_replicates_when_tiles_do_not_divide(rows, model):
    """A non-dividing mesh must replicate the WHOLE unit — a torn group
    (planes sharded while the codebook or gather index splits elsewhere,
    or a shard holding a partial (bn, bk) tile) is never produced."""
    rng = np.random.default_rng(rows)
    pqt = prepare_for_inference(_make_qt(rng, rows, [(2, 64)], k_out=2),
                                bn=32)
    assert not pqt.shards_whole_tiles(model)
    specs = shd.spec_for_quantized(pqt, _ax(model=model))
    assert all(s == P() for s in jax.tree_util.tree_leaves(specs))


def test_prepared_unit_stacked_leaves_shard_packed_row_axis():
    """launch.quantize stacks per-layer tensors: data leaves carry a
    leading (L,) dim while plan meta stays per-matrix.  The unit rule must
    shard the packed-row axis (-2), not the stack axis."""
    rng = np.random.default_rng(7)
    qt = _make_qt(rng, 128, [(2, 64), (4, 32)], k_out=2)
    stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a, a, a]), qt)
    pqt = prepare_for_inference(stacked, bn=32)
    assert pqt.shards_whole_tiles(4)
    specs = _specs_by_field(pqt, shd.spec_for_quantized(pqt, _ax(model=4)))
    assert all(s == P(None, "model", None) for s in specs["planes"])
    for field in ("codebook", "out_idx", "out_val", "gather_idx"):
        assert all(s == P() for s in specs[field])


def test_x_idx_tables_replicate_with_the_unit():
    """The x gather index of a permuted plan indexes the ACTIVATION's K
    axis, so it replicates — both when the unit shards along N and when it
    stacks — and aligned plans (identity permutation) keep the same leaf
    structure, so the spec tree stays leaf-congruent for device_put either
    way."""
    rng = np.random.default_rng(29)
    qt = _make_qt(rng, 128, [(2, 80), (4, 48)], k_out=2)   # permuted
    for stack in (False, True):
        q = qt if not stack else jax.tree_util.tree_map(
            lambda a: jnp.stack([a, a]), qt)
        pqt = prepare_for_inference(q, bn=32)
        assert not pqt.x_gather_free
        specs = shd.spec_for_quantized(pqt, _ax(model=4))
        found = []
        for path, spec in jax.tree_util.tree_flatten_with_path(specs)[0]:
            if ".gather_idx" in jax.tree_util.keystr(path):
                found.append(spec)
        assert found and all(s == P() for s in found)
        # spec tree must mirror the unit leaf-for-leaf (device_put contract)
        assert (jax.tree_util.tree_structure(specs)
                == jax.tree_util.tree_structure(
                    jax.tree_util.tree_map(lambda _: P(), pqt)))

    # aligned plan: no gather needed, structure still congruent
    ident = QuantizedTensor(
        stripes=qt.stripes[:1],
        col_perm=jnp.arange(qt.stripes[0].n_cols, dtype=jnp.int32),
        out_idx=qt.out_idx[:, :qt.stripes[0].n_cols],
        out_val=qt.out_val[:, :qt.stripes[0].n_cols],
        out_count=qt.out_count[:qt.stripes[0].n_cols],
        shape=(128, qt.stripes[0].n_cols))
    pqt = prepare_for_inference(ident, bn=32)
    assert pqt.x_gather_free
    specs = shd.spec_for_quantized(pqt, _ax(model=4))
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(
                jax.tree_util.tree_map(lambda _: P(), pqt)))


def test_word_unaligned_bn_replicates():
    """A plan built with bn below the 32-row packing word (bn=16) has tile
    boundaries that fall mid-word for width-1 planes (3-bit high plane
    packs 32 rows/word: 96 rows -> 3 packed rows, unsplittable by 2), so
    the guard must replicate even though the tile COUNT divides — a
    sharded spec would crash device_put on the indivisible plane axis."""
    rng = np.random.default_rng(13)
    pqt = prepare_for_inference(_make_qt(rng, 96, [(3, 64)], k_out=1),
                                bn=16)
    assert pqt.n_tiles % 2 == 0 and not pqt.shards_whole_tiles(2)
    specs = shd.spec_for_quantized(pqt, _ax(model=2))
    assert all(s == P() for s in jax.tree_util.tree_leaves(specs))


def test_shards_keep_legal_launch_tiles():
    """Each shard launches the kernel over its own rows, so a split must
    leave every shard a legal launch tile short of all its rows: whole
    launch units (256 rows with a width-1 plane, else 128) or at most one
    unit.  768 rows split in two are 384 per shard — whole 128-row units
    for a 2-bit plan, but neither one nor whole 256-row units for a 3-bit
    one, which therefore replicates."""
    rng = np.random.default_rng(17)
    p2 = prepare_for_inference(_make_qt(rng, 768, [(2, 64)]), bn=128)
    p3 = prepare_for_inference(_make_qt(rng, 768, [(3, 64)]), bn=128)
    assert p2.n_tiles == p3.n_tiles == 6
    assert p2.shards_whole_tiles(2) and not p3.shards_whole_tiles(2)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        shd.spec_for_quantized(p3, _ax(model=2))))
    assert p3.shards_whole_tiles(6)          # 128 rows a shard: one tile


def test_raw_quantized_tensor_replicates_as_a_unit():
    """The pre-deployment format has no tile-clean row split (3-bit packs
    two planes concatenated along packed rows) — replicate, never tear."""
    rng = np.random.default_rng(3)
    qt = _make_qt(rng, 128, [(3, 64)], k_out=2)
    specs = shd.spec_for_quantized(qt, _ax(model=4))
    leaves = jax.tree_util.tree_leaves(specs)
    assert leaves and all(s == P() for s in leaves)


def test_spec_for_quantized_rejects_plain_arrays():
    with pytest.raises(TypeError):
        shd.spec_for_quantized(jnp.zeros((4, 4)), _ax(model=4))


def test_single_device_prepared_unit_replicates():
    rng = np.random.default_rng(5)
    pqt = prepare_for_inference(_make_qt(rng, 128, [(2, 64)]), bn=32)
    specs = shd.spec_for_quantized(pqt, _ax(model=1))
    assert all(s == P() for s in jax.tree_util.tree_leaves(specs))


# ------------------------------------------------- per-leaf rule guards

def test_spec_for_param_never_tears_quantized_internals():
    """If a caller maps the generic per-leaf rule over quantized internals
    (the pre-fix failure mode: planes sharded along K, gather_idx along
    its only axis), they replicate instead."""
    ax = _ax(model=4)
    assert shd.spec_for_param(
        "['blocks']['attn']['q']['kernel'].groups[0].planes[0]",
        (2, 8, 128), None, ax) == P()
    assert shd.spec_for_param(
        "['blocks']['mlp']['up']['kernel'].gather_idx", (256,), None,
        ax) == P()
    assert shd.spec_for_param(
        "['blocks']['attn']['k']['kernel'].stripes[0].packed", (8, 64),
        None, ax) == P()
    # dense leaves keep the generic largest-divisible-dim pick
    assert shd.spec_for_param("['embed']['embedding']", (512, 128), None,
                              ax) == P("model", None)


def test_tree_shardings_routes_units_and_stays_leaf_congruent():
    """tree_shardings expands quantized units through the unit rule and
    returns a tree with one NamedSharding per array leaf — the exact
    contract device_put needs."""
    rng = np.random.default_rng(11)
    pqt = prepare_for_inference(_make_qt(rng, 128, [(2, 64)], k_out=1),
                                bn=32)
    params = {"dense": {"kernel": jnp.zeros((16, 8))},
              "q": {"kernel": pqt}}
    mesh = make_mesh((1,), ("model",))
    sh = shd.tree_shardings(params, shd.spec_for_param_serve, None, mesh)
    p_leaves, p_def = jax.tree_util.tree_flatten(params)
    s_leaves, s_def = jax.tree_util.tree_flatten(sh)
    assert p_def == s_def
    assert len(s_leaves) == len(p_leaves)
    assert all(isinstance(s, jax.sharding.NamedSharding) for s in s_leaves)
    # and the annotated-SDS variant agrees leaf-for-leaf
    sds = jax.eval_shape(lambda: params)
    ann = shd.with_shardings(sds, shd.spec_for_param_serve, None, mesh)
    a_leaves, a_def = jax.tree_util.tree_flatten(ann)
    assert a_def == p_def
    assert [a.sharding for a in a_leaves] == s_leaves


# ------------------------------------------------------- stacked cache rule

def test_cache_rule_shards_slot_axis_not_layer_axis():
    """Engine/dry-run caches are stacked (L, B, ...): the serving-slot
    axis is axis 1.  dp must land there — a dp spec on axis 0 would shard
    LAYERS across the data-parallel axis."""
    ax = _ax(model=4, dp=2)
    # stacked KVCache.k (L, B, S, KH, D): slots over dp, KV heads over model
    assert shd.spec_for_cache(".k", (2, 8, 64, 4, 32), None, ax) == \
        P(None, "data", None, "model", None)
    # fill counters (L, B)
    assert shd.spec_for_cache(".length", (2, 8), None, ax) == P(None, "data")
    # MLA c_kv (L, B, S, d_c): rank 4 — the sequence axis must NOT take
    # the head ("model") sharding
    assert shd.spec_for_cache(".c_kv", (2, 8, 64, 32), None, ax) == \
        P(None, "data", None, None)
    # rwkv state (L, B, H, N, N): not a k/v leaf -> dp only
    assert shd.spec_for_cache(".state", (2, 8, 4, 16, 16), None, ax) == \
        P(None, "data", None, None, None)
    # encdec cross-attention banks (L_dec, B, S_src, KH, hd) are KV leaves
    assert shd.spec_for_cache(".cross_k", (2, 8, 64, 4, 32), None, ax) == \
        P(None, "data", None, "model", None)
    assert shd.spec_for_cache(".cross_v", (2, 8, 64, 4, 32), None, ax) == \
        P(None, "data", None, "model", None)


def test_cache_rule_divisibility_guards():
    ax = _ax(model=4, dp=2)
    # 3 slots % dp=2 != 0 -> replicated batch axis
    assert shd.spec_for_cache(".k", (2, 3, 64, 4, 32), None, ax) == \
        P(None, None, None, "model", None)
    # 3 KV heads % model=4 != 0 -> heads replicated
    assert shd.spec_for_cache(".k", (2, 8, 64, 3, 32), None, ax) == \
        P(None, "data", None, None, None)
