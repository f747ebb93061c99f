"""Seeded CLAQ-layout weights, made on the device from `--seed`.

Two layers of code, kept apart on purpose:

* `raw_*` functions draw what a CLAQ checkpoint holds, in plain arrays:
  per-column codes, sorted codebooks, the Adaptive Precision (AP) column
  bit-widths and the Outlier Reservation (OR) rows and values, plus the
  bf16 leaves CLAQ leaves unquantized (embedding, norms, biases, head).
  They import nothing of the program; the plain reference
  (`bench/reference/dense.py`) dequantizes from them.
* `served_params` assembles the same draws into the program's storage
  format (`repro.core.quantized.QuantizedTensor`, stacked over layers as
  `launch.quantize` stacks them) in one jitted call: on the default
  device, or, for a configuration that states a mesh, spread over its
  chips so that each builds only its share (`_build_sharded`).

Nothing calibrates and nothing runs GPTQ: a served step's cost depends on
the layout (bit-widths, stripes, outlier planes), not on how the codes were
found.  Every layer of one matrix kind has the same number of columns at
each width and the same outlier counts, so the stack stays one pytree.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# storage cost of one reserved outlier (16-bit value + 16-bit row), the
# paper's Eq. 5 accounting
BITS_PER_OUTLIER = 32.0


def base_key(seed: int) -> jax.Array:
    """A key from any whole-number seed: both 32-bit halves are folded in
    (PRNGKey alone wraps seeds past 32 bits)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def matrix_shapes(mc: Dict) -> Dict[str, Tuple[int, int]]:
    """(rows, cols) of each quantized matrix of one block."""
    H, KH = mc["num_attention_heads"], mc["num_key_value_heads"]
    hd, d, f = mc["head_dim"], mc["hidden_size"], mc["intermediate_size"]
    return {"attn.q": (H * hd, d), "attn.k": (KH * hd, d),
            "attn.v": (KH * hd, d), "attn.o": (d, H * hd),
            "mlp.gate": (f, d), "mlp.up": (f, d), "mlp.down": (d, f)}


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static CLAQ layout of one matrix: AP column counts (Eq. 4) and OR
    per-column outlier counts (Eq. 5) for a two-level AP + OR setting."""
    rows: int
    cols: int
    p_lo: int
    p_hi: int
    n_hi: int        # columns at p_hi bits
    n_top: int       # OR's most sensitive columns
    k_top: int       # outliers kept per top column
    k_rest: int      # outliers kept per other column

    @property
    def k_max(self) -> int:
        return max(self.k_top, self.k_rest)


def layout(rows: int, cols: int, claq: Dict) -> Layout:
    t, lo, hi = claq["target_bits"], claq["p_lo"], claq["p_hi"]
    n_hi = int(round(cols * (t - lo) / (hi - lo)))
    total = claq["or_extra_bits"] * rows * cols / BITS_PER_OUTLIER
    n_top = max(int(round(claq["or_top_frac"] * cols)), 1)
    n_rest = cols - n_top
    k_top = min(int(round(claq["or_o1"] * total / n_top)), rows)
    k_rest = (min(int(round(claq["or_o2"] * total / n_rest)), rows)
              if n_rest else 0)
    return Layout(rows, cols, lo, hi, n_hi, n_top, k_top, k_rest)


def matrix_key(key: jax.Array, layer, m: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, layer), 1000 + m)


def raw_matrix(key: jax.Array, lay: Layout) -> Dict[str, jax.Array]:
    """One matrix's CLAQ contents drawn from `key` (jit-able: every shape
    is static).  The hi-precision and top-OR columns are random; codes are
    uniform over each column's levels; codebooks are sorted normals of std
    cols**-0.5; outliers sit on distinct rows of their column."""
    rows, cols = lay.rows, lay.cols
    k = jax.random.split(key, 6)
    hi_cols = jnp.sort(jax.random.permutation(k[0], cols)[:lay.n_hi])
    is_hi = jnp.zeros((cols,), bool).at[hi_cols].set(True)
    bits = jnp.where(is_hi, lay.p_hi, lay.p_lo).astype(jnp.int32)
    codes = jax.random.randint(k[1], (rows, cols), 0,
                               (1 << bits)[None, :]).astype(jnp.uint8)
    sigma = cols ** -0.5
    draw = jax.random.normal(k[2], (cols, 1 << lay.p_hi), jnp.float32)
    n_lo_levels = 1 << lay.p_lo
    lo_cb = jnp.sort(draw[:, :n_lo_levels], axis=1)
    lo_cb = jnp.pad(lo_cb, ((0, 0), (0, draw.shape[1] - n_lo_levels)),
                    constant_values=jnp.inf)
    codebook = jnp.where(is_hi[:, None], jnp.sort(draw, axis=1),
                         lo_cb) * sigma
    top = jax.random.permutation(k[3], cols)[:lay.n_top]
    is_top = jnp.zeros((cols,), bool).at[top].set(True)
    counts = jnp.where(is_top, lay.k_top, lay.k_rest).astype(jnp.int32)
    km = lay.k_max
    step = max(rows // max(km, 1), 1)
    off = jax.random.randint(k[4], (cols,), 0, rows)
    out_rows = jnp.sort((off[None, :] + step * jnp.arange(km)[:, None])
                        % rows, axis=0).astype(jnp.int32)
    out_val = 4.0 * sigma * jax.random.normal(k[5], (km, cols), jnp.float32)
    return {"codes": codes, "bits": bits, "codebook": codebook,
            "hi_cols": hi_cols, "counts": counts, "out_rows": out_rows,
            "out_val": out_val}


def dequantize(raw: Dict[str, jax.Array]) -> jax.Array:
    """(rows, cols) f32 matrix a raw draw stands for: each code's centroid,
    overridden by the reserved outliers."""
    codes = raw["codes"]
    cb = raw["codebook"]
    # a select per level: a gather from the (levels, cols) table is slow
    # on a TPU and would make the reference longer than the window
    W = jnp.zeros(codes.shape, jnp.float32)
    for level in range(cb.shape[1]):
        W = jnp.where(codes == level, cb[None, :, level], W)
    km, cols = raw["out_rows"].shape
    if km:
        valid = jnp.arange(km)[:, None] < raw["counts"][None, :]
        rows = W.shape[0]
        r = jnp.where(valid, raw["out_rows"], rows)     # invalid -> dropped
        c = jnp.broadcast_to(jnp.arange(cols)[None, :], r.shape)
        W = W.at[r, c].set(raw["out_val"], mode="drop")
    return W


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def raw_dense_leaf(key: jax.Array, name: str, shape, dtype) -> jax.Array:
    """An unquantized leaf by its dotted name, in the dtype it is served
    in: norm scales near 1, biases small, embedding and head normal."""
    k = _leaf_key(key, name)
    x = jax.random.normal(k, shape, jnp.float32)
    if name.endswith(".scale"):
        x = 1.0 + 0.1 * x
    elif name.endswith(".bias"):
        x = 0.1 * x
    elif name == "embed.embedding":
        x = 0.02 * x
    else:                                   # lm_head.kernel (in, out)
        x = x * shape[-2] ** -0.5
    return x.astype(dtype)


def dense_leaf_specs(mc: Dict, dtype) -> Dict[str, Tuple[tuple, object]]:
    """Name -> (shape, dtype) of every leaf CLAQ leaves unquantized, in
    the dense family's params tree (stacked block leaves lead with L)."""
    L, d, V = mc["num_hidden_layers"], mc["hidden_size"], mc["vocab_size"]
    shapes = matrix_shapes(mc)
    out = {"embed.embedding": ((V, d), dtype),
           "blocks.ln1.scale": ((L, d), jnp.float32),
           "blocks.ln2.scale": ((L, d), jnp.float32),
           "final_norm.scale": ((d,), jnp.float32)}
    if mc.get("qk_norm"):
        # RMSNorm of each q and k head before RoPE (Qwen3), held in the
        # served dtype as the program's attention block holds it
        for m in ("q_norm", "k_norm"):
            out[f"blocks.attn.{m}.scale"] = ((L, mc["head_dim"]), dtype)
    if mc["attention_bias"]:
        for m in ("attn.q", "attn.k", "attn.v"):
            out[f"blocks.{m}.bias"] = ((L, shapes[m][0]), dtype)
    if not mc["tie_word_embeddings"]:
        out["lm_head.kernel"] = ((d, V), dtype)
    return out


# ---------------------------------------------------------------------------
# the program's storage format
# ---------------------------------------------------------------------------

def _assemble(raw: Dict[str, jax.Array], lay: Layout):
    """A raw draw in `QuantizedTensor` layout (what
    `core.quantized.build_quantized_tensor` makes from the same codes),
    built with jit-able ops: stripes in ascending bit-width, columns in
    ascending index inside each stripe, outliers in original column
    order."""
    from repro.core import packing
    from repro.core.quantized import QuantizedTensor, QuantStripe

    cols = lay.cols
    is_hi = jnp.zeros((cols,), bool).at[raw["hi_cols"]].set(True)
    lo_cols = jnp.sort(jnp.where(is_hi, cols, jnp.arange(cols)))
    lo_cols = lo_cols[:cols - lay.n_hi]
    stripes = []
    for idx, b in ((lo_cols, lay.p_lo), (raw["hi_cols"], lay.p_hi)):
        if idx.shape[0] == 0:
            continue
        sub = jnp.take(raw["codes"], idx, axis=1).astype(jnp.int32)
        cb = jnp.take(raw["codebook"], idx, axis=0)[:, :1 << b]
        stripes.append(QuantStripe(packed=packing.pack_codes(sub, b),
                                   codebook=cb.astype(jnp.float32), bits=b))
    col_perm = jnp.concatenate([lo_cols, raw["hi_cols"]]).astype(jnp.int32)
    return QuantizedTensor(stripes=tuple(stripes), col_perm=col_perm,
                           out_idx=raw["out_rows"],
                           out_val=raw["out_val"].astype(jnp.float32),
                           out_count=raw["counts"],
                           shape=(lay.rows, lay.cols))


def quantized_matrix(key: jax.Array, lay: Layout):
    """One matrix in the program's format (for tests and single calls)."""
    return _assemble(raw_matrix(key, lay), lay)


def _set(tree: Dict, dotted: str, value) -> None:
    parts = dotted.split(".")
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = value


def _stacks(key, mc: Dict, claq: Dict, layers: jax.Array) -> Dict:
    """Each quantized matrix kind stacked over `layers`, one layer at a
    time, every layer from its own key (`matrix_key`)."""
    out = {}
    for m_i, (name, (rows, cols)) in enumerate(matrix_shapes(mc).items()):
        lay = layout(rows, cols, claq)
        out[name] = jax.lax.map(
            lambda l, m_i=m_i, lay=lay: _assemble(
                raw_matrix(matrix_key(key, l, m_i), lay), lay),
            layers)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _build(key, mc_items: tuple, claq_items: tuple):
    mc, claq = dict(mc_items), dict(claq_items)
    params: Dict = {}
    for name, (shape, dt) in dense_leaf_specs(mc, jnp.bfloat16).items():
        _set(params, name, raw_dense_leaf(key, name, shape, dt))
    stacks = _stacks(key, mc, claq, jnp.arange(mc["num_hidden_layers"]))
    for name, qt in stacks.items():
        _set(params, f"blocks.{name}.kernel", qt)
    return params


def dense_leaf_spec(name: str, ndim: int, axes) -> P:
    """How a mesh splits an unquantized leaf over all its chips: the
    embedding and the head by vocabulary rows, every other leaf along its
    first axis (the layer axis of a stacked block leaf)."""
    if name == "lm_head.kernel":
        return P(None, axes)
    return P(axes, *[None] * (ndim - 1))


def _check_split(mc: Dict, n: int) -> None:
    sizes = {"num_hidden_layers": mc["num_hidden_layers"],
             "vocab_size": mc["vocab_size"],
             "hidden_size": mc["hidden_size"]}
    uneven = {k: v for k, v in sizes.items() if v % n}
    if uneven:
        raise ValueError(f"weights split over {n} chips need these sizes "
                         f"to divide by {n}: {uneven}")


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _build_sharded(key, mc_items: tuple, claq_items: tuple, mesh):
    """`_build`'s tree, value for value, spread over every chip of `mesh`:
    each chip draws its own run of layers of every quantized stack (the
    same per-layer keys, one layer at a time), and its slice of each
    unquantized leaf (`dense_leaf_spec`; the counter-based generator
    gives a slice the values of the whole draw).  No chip holds more
    than its share; the engine lays the tree out as it serves it."""
    mc, claq = dict(mc_items), dict(claq_items)
    axes = tuple(mesh.axis_names)
    n = mesh.size
    _check_split(mc, n)
    params: Dict = {}
    for name, (shape, dt) in dense_leaf_specs(mc, jnp.bfloat16).items():
        spec = dense_leaf_spec(name, len(shape), axes)
        _set(params, name, jax.lax.with_sharding_constraint(
            raw_dense_leaf(key, name, shape, dt), NamedSharding(mesh, spec)))
    per_chip = mc["num_hidden_layers"] // n

    def local(key):
        first = jax.lax.axis_index(axes) * per_chip
        return _stacks(key, mc, claq, first + jnp.arange(per_chip))

    stacks = jax.shard_map(local, mesh=mesh, in_specs=P(),
                           out_specs=P(axes))(key)
    for name, qt in stacks.items():
        _set(params, f"blocks.{name}.kernel", qt)
    return params


# the keys of a configuration file the weights depend on (`qk_norm` only
# where the file states it)
MODEL_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "attention_bias", "tie_word_embeddings",
              "qk_norm")


def model_items(conf: Dict) -> tuple:
    """The configuration file's values the weights depend on, as a static
    argument of the builders."""
    return tuple((k, conf[k]) for k in MODEL_KEYS if k in conf)


def served_params(seed: int, conf: Dict, mesh=None):
    """The whole served params tree for `seed` and a configuration file's
    contents, in one jitted call (the key is an operand, so every seed
    reuses one compiled program): on the default device, or with `mesh`
    spread over its chips, each building only its share."""
    mc, claq = model_items(conf), tuple(sorted(conf["claq"].items()))
    if mesh is None:
        return _build(base_key(seed), mc, claq)
    return _build_sharded(base_key(seed), mc, claq, mesh)
