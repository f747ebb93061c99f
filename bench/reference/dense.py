"""Plain reference of the dense decoder family, in float32.

A straightforward forward pass over whole sequences: no cache, no kernel,
no batching tricks, at `jax.default_matmul_precision("highest")` so that a
float32 matmul on a TPU is not run in a lower precision.  Weights are the
benchmark's own draws (`bench.weights.raw_*`), dequantized here from codes,
codebooks and reserved outliers; nothing is taken from the program.

The block follows the dense family as the served configurations state it:
RMSNorm, GQA attention with QKV bias, an RMSNorm of each q and k head
where the file states `qk_norm` (Qwen3), and RoPE over the first
`rotary_dim` dims of each head (dims paired as (2i, 2i+1), the program's
convention), SwiGLU MLP, residuals, a final RMSNorm and an untied or tied
head.
The layers run one at a time, each drawing its weights afresh, so that the
reference fits beside nothing else on the chip.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import weights as W

Q_BLOCK = 512     # query rows per attention block: bounds the score buffer


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, rot: int, theta: float):
    """x (N, S, heads, hd): rotate dims [0, rot) in (2i, 2i+1) pairs."""
    S = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, rot/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return jnp.concatenate([y.reshape(x[..., :rot].shape), x[..., rot:]], -1)


def _attention(q, k, v):
    """Causal softmax attention; q (N, S, H, hd), k/v (N, S, KH, hd)."""
    N, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)                   # head h reads kv h // G
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK] * hd ** -0.5
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k)
        qpos = q0 + jnp.arange(qb.shape[1])
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnums=(3,))
def _block(h, mats, leaves, mc_items):
    mc = dict(mc_items)
    N, S, d = h.shape
    H, KH, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    eps = mc["rms_norm_eps"]

    def lin(x, name):
        y = x @ mats[name].T
        b = leaves.get(f"{name}.bias")
        return y if b is None else y + b

    x = _rms(h, leaves["ln1.scale"], eps)
    q = lin(x, "attn.q").reshape(N, S, H, hd)
    k = lin(x, "attn.k").reshape(N, S, KH, hd)
    v = lin(x, "attn.v").reshape(N, S, KH, hd)
    if mc.get("qk_norm"):
        q = _rms(q, leaves["attn.q_norm.scale"], eps)
        k = _rms(k, leaves["attn.k_norm.scale"], eps)
    rot = mc["rotary_dim"] or hd
    q, k = _rope(q, rot, mc["rope_theta"]), _rope(k, rot, mc["rope_theta"])
    h = h + lin(_attention(q, k, v).reshape(N, S, H * hd), "attn.o")
    x = _rms(h, leaves["ln2.scale"], eps)
    m = jax.nn.silu(lin(x, "mlp.gate")) * lin(x, "mlp.up")
    return h + lin(m, "mlp.down")


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_mats(key, lays):
    """The layer's quantized matrices, dequantized to float32."""
    return {name: W.dequantize(W.raw_matrix(W.matrix_key(key[0], key[1], i),
                                            lay))
            for i, (name, lay) in enumerate(lays)}


@functools.partial(jax.jit, static_argnums=(4,))
def _head(h, idx, final_scale, head, eps):
    """Logits (N, P, V) at positions `idx` (N, P) only."""
    x = jnp.take_along_axis(h, idx[..., None], axis=1)
    return _rms(x, final_scale, eps) @ head


_MC_KEYS = W.MODEL_KEYS + ("rotary_dim", "rope_theta", "rms_norm_eps")


def logits(conf: Dict, seed: int, tokens, positions):
    """Reference logits of a seeded model.

    tokens (N, S) int32 — sequences padded at the end (causal attention
    keeps padding out of every earlier position); positions (N, P) int32 —
    where to read the next-token logits.  Returns (N, P, V) float32.
    """
    mc = {k: conf[k] for k in _MC_KEYS if k in conf}
    key = W.base_key(seed)
    L = mc["num_hidden_layers"]
    specs = W.dense_leaf_specs(mc, jnp.bfloat16)

    def leaf(name):
        shape, dt = specs[name]
        return W.raw_dense_leaf(key, name, shape, dt).astype(jnp.float32)

    lays = tuple((name, W.layout(rows, cols, conf["claq"]))
                 for name, (rows, cols) in W.matrix_shapes(mc).items())
    stacked = {name[len("blocks."):]: leaf(name) for name in specs
               if name.startswith("blocks.")}
    with jax.default_matmul_precision("highest"):
        h = jnp.take(leaf("embed.embedding"), jnp.asarray(tokens), axis=0)
        for layer in range(L):
            mats = _layer_mats((key, jnp.int32(layer)), lays)
            leaves = {n: a[layer] for n, a in stacked.items()}
            h = _block(h, mats, leaves, tuple(sorted(mc.items())))
            del mats
        head = (leaf("embed.embedding").T if mc["tie_word_embeddings"]
                else leaf("lm_head.kernel"))
        out = _head(h, jnp.asarray(positions), leaf("final_norm.scale"),
                    head, mc["rms_norm_eps"])
    return out
