"""Minimal functional module system: named scopes, parameter initializers,
calibration taps, and a quantization-aware dense primitive.

Models are pure functions over nested-dict params.  Two cross-cutting
concerns are threaded through module-level context:

  * **Scopes** give every dense() call a stable path name ("layers.3.attn.q").
    The same names key calibration Hessians and quantization stats.
  * **Taps**: during (eager) calibration runs, dense() streams its input
    activations into per-name Hessian accumulators (H += 2 x^T x) — the JAX
    answer to torch forward hooks, memory-light because only the (in,in)
    moment matrix is kept, and kept in host memory.
  * **Quantized dispatch**: a params leaf may be a QuantizedTensor instead of
    a dense kernel; dense() then routes through kernels.ops.qmatmul.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gptq
from repro.core.quantized import QuantizedTensor
from repro.kernels.plan import PreparedQuantizedTensor

Array = jax.Array

_STATE = threading.local()


def _scope_stack():
    if not hasattr(_STATE, "scopes"):
        _STATE.scopes = []
    return _STATE.scopes


@contextlib.contextmanager
def scope(name: str):
    _scope_stack().append(str(name))
    try:
        yield
    finally:
        _scope_stack().pop()


def current_scope() -> str:
    return ".".join(_scope_stack())


def scoped_name(name: str) -> str:
    prefix = current_scope()
    return f"{prefix}.{name}" if prefix else name


# ---------------------------------------------------------------------------
# Calibration taps
# ---------------------------------------------------------------------------

class TapCollector:
    """Streams dense() inputs into per-matrix Hessian sums held in HOST
    memory.  Each record computes 2 x^T x on the device and adds it into
    a numpy sum, so the device holds one Hessian at a time: a full-width
    model's sums outgrow the chip (qwen2_1p5b's 28 down-projection inputs
    alone are 28 x 8960^2 f32 = 9 GB).  The sums equal a device
    accumulator's up to the order of f32 rounding."""

    def __init__(self):
        self.hessians: Dict[str, gptq.HessianState] = {}

    def record(self, name: str, x: Array):
        part = gptq.accumulate_hessian(gptq.init_hessian(x.shape[-1]), x)
        H = np.array(part.H)
        st = self.hessians.get(name)
        if st is None:
            self.hessians[name] = gptq.HessianState(H, float(part.count))
        else:
            np.add(st.H, H, out=st.H)
            self.hessians[name] = st._replace(
                count=st.count + float(part.count))

    def finalized(self) -> Dict[str, np.ndarray]:
        """{name: (in, in) f32 host Hessian}, finalized in place — the
        collector empties as it goes, so host memory never holds two
        copies."""
        out = {}
        for name in list(self.hessians):
            st = self.hessians.pop(name)
            out[name] = np.divide(st.H, np.float32(max(st.count, 1.0)),
                                  out=st.H)
        return out


@contextlib.contextmanager
def collecting(collector: TapCollector):
    prev = getattr(_STATE, "collector", None)
    _STATE.collector = collector
    try:
        yield collector
    finally:
        _STATE.collector = prev


def _maybe_record(name: str, x: Array):
    col: Optional[TapCollector] = getattr(_STATE, "collector", None)
    if col is not None and not isinstance(x, jax.core.Tracer):
        col.record(name, x)


def record_expert_inputs(name: str, x_e: Array):
    """MoE calibration taps: x_e (G, E, cap, D) dispatched activations.
    One Hessian per expert (tokens routed to it) — the activation-aware
    compensation analogue for expert FFNs (DESIGN.md §3)."""
    col: Optional[TapCollector] = getattr(_STATE, "collector", None)
    if col is None or isinstance(x_e, jax.core.Tracer):
        return
    E = x_e.shape[1]
    base = scoped_name(name)
    for e in range(E):
        col.record(f"{base}_{e}", x_e[:, e].reshape(-1, x_e.shape[-1]))


# ---------------------------------------------------------------------------
# Quantized-matmul runtime mode
# ---------------------------------------------------------------------------

class QuantMode:
    """What runs quantized matmuls, read at trace time inside dense() and
    resolved by `kernels.ops.kernel_mode`: on a TPU always the compiled
    Pallas kernel; elsewhere `mode` None/'ref' = XLA dequant+dot and
    'kernel' = the Pallas kernel in interpret mode.  `act_dtype` opts
    quantized matmuls into per-token int8 activation quantization ("int8";
    None/"f32" = full precision) — an engine-level deployment knob
    (DESIGN.md §9)."""
    mode: Optional[str] = None
    act_dtype: Optional[str] = None


@contextlib.contextmanager
def quant_mode(mode: Optional[str], act_dtype: Optional[str] = None):
    prev = (QuantMode.mode, QuantMode.act_dtype)
    QuantMode.mode, QuantMode.act_dtype = mode, act_dtype
    try:
        yield
    finally:
        QuantMode.mode, QuantMode.act_dtype = prev


@contextlib.contextmanager
def activation_quant(act_dtype: Optional[str]):
    """Scope ONLY the activation quantization mode (the ServingEngine wraps
    its jitted steps with this so `mode` stays whatever the caller set)."""
    prev = QuantMode.act_dtype
    QuantMode.act_dtype = act_dtype
    try:
        yield
    finally:
        QuantMode.act_dtype = prev


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def dense(p: Dict[str, Any], x: Array, name: str = "dense") -> Array:
    """y = x @ kernel (+ bias). kernel: (in, out) array, or a
    QuantizedTensor / PreparedQuantizedTensor in paper layout (out, in).
    Prepared leaves take the fused one-launch-per-bit-width kernel path,
    per shard when a device mesh is active."""
    full = scoped_name(name)
    kernel = p["kernel"]
    if isinstance(kernel, (QuantizedTensor, PreparedQuantizedTensor)):
        from repro.dist import context as dctx
        from repro.dist import sharding as dshard
        from repro.kernels import ops as kops
        use_kernel = kops.kernel_mode(QuantMode.mode)[0]
        mesh = dctx.get_mesh() if use_kernel else None
        if (mesh is not None and mesh.size > 1
                and isinstance(kernel, PreparedQuantizedTensor)):
            y = dshard.sharded_qmatmul(x, kernel, mesh,
                                       act_dtype=QuantMode.act_dtype)
        else:
            y = kops.qmatmul(x, kernel, use_kernel=use_kernel,
                             act_dtype=QuantMode.act_dtype)
    else:
        _maybe_record(full, x)
        y = x @ kernel.astype(x.dtype)
    b = p.get("bias")
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def materialize_kernel(p: Dict[str, Any]) -> Array:
    """Kernel as a dense (in, out) array (dequantizing if quantized) — for
    paths that need explicit weight access (e.g. MLA absorbed decode)."""
    kernel = p["kernel"]
    if isinstance(kernel, (QuantizedTensor, PreparedQuantizedTensor)):
        return kernel.dequantize(jnp.bfloat16).T
    return kernel


def rms_norm(p: Dict[str, Any], x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layer_norm(p: Dict[str, Any], x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def embed(p: Dict[str, Any], tokens: Array) -> Array:
    return jnp.take(p["embedding"], tokens, axis=0)


# ---------------------------------------------------------------------------
# Initializers (host-side, explicit rngs)
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None):
    if scale is None:
        scale = in_dim ** -0.5
    k = jax.random.normal(rng, (in_dim, out_dim), dtype) * scale
    p = {"kernel": k}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def rms_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def layer_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32):
    return {"embedding": jax.random.normal(rng, (vocab, dim), dtype) * 0.02}


def split_rngs(rng, n: int):
    return list(jax.random.split(rng, n))
