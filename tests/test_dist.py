"""Distribution tests (8 forced host devices via subprocess — the main
process keeps 1 device per the dry-run contract): row-sharded quantizer
parity, compressed DP all-reduce, small-mesh lower+compile, HLO analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.hlo_analysis import analyze_hlo, gather_instructions


def test_hlo_analyzer_counts_loops_exactly():
    def g(x):
        def inner(c, _):
            return c @ x, None
        c, _ = jax.lax.scan(inner, x, None, length=3)
        return c
    compiled = jax.jit(g).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    res = analyze_hlo(compiled.as_text())
    assert res["flops"] == 3 * 2 * 64 ** 3


def test_hlo_analyzer_nested_loops():
    def g(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ x, None
            return jax.lax.scan(inner, c, None, length=4)[0], None
        return jax.lax.scan(outer, x, None, length=5)[0]
    compiled = jax.jit(g).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    res = analyze_hlo(compiled.as_text())
    assert res["flops"] == 20 * 2 * 32 ** 3


def test_hlo_analyzer_fused_elementwise_cost():
    """Elementwise ops are charged result_elems x op-weight — including
    inside fusion bodies and multiplied by loop trip counts — under the
    separate `elementwise_flops` key (dot FLOPs stay contraction-only)."""
    def g(x):
        def inner(c, _):
            # one add (weight 1) + one exp (weight 8) per iteration,
            # each producing 32*32 elements, plus the dot
            return jnp.exp(c + x) @ x, None
        c, _ = jax.lax.scan(inner, x, None, length=3)
        return c
    compiled = jax.jit(g).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    res = analyze_hlo(compiled.as_text())
    assert res["flops"] == 3 * 2 * 32 ** 3           # unchanged by the ew term
    # the loop body's add+exp dominate: at least 3 * (1 + 8) * 32*32, and
    # bounded by a small multiple of it (XLA may add a few bookkeeping
    # elementwise ops, e.g. iota/compare on the induction variable)
    ew = res["elementwise_flops"]
    assert ew >= 3 * 9 * 32 * 32
    assert ew <= 3 * 9 * 32 * 32 + 3 * 4 * 32 * 32 + 1024


def test_gather_instruction_counter():
    """`gather_instructions` lists gather / dynamic-slice ops per kind
    with result bytes — fusion bodies included, each once, collectives
    (all-gather) NOT miscounted as gathers."""
    def g(x, idx):
        y = jnp.take(x, idx, axis=1)               # gather
        z = jax.lax.dynamic_slice(x, (0, 0), (8, 16))   # dynamic-slice
        return y.sum() + z.sum()
    compiled = jax.jit(g).lower(
        jax.ShapeDtypeStruct((8, 64), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.int32)).compile()
    got = gather_instructions(compiled.as_text())
    kinds = [k for k, _ in got]
    assert kinds.count("gather") == 1
    # the gather's result is (8, 16) f32
    assert dict(got)["gather"] == 8 * 16 * 4

    def h(x):
        return jnp.tanh(x) * 2.0                   # purely elementwise
    compiled = jax.jit(h).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    assert gather_instructions(compiled.as_text()) == []


def test_hlo_analyzer_elementwise_weights_from_text():
    """Deterministic check on hand-written HLO: weights 1 / 4 / 8 and the
    while trip-count multiplier."""
    txt = """
body (p.0: (f32[8,4], s32[])) -> (f32[8,4], s32[]) {
  p = (f32[8,4], s32[]) parameter(0)
  t = f32[8,4] get-tuple-element(%p), index=0
  iv = s32[] get-tuple-element(%p), index=1
  a = f32[8,4] add(%t, %t)
  d = f32[8,4] divide(%a, %t)
  e = f32[8,4] exponential(%d)
  one = s32[] constant(1)
  ivn = s32[] add(%iv, %one)
  ROOT r = (f32[8,4], s32[]) tuple(%e, %ivn)
}
cond (p.1: (f32[8,4], s32[])) -> pred[] {
  p = (f32[8,4], s32[]) parameter(0)
  iv = s32[] get-tuple-element(%p), index=1
  k = s32[] constant(5)
  ROOT lt = pred[] compare(%iv, %k), direction=LT
}
ENTRY main (x.0: f32[8,4]) -> f32[8,4] {
  x = f32[8,4] parameter(0)
  zero = s32[] constant(0)
  init = (f32[8,4], s32[]) tuple(%x, %zero)
  w = (f32[8,4], s32[]) while(%init), condition=%cond, body=%body
  ROOT out = f32[8,4] get-tuple-element(%w), index=0
}
"""
    res = analyze_hlo(txt)
    # per iteration: add 32 elems, divide 4*32, exponential 8*32, and the
    # scalar induction add (1); cond: compare (1) — all x trip count 5
    assert res["elementwise_flops"] == 5 * (32 + 4 * 32 + 8 * 32 + 1 + 1)
    assert res["flops"] == 0


def test_rowsharded_quantizer_matches_single_device(subproc):
    subproc("""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp, numpy as np
from repro.core import CLAQConfig, quantize_matrix
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
X = rng.normal(size=(256, 64)).astype(np.float32)
H = jnp.asarray(2 * X.T @ X)
cfg = CLAQConfig(bits=3, method="kmeans", kmeans_iters=5, gptq_blocksize=32)
qt1, Q1, st1 = quantize_matrix(W, H, cfg)
mesh = make_mesh((8,), ("model",))
qt8, Q8, st8 = quantize_matrix(W, H, cfg, mesh=mesh, shard_axis="model")
np.testing.assert_allclose(np.asarray(Q1), np.asarray(Q8), rtol=1e-4, atol=1e-5)
assert abs(st1.proxy_loss - st8.proxy_loss) / max(st1.proxy_loss, 1e-9) < 1e-3
print("rowsharded parity OK")
""")


def test_compressed_psum_error_feedback(subproc):
    subproc("""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compressed_psum
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
gs = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
err0 = jnp.zeros((8, 32), jnp.float32)

def body(g, e):
    out, new_e = compressed_psum({"g": g}, {"g": e}, "data")
    return out["g"], new_e["g"]

fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
               out_specs=(P("data"), P("data")), check_vma=False)
out, err = fn(gs, err0)
true_mean = np.asarray(gs).mean(axis=0)
# every shard holds the same compressed mean, error bounded by int8 step
got = np.asarray(out)
for i in range(8):
    assert np.allclose(got[i], got[0])
scale = np.abs(np.asarray(gs)).max() / 127
assert np.max(np.abs(got[0] - true_mean)) <= scale + 1e-6
# error feedback: residual equals what compression dropped
assert np.max(np.abs(np.asarray(err))) <= scale + 1e-6
print("compressed psum OK")
""")


def test_small_mesh_dryrun_lower_compile(subproc):
    """The dry-run path end-to-end on a 2x4 debug mesh with a smoke config:
    proves the sharding rules + constraints lower on multi-device."""
    subproc("""
from repro.launch.mesh import make_mesh
import dataclasses, jax, jax.numpy as jnp
from repro.configs import get_smoke_config, SHAPES_BY_NAME
from repro.dist import sharding as shd, context as dctx
from repro.models import api
from repro.optim import OptimConfig, OptState, init_opt_state
from repro.train import make_train_step

mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(get_smoke_config("llama1_7b"),
                          d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                          d_ff=128, vocab=256)
param_sds = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0), cfg))
params = shd.with_shardings(param_sds, shd.spec_for_param, cfg, mesh)
ocfg = OptimConfig()
opt_sds = jax.eval_shape(lambda p: init_opt_state(p, ocfg), param_sds)
opt = OptState(
    m=shd.with_shardings(opt_sds.m, shd.spec_for_param, cfg, mesh),
    v=shd.with_shardings(opt_sds.v, shd.spec_for_param, cfg, mesh),
    step=jax.ShapeDtypeStruct((), jnp.int32,
        sharding=jax.NamedSharding(mesh, jax.sharding.PartitionSpec())),
    err=None)
batch = shd.with_shardings({"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)},
                           shd.spec_for_batch, cfg, mesh)
with mesh, dctx.use_mesh(mesh):
    step = make_train_step(cfg, ocfg)
    compiled = jax.jit(step).lower(params, opt, batch).compile()
assert compiled.memory_analysis() is not None
print("small-mesh dryrun OK")
""")


def test_multi_device_train_step_runs(subproc):
    """Actually EXECUTE a sharded train step on 8 devices (not just lower)
    and check the loss matches the single-device value."""
    subproc("""
from repro.launch.mesh import make_mesh
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.dist import sharding as shd, context as dctx
from repro.models import api
from repro.optim import OptimConfig, init_opt_state
from repro.train import make_train_step
from repro.data import DataConfig, SyntheticCorpus

cfg = dataclasses.replace(get_smoke_config("llama1_7b"),
                          d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                          d_ff=128, vocab=256, dtype="float32")
params = api.init_params(jax.random.PRNGKey(0), cfg)
ocfg = OptimConfig(lr=1e-2, warmup_steps=1, total_steps=10)
opt = init_opt_state(params, ocfg)
data = SyntheticCorpus(DataConfig(vocab=256, seq_len=32, batch=8, seed=0))
batch = {"tokens": data.batch_at(0)}

step = jax.jit(make_train_step(cfg, ocfg))
_, _, m_single = step(params, opt, batch)

mesh = make_mesh((2, 4), ("data", "model"))
pshard = shd.tree_shardings(params, shd.spec_for_param, cfg, mesh)
params_d = jax.device_put(params, pshard)
opt_d = init_opt_state(params_d, ocfg)
with mesh, dctx.use_mesh(mesh):
    stepd = jax.jit(make_train_step(cfg, ocfg))
    _, _, m_multi = stepd(params_d, opt_d, batch)
assert abs(float(m_single["loss"]) - float(m_multi["loss"])) < 1e-3, (
    float(m_single["loss"]), float(m_multi["loss"]))
print("multi-device execution OK", float(m_multi["loss"]))
""", devices=8, timeout=600)
