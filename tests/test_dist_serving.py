"""Multi-device serving (8 forced host devices via subprocess): the
mesh-wired ServingEngine on a 2x4 (data x model) mesh must emit tokens
bit-identical to the single-device engine on an AP+OR-quantized model
with bucketed admission, and the compiled decode step must stay
weight-resident per shard — no all-gather of a weight-sized operand
(hlo_analysis.collective_instructions).

The PreparedQuantizedTensor units shard along N in whole (bn, bk) tiles
(plan_bn=32 so the smoke model's 128/256-row matrices split over
model=4); parity holds bitwise because N/dp sharding never splits a
contraction — each shard dequantizes and contracts its own rows.
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.serve import ServingEngine
from repro.models import api
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh

jax.config.update("jax_platform_name", "cpu")


def test_trivial_mesh_engine_matches_no_mesh():
    """The mesh wiring (device_put + mesh-scoped jits) is exercised
    in-process on a 1x1 mesh: must behave exactly like mesh=None."""
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=64,
                              n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng0 = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8)
    engm = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8,
                         mesh=mesh)
    for eng in (eng0, engm):
        eng.add_requests([[1, 2, 3], [5, 6, 7, 8, 9]], max_new_tokens=4)
        eng.run_to_completion()
    t0 = [r.tokens for r in eng0.take_finished().values()]
    tm = [r.tokens for r in engm.take_finished().values()]
    assert t0 == tm
    assert engm.stats()["mesh"] == {"data": 1, "model": 1}


def test_mesh_preempt_resume_parity_and_cache_pinning():
    """Preemption on a mesh-wired engine: the jitted slot clear and the
    resume insert must leave the sharded cache PINNED to the engine's
    NamedShardings (no placement drift into the decode jit), and resumed
    requests must emit tokens bit-identical to the no-mesh engine."""
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=64,
                              n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9]]
    eng0 = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8)
    uids0 = eng0.add_requests(prompts, max_new_tokens=8)
    eng0.run_to_completion()
    fin0 = eng0.take_finished()
    base = [fin0[u].tokens for u in uids0]

    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8,
                        mesh=mesh)
    uids = eng.add_requests(prompts, max_new_tokens=8)
    for _ in range(3):
        eng.step()
    eng.set_cache_pressure(4)                   # below both fills: preempt
    eng.step()
    assert eng.stats()["preemptions"] == 2 and not eng.active

    def assert_pinned():
        flat = jax.tree_util.tree_flatten_with_path(eng.cache)[0]
        want = jax.tree_util.tree_flatten_with_path(eng._cache_shardings)[0]
        for (p, leaf), (_, sh) in zip(flat, want):
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), p

    assert_pinned()                             # after the jitted clear
    eng.set_cache_pressure(None)
    eng.step()                                  # resume both
    assert eng.stats()["resumes"] == 2
    assert_pinned()                             # after the resume insert
    eng.run_to_completion()
    fin = eng.take_finished()
    assert [fin[u].tokens for u in uids] == base
    assert all(fin[u].preemptions == 1 for u in uids)


def test_sharded_speculative_token_parity(subproc):
    """Self-speculative decoding on a 2x4 mesh: the draft/target pair
    (quantized from ONE calibration pass) served with propose/verify/
    rollback windows must emit tokens bit-identical to the single-device
    VANILLA engine — losslessness and shard-parity composed.  Spec trace
    counters stay constant (one draft decode, one verify) and the draft's
    prepared plans shard over "model" like the target's."""
    subproc("""
from repro.launch.mesh import make_mesh
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import APConfig, CLAQConfig, ORConfig
from repro.data import calibration_set
from repro.launch.quantize import claq_quantize_with_draft
from repro.models import api
from repro.serve import ServingEngine, SpecConfig
from repro.kernels.plan import PreparedQuantizedTensor

cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                          n_layers=2)
params = api.init_params(jax.random.PRNGKey(0), cfg)
qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=4, gptq_blocksize=32,
                  ap=APConfig(2.2, 2, 4), orr=ORConfig(0.1))
calib = calibration_set(vocab=cfg.vocab, n_segments=4, seq_len=32)
(qparams, rep), (dparams, drep) = claq_quantize_with_draft(
    params, cfg, calib, qcfg, draft_bits=2)
assert drep.mean_effective_bits < rep.mean_effective_bits

def serve(eng, prompts, max_new=6):
    uids = eng.add_requests(prompts, max_new_tokens=max_new)
    eng.run_to_completion()
    fin = eng.take_finished()
    return [fin[u].tokens for u in uids]

wave1 = [[1, 2, 3], [4, 5, 6, 7, 8, 9], [10, 11, 12, 13, 14, 15, 16, 17, 18],
         [20, 21]]
wave2 = [[7, 7, 7, 7, 7], [9, 8, 7]]

# ground truth: single-device VANILLA greedy decode
eng0 = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8,
                     plan_bn=32)
t0 = serve(eng0, wave1) + serve(eng0, wave2)

mesh = make_mesh((2, 4), ("data", "model"))
for gamma in (2, 4):
    eng = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8,
                        plan_bn=32, mesh=mesh, draft_params=dparams,
                        spec=SpecConfig(gamma=gamma, draft_bits=2))
    t = serve(eng, wave1) + serve(eng, wave2)
    assert t == t0, (gamma, t, t0)
    st = eng.stats()
    assert st["verify_traces"] == 1 and st["draft_decode_traces"] == 1
    assert st["decode_traces"] == 0
    print(f"gamma={gamma} sharded spec parity OK, acceptance "
          f"{st['acceptance_rate']:.2f}, {st['tokens_per_step']:.2f} tok/step")

# the draft's prepared units shard over model=4 like the target's
n_sharded = 0
def visit(leaf):
    global n_sharded
    if isinstance(leaf, PreparedQuantizedTensor) and leaf.shards_whole_tiles(4):
        n_sharded += 1
jax.tree_util.tree_map(
    visit, eng.draft_params,
    is_leaf=lambda l: isinstance(l, PreparedQuantizedTensor))
assert n_sharded > 0, "no draft unit sharded -> draft replicated everywhere"
print("draft sharded units:", n_sharded)
""", devices=8, timeout=1200)


def test_mesh_paged_decode_parity(subproc):
    """Paged KV cache on a 2x4 (data x model) mesh: pool pages shard over
    "data" (and kp/vp heads over "model"), tables replicate, and decode
    tokens stay bit-identical to the single-device CONTIGUOUS engine —
    the paged-parity claim and the shard-parity claim composed.  Also
    exercises preempt/resume on the mesh so the jitted slot clear, the
    host-side page release, and the batch-1 replay reinsert all run with
    sharded pool leaves."""
    subproc("""
from repro.launch.mesh import make_mesh
import dataclasses
import jax
from repro.configs import get_smoke_config
from repro.models import api
from repro.serve import ServingEngine

cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                          n_layers=2)
params = api.init_params(jax.random.PRNGKey(0), cfg)

def serve(eng, prompts, max_new=6):
    uids = eng.add_requests(prompts, max_new_tokens=max_new)
    eng.run_to_completion()
    fin = eng.take_finished()
    return [fin[u].tokens for u in uids]

wave1 = [[1, 2, 3], [4, 5, 6, 7, 8, 9], [10, 11, 12, 13, 14, 15, 16, 17, 18],
         [20, 21]]
wave2 = [[7, 7, 7, 7, 7], [9, 8, 7]]          # slot + page reuse

# ground truth: single-device CONTIGUOUS fp engine
eng0 = ServingEngine(params, cfg, n_slots=4, max_len=64, min_bucket=8,
                     prepare=False)
t0 = serve(eng0, wave1) + serve(eng0, wave2)

# kv_pages=31 -> pool leaves carry 32 page rows (31 + scratch), which the
# data=2 axis splits evenly; default capacity (4*64/8=32 pages -> 33 rows)
# would not.
mesh = make_mesh((2, 4), ("data", "model"))
paged_kw = dict(kv_layout="paged", page_size=8, kv_pages=31)
eng = ServingEngine(params, cfg, n_slots=4, max_len=64, min_bucket=8,
                    prepare=False, mesh=mesh, **paged_kw)
t = serve(eng, wave1) + serve(eng, wave2)
assert t == t0, (t, t0)

# the engine's declared placement: page axis over "data", kp/vp heads
# over "model"; the page tables replicate (any slot may name any page).
# Checked on _cache_shardings, the pins every insert restores — live
# leaves may carry whatever output sharding the decode jit propagated.
flat = jax.tree_util.tree_flatten_with_path(eng._cache_shardings)[0]
specs = {jax.tree_util.keystr(p): tuple(sh.spec) for p, sh in flat}
kp = [s for p, s in specs.items() if p.endswith(".kp")]
assert kp and all(s[1] == "data" and "model" in s for s in kp), specs
tables = [s for p, s in specs.items() if p.endswith(".table")]
assert tables and all(all(e is None for e in s) for s in tables), specs

# preempt/resume with sharded pages: release + replay stays bitwise
eng2 = ServingEngine(params, cfg, n_slots=4, max_len=64, min_bucket=8,
                     prepare=False, mesh=mesh, **paged_kw)
uids = eng2.add_requests(wave1, max_new_tokens=6)
for _ in range(2):
    eng2.step()
eng2.set_cache_pressure(4)          # every fill >= 4 now -> all preempt
eng2.step()
assert eng2.stats()["preemptions"] == 4 and not eng2.active
assert not eng2._req_pages          # preemption released every page
eng2.set_cache_pressure(None)
eng2.run_to_completion()
fin = eng2.take_finished()
assert [fin[u].tokens for u in uids] == t0[:4]
assert eng2.stats()["resumes"] == 4
print("mesh paged parity OK: tokens bitwise, pages sharded over data,"
      " 4 preempted/resumed")
""", devices=8, timeout=900)


def test_sharded_engine_token_parity_and_weight_residency(subproc):
    subproc("""
from repro.launch.mesh import make_mesh
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import APConfig, CLAQConfig, ORConfig
from repro.data import calibration_set
from repro.launch.quantize import claq_quantize
from repro.models import api
from repro.serve import ServingEngine
from repro.analysis import REGISTRY, run_rules
from repro.analysis.artifacts import weight_shard_threshold
from repro.dist.hlo_analysis import analyze_hlo

# --- AP+OR-quantized smoke model (the paper's deployment format) --------
cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                          n_layers=2)
params = api.init_params(jax.random.PRNGKey(0), cfg)
qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=4, gptq_blocksize=32,
                  ap=APConfig(2.2, 2, 4), orr=ORConfig(0.1))
calib = calibration_set(vocab=cfg.vocab, n_segments=4, seq_len=32)
qparams, report = claq_quantize(params, cfg, calib, qcfg)
assert 2.0 < report.mean_effective_bits < 2.6

def serve(eng, prompts, max_new=6):
    uids = eng.add_requests(prompts, max_new_tokens=max_new)
    eng.run_to_completion()
    fin = eng.take_finished()
    return [fin[u].tokens for u in uids]

# bucketed admission: lengths spanning several power-of-2 buckets
wave1 = [[1, 2, 3], [4, 5, 6, 7, 8, 9], [10, 11, 12, 13, 14, 15, 16, 17, 18],
         [20, 21]]
wave2 = [[7, 7, 7, 7, 7], [9, 8, 7]]          # slot reuse after retirement

# plan_bn=32: the smoke model's 128/256-row matrices split into 4/8 whole
# (bn, bk) tiles -> every quantized unit shards over model=4
eng1 = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8,
                     plan_bn=32)
t1 = serve(eng1, wave1) + serve(eng1, wave2)

mesh = make_mesh((2, 4), ("data", "model"))
eng2 = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8,
                     plan_bn=32, mesh=mesh)
t2 = serve(eng2, wave1) + serve(eng2, wave2)

assert t1 == t2, (t1, t2)                      # bit-identical greedy tokens
assert all(len(t) == 6 for t in t1)
assert eng2.bucketing.enabled and eng2.prefill_traces >= 1

# --- decode stays weight-resident per shard -----------------------------
# threshold = largest sharded quantized plane; computed by the shared
# helper the HLO-AG1 contract rule uses (repro.analysis.artifacts)
threshold = weight_shard_threshold(eng2.params, model_parts=4)
assert threshold, "no quantized unit sharded -> vacuous check"

# --- preemption on the real 2x4 mesh: the jitted slot clear and the ----
# --- batch-1 resume replay must preserve bitwise token parity ----------
eng3 = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8,
                     plan_bn=32, mesh=mesh)
uids3 = eng3.add_requests(wave1, max_new_tokens=6)
for _ in range(2):
    eng3.step()
eng3.set_cache_pressure(4)          # every fill >= 4 now -> all preempt
eng3.step()
st3 = eng3.stats()
assert st3["preemptions"] == 4 and not eng3.active, st3["preemptions"]
eng3.set_cache_pressure(None)
eng3.run_to_completion()
fin3 = eng3.take_finished()
t3 = [fin3[u].tokens for u in uids3]
assert t3 == t1[:4], (t3, t1[:4])
assert eng3.stats()["resumes"] == 4
print("mesh preemption parity OK: 4 preempted, 4 resumed, bitwise tokens")

txt = eng2.lower_decode().compile().as_text()
res = analyze_hlo(txt)
assert res["flops"] > 0                        # the analyzer parsed the module
rep = run_rules([REGISTRY["HLO-AG1"], REGISTRY["HLO-CB1"]],
                {"hlo": {"decode": txt}, "weight_shard_bytes": threshold})
assert rep.rules_run == ["HLO-AG1", "HLO-CB1"] and not rep.findings, (
    rep.render())
print("dist serving parity OK: decode clean under HLO-AG1/HLO-CB1,"
      " weight-shard threshold", threshold, "B")
""", devices=8, timeout=900)


def test_sharded_kernel_matches_single_device_bitwise(subproc):
    """The kernel's mesh path (dist.sharding.sharded_qmatmul: per-shard
    Pallas launches under shard_map, outlier row ids shifted per shard)
    is BITWISE the single-device fused kernel, interpreted, on a 2x2
    (data x model) mesh — for an AP+OR tensor whose outliers fall on both
    model shards, with f32 and int8 activations, 2-D and 3-D x."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import APConfig, CLAQConfig, ORConfig, quantize_matrix
from repro.dist.sharding import sharded_qmatmul
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import modules as nn

rng = np.random.default_rng(0)
rows, cols = 128, 160
W = rng.normal(size=(rows, cols)).astype(np.float32)
W[:, :12] += rng.standard_t(df=2, size=(rows, 12)) * 4
X = rng.normal(size=(256, cols)).astype(np.float32)
qt, _, _ = quantize_matrix(jnp.asarray(W), jnp.asarray(2 * X.T @ X),
                           CLAQConfig(bits=2, method="kmeans",
                                      kmeans_iters=4, gptq_blocksize=32,
                                      ap=APConfig(2.5, 2, 4),
                                      orr=ORConfig(0.15)))
pqt = qt.prepare(bn=32)              # 4 tiles of 32 rows: 2 per model shard
assert len(pqt.groups) == 2 and not pqt.x_gather_free
ids = np.concatenate([np.asarray(g.out_idx).ravel() for g in pqt.groups
                      if g.out_idx is not None])
ids = ids[ids >= 0]
assert (ids < rows // 2).any() and (ids >= rows // 2).any(), "one shard"

mesh = make_mesh((2, 2), ("data", "model"))
assert pqt.shards_whole_tiles(2)
with nn.quant_mode("kernel"):
    for shape in ((4, cols), (2, 3, cols)):
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        for act in (None, "int8"):
            one = jax.jit(lambda a, p: ops.prepared_qmatmul(
                a, p, act_dtype=act))(x, pqt)
            with mesh:
                got = jax.jit(lambda a, p: sharded_qmatmul(
                    a, p, mesh, act_dtype=act))(x, pqt)
            assert got.shape == one.shape, (got.shape, one.shape)
            assert np.array_equal(np.asarray(got), np.asarray(one)), (
                shape, act, float(jnp.max(jnp.abs(got - one))))
print("sharded kernel bitwise OK")
""", devices=4, timeout=600)
