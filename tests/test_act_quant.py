"""Opt-in int8 activation quantization + the gather-free decode hot path
(DESIGN.md §9): per-token absmax quantization units, the documented error
bound against the f32 path across bit-widths and gather modes, engine
wiring of ``act_dtype``, and the HLO-level claim the tentpole is about —
a kernel-mode decode step over integer-bit CLAQ plans compiles to the
SAME number of gather instructions as the dense model's decode step (the
quantized matmul path contributes zero; shared rule `HLO-GA1` from
`repro.analysis`)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import CLAQConfig
from repro.data import calibration_set
from repro.kernels import ops, ref as ref_lib
from repro.kernels.plan import prepare_for_inference
from repro.launch.quantize import claq_quantize
from repro.models import api
from repro.models import modules as nn
from repro.serve import ServingEngine, SpecConfig

from test_plan import _make_qt

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- units

def test_quantize_activations_units():
    x = jnp.asarray([[0.5, -2.0, 1.0, 0.0],
                     [0.0, 0.0, 0.0, 0.0],       # all-zero row: scale 1
                     [127.0, -127.0, 3.0, -3.0]], jnp.float32)
    xq, scale = ops.quantize_activations(x)
    assert xq.dtype == jnp.int8 and scale.shape == (3, 1)
    # the row max always quantizes to exactly +-127 (absmax scaling)
    assert int(jnp.max(jnp.abs(xq[0]))) == 127
    assert int(jnp.max(jnp.abs(xq[2]))) == 127
    np.testing.assert_array_equal(np.asarray(xq[1]), 0)
    assert float(scale[1, 0]) == 1.0
    # reconstruction error bounded by scale/2 per element
    err = jnp.abs(xq.astype(jnp.float32) * scale - x)
    assert bool(jnp.all(err <= scale / 2 + 1e-7))


def test_act_dtype_rejected_without_plan():
    rng = np.random.default_rng(0)
    qt = _make_qt(rng, rows=32, stripe_spec=[(2, 48)])
    x = jnp.asarray(rng.normal(size=(3, 48)).astype(np.float32))
    with pytest.raises(ValueError, match="plan"):
        ops.qmatmul(x, qt, use_kernel=True, act_dtype="int8")
    with pytest.raises(ValueError, match="act_dtype"):
        ops.prepared_qmatmul(x, prepare_for_inference(qt),
                             act_dtype="int4")


@pytest.mark.parametrize("spec,k_out", [
    ([(2, 96)], 0),                   # aligned via identity (random perm
    ([(3, 140)], 2),                  # here -> gathered; both layouts run)
    ([(2, 80), (4, 48)], 3),          # mixed precision, two launches
])
def test_int8_error_bound_all_paths(spec, k_out):
    """The int8 path's deviation from the f32 reference stays under the
    analytic bound scale/2 * ||W||_1 on every dispatch: raw x blocks,
    x through the XLA gather (bitwise-identical pair), and the XLA ref
    path."""
    rng = np.random.default_rng(sum(b for b, _ in spec) + k_out)
    qt = _make_qt(rng, rows=64, stripe_spec=spec, k_out=k_out)
    pqt = prepare_for_inference(qt)
    x = jnp.asarray(rng.normal(size=(5, qt.cols)).astype(np.float32))
    y_ref = ref_lib.ref_qmatmul(x, qt)
    bound = np.asarray(ref_lib.ref_act_int8_bound(x, qt.dequantize()))
    bound = bound * 1.01 + 1e-5       # epsilon for f32 accumulation order

    y_ker = ops.prepared_qmatmul(x, pqt, act_dtype="int8")
    y_pre = ops.prepared_qmatmul(x, dataclasses.replace(pqt, groups=tuple(
        dataclasses.replace(g, x_start=None) for g in pqt.groups)),
        act_dtype="int8")
    y_xla = ops.qmatmul(x, pqt, use_kernel=False, act_dtype="int8")
    assert np.array_equal(np.asarray(y_ker), np.asarray(y_pre)), \
        "int8 x fetches must match bitwise (same values, same order)"
    for y in (y_ker, y_xla):
        err = np.abs(np.asarray(y - y_ref))
        assert (err <= bound).all(), (err.max(), bound.max())
    # int8 really quantized: a generic random layout perturbs the output
    assert not np.array_equal(np.asarray(y_ker), np.asarray(y_ref))


def test_int8_bound_scales_with_activations():
    """The bound is per-token: scaling one token's activations scales
    exactly its row of the bound by the same factor, leaving other rows
    untouched."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32))
    b0 = np.asarray(ref_lib.ref_act_int8_bound(x, W))
    b1 = np.asarray(ref_lib.ref_act_int8_bound(x.at[2].multiply(100.0), W))
    np.testing.assert_allclose(b1[2], 100.0 * b0[2], rtol=1e-5)
    np.testing.assert_array_equal(b1[[0, 1, 3]], b0[[0, 1, 3]])


# ------------------------------------------------- engine + compiled HLO

@pytest.fixture(scope="module")
def int_bit_quantized():
    """Integer-bit (3-bit, no AP/OR) quantized smoke model: every matrix
    is single-stripe with an identity permutation, so all plans are
    x-aligned — the configuration whose decode must compile gather-free."""
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=64,
                              n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    qcfg = CLAQConfig(bits=3, method="uniform", gptq_blocksize=64)
    calib = calibration_set(vocab=cfg.vocab, n_segments=2, seq_len=16)
    qparams, _ = claq_quantize(params, cfg, calib, qcfg)
    return cfg, params, qparams


def test_engine_act_dtype_int8_serves(int_bit_quantized):
    cfg, _, qparams = int_bit_quantized
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=32, min_bucket=8,
                        act_dtype="int8")
    assert eng.stats()["act_dtype"] == "int8"
    uids = eng.add_requests([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)
    eng.run_to_completion()
    fin = eng.take_finished()
    assert all(len(fin[u].tokens) == 4 for u in uids)


def test_engine_act_dtype_validation(int_bit_quantized):
    cfg, _, qparams = int_bit_quantized
    with pytest.raises(ValueError, match="act_dtype"):
        ServingEngine(qparams, cfg, n_slots=2, max_len=32, act_dtype="int4")
    with pytest.raises(ValueError, match="prepare"):
        ServingEngine(qparams, cfg, n_slots=2, max_len=32,
                      act_dtype="int8", prepare=False)
    with pytest.raises(ValueError, match="draft_plan"):
        ServingEngine(qparams, cfg, n_slots=2, max_len=32,
                      draft_plan_bn=32)
    # draft tile overrides shape the draft's PLANS — meaningless (and
    # previously silently ignored) without preparation
    with pytest.raises(ValueError, match="prepare"):
        ServingEngine(qparams, cfg, n_slots=2, max_len=32, prepare=False,
                      draft_plan_bn=32, draft_params=qparams,
                      spec=SpecConfig(gamma=2, draft_bits=2))


def test_kernel_decode_step_adds_zero_gathers(int_bit_quantized):
    """THE hot-path claim: with the stripe-permutation gather folded into
    the kernel, a kernel-mode decode step over integer-bit CLAQ plans
    compiles to exactly as many gather instructions as the DENSE model's
    decode step — the quantized matmul path contributes none (it used to
    contribute one XLA activation gather per matmul).  Holds for f32 and
    int8 activations (quantization is elementwise).  Enforced through the
    shared HLO-GA1 rule (repro.analysis), the same check
    ``verify_contracts=True`` runs at engine init."""
    from repro.analysis import REGISTRY, run_rules
    from repro.analysis.artifacts import lowered_decode_text, plan_stats

    cfg, params, qparams = int_bit_quantized

    def decode_hlo(p, act_dtype=None):
        eng = ServingEngine(p, cfg, n_slots=2, max_len=32,
                            act_dtype=act_dtype)
        return eng, lowered_decode_text(eng)

    _, dense_txt = decode_hlo(params)
    eng_q, quant_txt = decode_hlo(qparams)
    _, quant_i8_txt = decode_hlo(qparams, act_dtype="int8")

    plan = plan_stats(eng_q.params, n_slots=2)
    assert plan["has_plans"] and plan["n_permuted_groups"] == 0, \
        "integer-bit plans must be all-aligned, else the check is vacuous"
    for txt in (quant_txt, quant_i8_txt):
        rep = run_rules([REGISTRY["HLO-GA1"]],
                        {"hlo": {"decode": txt},
                         "dense_hlo": {"decode": dense_txt}, "plan": plan})
        assert rep.rules_run == ["HLO-GA1"] and not rep.findings, \
            rep.render()
