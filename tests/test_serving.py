"""Serving-engine correctness and prefill length bucketing.

Covers the bucketing acceptance bar — N distinct prompt lengths cost at
most ``ceil(log2(max_len / min_bucket)) + 1`` prefill traces (counted by
a trace-time side effect, not estimated), and bucketed admission emits
token-for-token identical greedy output to unbucketed admission on an
AP+OR-quantized model — plus the decode-loop retirement fixes: EOS at
prefill, a one-token budget, slot reuse after retirement, and
``run_to_completion`` surfacing truncation.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import APConfig, CLAQConfig, ORConfig
from repro.data import calibration_set
from repro.launch.quantize import claq_quantize
from repro.models import api
from repro.serve import BucketingPolicy, ServingEngine

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------------- policy

def test_bucket_policy_shapes():
    pol = BucketingPolicy(min_bucket=8, max_len=64)
    assert pol.buckets() == (8, 16, 32, 64)
    assert pol.max_traces() == math.ceil(math.log2(64 / 8)) + 1 == 4
    assert pol.bucket_for(1) == 8
    assert pol.bucket_for(8) == 8
    assert pol.bucket_for(9) == 16
    assert pol.bucket_for(33) == 64
    with pytest.raises(ValueError):
        pol.bucket_for(65)
    with pytest.raises(ValueError):
        pol.bucket_for(0)


def test_bucket_policy_non_pow2_max_len():
    pol = BucketingPolicy(min_bucket=16, max_len=100)
    assert pol.buckets() == (16, 32, 64, 100)
    assert pol.bucket_for(70) == 100
    assert len(pol.buckets()) == pol.max_traces() == 4


def test_bucket_policy_disabled_is_identity():
    pol = BucketingPolicy(min_bucket=8, max_len=64, enabled=False)
    assert pol.bucket_for(13) == 13


def test_bucket_policy_compile_cache_stats():
    pol = BucketingPolicy(min_bucket=8, max_len=64)
    assert pol.record(1, 8) is False      # first (batch, bucket): a trace
    assert pol.record(1, 8) is True       # same shape: compile-cache hit
    assert pol.record(2, 8) is False      # new batch size: a trace
    assert pol.stats.misses == 2 and pol.stats.hits == 1
    assert pol.stats.hit_rate == pytest.approx(1 / 3)


# ----------------------------------------------------------------- engine

@pytest.fixture(scope="module")
def fp_model():
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def quantized_model(fp_model):
    """AP+OR fused CLAQ quantization (the paper's deployment format)."""
    cfg, params = fp_model
    qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=4,
                      gptq_blocksize=32, ap=APConfig(2.2, 2, 4),
                      orr=ORConfig(0.1))
    calib = calibration_set(vocab=cfg.vocab, n_segments=4, seq_len=32)
    qparams, report = claq_quantize(params, cfg, calib, qcfg)
    assert 2.0 < report.mean_effective_bits < 2.6
    return cfg, qparams


def _serve(eng, prompts, max_new, eos_id=None):
    """Admit, run to completion, return token lists in prompt order."""
    uids = eng.add_requests(prompts, max_new_tokens=max_new, eos_id=eos_id)
    eng.run_to_completion()
    fin = eng.take_finished()
    return [fin[u].tokens for u in uids]


def test_trace_count_bounded_by_buckets(fp_model):
    """≥6 distinct prompt lengths in [1, max_len) cost at most
    ceil(log2(max_len / min_bucket)) + 1 prefill traces.  The bound is
    also enforced by the shared TRC-CC1/TRC-SG1 rules over the engine's
    TraceSentinel — the same check ``verify_contracts=True`` runs."""
    from repro.analysis import REGISTRY, run_rules
    from repro.analysis.artifacts import compile_budgets, trace_counts

    cfg, params = fp_model
    lengths = [1, 3, 7, 9, 20, 40, 63]
    prompts = [list(range(1, n + 1)) for n in lengths]

    eng = ServingEngine(params, cfg, n_slots=2, max_len=64, min_bucket=8)
    for p in prompts:
        # one-token budget: each request retires at admission, so a
        # 2-slot engine admits any number of distinct lengths
        eng.add_request(p, max_new_tokens=1)
    bound = math.ceil(math.log2(64 / 8)) + 1
    assert eng.bucketing.max_traces() == bound
    assert eng.prefill_traces <= bound, eng.stats()
    assert eng.stats()["bucket_misses"] == eng.prefill_traces

    rep = run_rules([REGISTRY["TRC-CC1"], REGISTRY["TRC-SG1"]],
                    {"sentinel": eng.sentinel,
                     "compile_budget": compile_budgets(eng),
                     "trace_counts": trace_counts(eng)})
    assert rep.rules_run == ["TRC-CC1", "TRC-SG1"] and not rep.findings, \
        rep.render()
    assert eng.sentinel.distinct("prefill") == eng.prefill_traces

    # without bucketing every distinct length is its own compile
    eng2 = ServingEngine(params, cfg, n_slots=2, max_len=64,
                         bucketing=False)
    for p in prompts:
        eng2.add_request(p, max_new_tokens=1)
    assert eng2.prefill_traces == len(lengths)
    assert eng2.prefill_traces > eng.prefill_traces


def test_bucketed_matches_unbucketed_on_quantized_model(quantized_model):
    """Greedy tokens are identical with and without padding to buckets,
    on the AP+OR-quantized weights flowing through prepared plans."""
    cfg, qparams = quantized_model
    prompts = [[1, 2], [3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15, 16],
               [20, 21, 22, 23, 24, 25, 26, 27, 28, 29, 30, 31, 32]]

    eng_b = ServingEngine(qparams, cfg, n_slots=4, max_len=64, min_bucket=8)
    toks_b = _serve(eng_b, prompts, max_new=6)
    eng_u = ServingEngine(qparams, cfg, n_slots=4, max_len=64,
                          bucketing=False)
    toks_u = _serve(eng_u, prompts, max_new=6)

    assert toks_b == toks_u
    assert all(len(t) == 6 for t in toks_b)
    assert eng_b.prefill_traces < eng_u.prefill_traces


def test_bucketed_matches_unbucketed_with_int8_activations(quantized_model):
    """The opt-in int8 activation path keeps the engine's structural
    invariants: activation quantization is per-token (elementwise per
    position), so bucketed admission still emits tokens bit-identical to
    unbucketed admission under act_dtype='int8' — and the int8 engine
    runs the same machinery end to end on the AP+OR plans."""
    cfg, qparams = quantized_model
    prompts = [[1, 2], [3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15, 16]]

    eng_b = ServingEngine(qparams, cfg, n_slots=3, max_len=64, min_bucket=8,
                          act_dtype="int8")
    toks_b = _serve(eng_b, prompts, max_new=5)
    eng_u = ServingEngine(qparams, cfg, n_slots=3, max_len=64,
                          bucketing=False, act_dtype="int8")
    toks_u = _serve(eng_u, prompts, max_new=5)

    assert toks_b == toks_u
    assert all(len(t) == 5 for t in toks_b)
    assert eng_b.stats()["act_dtype"] == "int8"


def test_ap_kernel_decode_gathers_are_tile_sized(quantized_model, fp_model):
    """Mixed-precision (AP) plans cannot drop indexing entirely — the
    kernel takes each tile's columns from a VMEM-resident x block.  The
    compiled kernel-mode decode step may therefore add gathers over the
    dense baseline, but every one of them must be a TILE-sized in-kernel
    take, never the old activation-sized XLA gather (whose result spans
    the whole fused K axis of a matmul).  The byte cap, the
    count-per-permuted-group cap, and the multiset diff against the dense
    baseline all live in the shared HLO-GA1 rule (repro.analysis)."""
    from repro.analysis import REGISTRY, run_rules
    from repro.analysis.artifacts import lowered_decode_text, plan_stats

    cfg, qparams = quantized_model
    _, params = fp_model

    def decode_hlo(p):
        eng = ServingEngine(p, cfg, n_slots=2, max_len=32)
        return eng, lowered_decode_text(eng)

    _, dense_txt = decode_hlo(params)
    eng_q, quant_txt = decode_hlo(qparams)

    plan = plan_stats(eng_q.params, n_slots=2)
    assert plan["n_permuted_groups"] > 0, \
        "AP model produced no permuted plan -> vacuous"
    rep = run_rules([REGISTRY["HLO-GA1"]],
                    {"hlo": {"decode": quant_txt},
                     "dense_hlo": {"decode": dense_txt}, "plan": plan})
    assert rep.rules_run == ["HLO-GA1"] and not rep.findings, rep.render()


def test_batched_admission_shares_one_prefill(fp_model):
    """Prompts in the same bucket are admitted in ONE batched prefill and
    match one-at-a-time admission token for token."""
    cfg, params = fp_model
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11, 12]]  # bucket 8

    eng = ServingEngine(params, cfg, n_slots=4, max_len=64, min_bucket=8)
    toks_batched = _serve(eng, prompts, max_new=5)
    assert eng.prefill_traces == 1, eng.stats()

    # the admission batch size is bucketed too: a different group size in
    # the same (padded) shape class reuses the compile
    toks_again = _serve(eng, prompts + [[13, 14]], max_new=5)
    assert eng.prefill_traces == 1, eng.stats()
    assert toks_again[:3] == toks_batched

    eng1 = ServingEngine(params, cfg, n_slots=4, max_len=64, min_bucket=8)
    toks_single = []
    for p in prompts:
        toks_single += _serve(eng1, [p], max_new=5)
    assert toks_batched == toks_single


def test_moe_family_admits_unpadded_and_unbatched():
    """Capacity-bounded MoE routing couples tokens across the flattened
    B*S batch: padded or co-batched rows change which valid tokens are
    capacity-dropped.  The engine must admit moe at exact lengths, one
    request per prefill, so add_requests == one-at-a-time admission."""
    cfg = dataclasses.replace(get_smoke_config("qwen3_moe_30b_a3b"),
                              vocab=64, n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[1, 2, 3], [4, 5, 6], [7, 8, 9]]

    eng = ServingEngine(params, cfg, n_slots=4, max_len=32)
    assert not eng.bucketing.enabled
    toks_grouped = _serve(eng, prompts, max_new=2)
    # same length, but never batched together: one (1, 3) prefill each
    assert eng.bucketing.stats.per_shape == {(1, 3): 3}

    eng1 = ServingEngine(params, cfg, n_slots=4, max_len=32)
    toks_single = []
    for p in prompts:
        toks_single += _serve(eng1, [p], max_new=2)
    # prefill-sampled first tokens match isolated admission exactly (the
    # admission guarantee); later tokens may differ because the DECODE
    # batch composition differs (slots decode together here, alone in
    # eng1) and moe routing couples the decode batch too — inherent to
    # continuous batching, not an admission artifact.
    assert [t[0] for t in toks_grouped] == [t[0] for t in toks_single]
    assert all(len(t) == 2 for t in toks_grouped)


def test_windowed_dense_admits_unpadded(fp_model):
    """A sliding-window ring cache keeps the LAST W keys, so a padded
    suffix would evict valid ones: padding must gate off on attn_window."""
    cfg, params = fp_model
    wcfg = dataclasses.replace(cfg, attn_window=16)
    eng = ServingEngine(params, wcfg, n_slots=2, max_len=64)
    assert not eng.bucketing.enabled
    (toks,) = _serve(eng, [[1, 2, 3, 4, 5]], max_new=3)
    assert len(toks) == 3


def test_eos_at_prefill_retires_at_admission(fp_model):
    cfg, params = fp_model
    prompt = [5, 6, 7]
    cache = api.make_cache(cfg, 1, 64, dtype=jnp.float32)
    logits, _ = api.prefill_step(
        params, cfg, {"tokens": jnp.asarray([prompt], jnp.int32)}, cache)
    first = int(jnp.argmax(logits[0]))

    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    uid = eng.add_request(prompt, max_new_tokens=8, eos_id=first)
    assert uid not in eng.active          # retired before any decode step
    assert eng.finished[uid].done
    assert eng.finished[uid].tokens == [first]
    assert len(eng.free) == 2             # slot returned immediately
    assert eng.step() == {}


def test_max_new_tokens_one_emits_exactly_one(fp_model):
    cfg, params = fp_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    uid = eng.add_request([1, 2, 3, 4], max_new_tokens=1)
    assert uid in eng.finished and len(eng.finished[uid].tokens) == 1
    # budget honored exactly for >1 too: prefill token + (n-1) decode steps
    (toks,) = _serve(eng, [[1, 2, 3, 4]], max_new=2)
    assert len(toks) == 2


def test_slot_reuse_after_retirement(fp_model):
    cfg, params = fp_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    pending = [[i + 1, i + 2] for i in range(6)]  # 6 requests, 2 slots
    admitted = []
    while pending or eng.active:
        if pending and eng.free:
            batch = [pending.pop(0)
                     for _ in range(min(len(pending), len(eng.free)))]
            admitted += eng.add_requests(batch, max_new_tokens=3)
        eng.step()
    fin = eng.take_finished()
    assert sorted(fin) == sorted(admitted) and len(fin) == 6
    assert all(r.done and len(r.tokens) == 3 for r in fin.values())
    assert sorted(eng.free) == [0, 1]


def test_admission_rejects_cache_overflow(fp_model):
    """A request whose prompt + token budget exceeds max_len must be
    rejected at admission: decode would write past the cache end, where
    the K/V update clamps/drops — silently corrupting the last cache
    position."""
    cfg, params = fp_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(list(range(1, 10)), max_new_tokens=8)   # 9 + 8 > 16
    # nothing was admitted, no slot leaked
    assert not eng.active and len(eng.free) == 2
    # the boundary case fits exactly (the last generated token is never
    # written back) and must run its full budget
    uid = eng.add_request(list(range(1, 9)), max_new_tokens=8)  # 8 + 8 == 16
    eng.run_to_completion()
    req = eng.take_finished()[uid]
    assert len(req.tokens) == 8 and not req.truncated


def test_cache_full_retires_truncated(fp_model):
    """Belt-and-braces guard behind admission validation: if a request's
    budget grows mid-flight (streaming extension), a full slot cache
    retires it with `truncated` set instead of decode silently
    overwriting the last K/V position."""
    cfg, params = fp_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=16)
    uid = eng.add_request(list(range(1, 9)), max_new_tokens=8)
    eng.active[uid].max_new_tokens = 100   # simulate a mid-flight extension
    eng.run_to_completion()
    req = eng.take_finished()[uid]
    assert req.done and req.truncated
    # prefill wrote 8 positions; decode may write the remaining 8, and the
    # token sampled from the last in-bounds write is still emitted
    assert len(req.tokens) == 16 - 8 + 1
    assert len(eng.free) == 2              # slot recycled


def test_run_to_completion_surfaces_truncation(fp_model):
    cfg, params = fp_model
    eng = ServingEngine(params, cfg, n_slots=2, max_len=64)
    uid = eng.add_request([1, 2, 3], max_new_tokens=32)
    with pytest.raises(RuntimeError, match="max_steps"):
        eng.run_to_completion(max_steps=3)
    unfinished = eng.run_to_completion(max_steps=2, strict=False)
    assert unfinished == [uid]
    assert eng.run_to_completion() == []  # now finishes; [] == complete


def test_engine_records_the_outlier_slot_share(fp_model, quantized_model):
    """At construction an engine with a recorder records the share of
    outlier slots its prepared weights' kernel epilogues visit
    (`kernel.or_slot_share`, DESIGN.md §13) — what the benchmark's reader
    of that name reads; a model without outliers records none."""
    import importlib.util
    import pathlib
    import types

    from repro.kernels.plan import or_slot_share
    from repro.serve import Telemetry

    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "metrics" / "kernel.or_slot_share.py")
    spec = importlib.util.spec_from_file_location("or_slot_share_reader",
                                                  path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    cfg, qparams = quantized_model
    tel = Telemetry()
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=32, telemetry=tel)
    share = tel.registry.get("kernel.or_slot_share").value
    assert 0 < share <= 100 and share == or_slot_share(eng.params)
    assert reader.read(types.SimpleNamespace(telemetry=tel)) == share

    cfg, params = fp_model
    tel = Telemetry()
    ServingEngine(params, cfg, n_slots=2, max_len=32, telemetry=tel)
    assert tel.registry.get("kernel.or_slot_share") is None
    assert reader.read(types.SimpleNamespace(telemetry=tel)) is None
