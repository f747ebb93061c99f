"""Contract-checker tests (repro.analysis): one mutation test per
registered rule — a deliberately violated invariant must make exactly
that rule fire with its declared id/severity — plus registry
completeness, the TraceSentinel, golden reports for a dense and an AP+OR
config, and the ``verify_contracts=True`` engine-init smoke on the bench
substrate.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import pytest

from conftest import REPO

from repro.analysis import (REGISTRY, Report, Severity, ast_context,
                            run_rules)
from repro.analysis.artifacts import (dense_twin_engine, plan_stats,
                                      verify_engine,
                                      weight_shard_threshold)
from repro.analysis.core import ContractViolation, Finding, Rule, register
from repro.analysis.trace_rules import TraceSentinel
from repro.configs import get_smoke_config
from repro.core import APConfig, CLAQConfig, ORConfig
from repro.data import calibration_set
from repro.launch.quantize import claq_quantize
from repro.models import api
from repro.serve import ServingEngine

jax.config.update("jax_platform_name", "cpu")

GOLDEN = Path(__file__).parent / "golden"


# ---------------------------------------------------------------------------
# synthetic HLO modules for the compiled-artifact mutations
# ---------------------------------------------------------------------------

def _mod(body: str, header: str = "HloModule m") -> str:
    return (f"{header}\n\n"
            "%f (p: f32[8,16]) -> f32[8,16] {\n"
            "  %w = f32[8,16]{1,0} parameter(0)\n"
            f"{body}\n"
            "  ROOT %t = f32[8,16]{1,0} add(%w, %w)\n"
            "}\n\n"
            "ENTRY %e (a: f32[8,16]) -> f32[8,16] {\n"
            "  %a = f32[8,16]{1,0} parameter(0)\n"
            "  ROOT %r = f32[8,16]{1,0} add(%a, %a)\n"
            "}\n")


_CLEAN_MOD = _mod("  %x = f32[8,16]{1,0} multiply(%w, %w)")
_ALIGNED_PLAN = {"has_plans": True, "n_permuted_groups": 0, "max_bk": 0,
                 "bm": 8, "itemsize": 4}
_PERMUTED_PLAN = {"has_plans": True, "n_permuted_groups": 1, "max_bk": 16,
                  "bm": 8, "itemsize": 4}


def _sentinel_over_budget():
    s = TraceSentinel()
    s.observe("prefill", (1, 8))
    s.observe("prefill", (1, 16))
    s.observe("prefill", (2, 8))
    return {"sentinel": s, "compile_budget": {"prefill": 2}}


def _sentinel_retrace():
    s = TraceSentinel()
    s.observe("decode", (2, False))
    return {"sentinel": s, "trace_counts": {"decode": 3}}


# Every mutation: rule id -> ctx builder that VIOLATES exactly that
# invariant.  tmp_path is used by the AST entries (they lint real files).
MUTATIONS = {
    "HLO-AG1": lambda tmp: {
        "hlo": {"decode": _mod(
            "  %ag = f32[64,16]{1,0} all-gather(%w), replica_groups={}")},
        "weight_shard_bytes": 1024},
    "HLO-CB1": lambda tmp: {
        "hlo": {"decode": _mod(
            "  %ar = f32[64,16]{1,0} all-reduce(%w), to_apply=%f")},
        "collective_budget_bytes": 1024},
    "HLO-HT1": lambda tmp: {
        "hlo": {"decode": _mod(
            "  %o = token[] outfeed(%w, token[] %tok)")}},
    "HLO-DT1": lambda tmp: {
        "hlo": {"decode": _mod(
            "  %d = f32[4,64]{1,0} convert(s8[4,64]{1,0} %q)")},
        "pool_slice_elems": 64},
    "HLO-GA1": lambda tmp: {
        "hlo": {"decode": _mod(
            "  %g = f32[2,16]{1,0} gather(%w, s32[2]{0} %i), "
            "offset_dims={1}")},
        "dense_hlo": {"decode": _CLEAN_MOD},
        "plan": dict(_ALIGNED_PLAN)},
    "HLO-CP1": lambda tmp: {
        "hlo": {"decode": _mod("  %c = f32[16,16]{1,0} copy(%w)")},
        "cache_leaf_bytes": 16 * 16 * 4},
    "HLO-DN1": lambda tmp: {
        "hlo": {"decode": _CLEAN_MOD},
        "donation_expected": True},
    "TRC-CC1": lambda tmp: _sentinel_over_budget(),
    "TRC-SG1": lambda tmp: _sentinel_retrace(),
    "AST-IM1": lambda tmp: ast_context([_write(
        tmp, "m.py", "import jax.numpy as jnp\nx = jnp.zeros((3,))\n")]),
    "AST-JT1": lambda tmp: ast_context([_write(
        tmp, "m.py",
        "import jax\n@jax.jit\ndef f(x):\n"
        "    global evil\n    evil = 1\n    return x\n")]),
    "AST-HS1": lambda tmp: ast_context([_write(
        tmp, "m.py",
        "import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")]),
    "AST-DT1": lambda tmp: ast_context([_write(
        tmp, "repro/serve/sched.py",
        "import time\ndef tick():\n    return time.time()\n")]),
}


def _write(tmp: Path, rel: str, source: str) -> Path:
    p = tmp / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(source)
    return p


def test_registry_is_complete():
    """Every registered rule has a mutation test — no vacuous green."""
    assert set(MUTATIONS) == set(REGISTRY)


@pytest.mark.parametrize("rule_id", sorted(MUTATIONS))
def test_mutation_fires_rule(rule_id, tmp_path):
    rule = REGISTRY[rule_id]
    rep = run_rules([rule], MUTATIONS[rule_id](tmp_path), subject=rule_id)
    assert rep.findings, f"{rule_id} did not fire on a seeded violation"
    assert all(f.rule_id == rule_id for f in rep.findings)
    assert all(f.severity is rule.severity for f in rep.findings)
    assert rep.rules_run == [rule_id]


@pytest.mark.parametrize("rule_id", sorted(MUTATIONS))
def test_rule_skips_on_empty_context(rule_id):
    """With none of its context keys present every rule reports skipped,
    never a false finding (and never a crash)."""
    rep = run_rules([REGISTRY[rule_id]], {}, subject="empty")
    assert rep.rules_skipped == [rule_id] and not rep.findings


# ---------------------------------------------------------------------------
# targeted clean-path checks (the mutation's conforming twin)
# ---------------------------------------------------------------------------

def test_gather_parity_permuted_branch():
    """Permuted plans: a tile-sized added take passes; an activation-sized
    gather or more takes than permuted groups fails."""
    rule = REGISTRY["HLO-GA1"]
    dense = {"decode": _CLEAN_MOD}
    tile = _mod("  %g = f32[2,16]{1,0} gather(%w, s32[2]{0} %i), "
                "offset_dims={1}")                      # 128 B <= 512 B cap
    ok = run_rules([rule], {"hlo": {"decode": tile}, "dense_hlo": dense,
                            "plan": dict(_PERMUTED_PLAN)})
    assert not ok.findings
    big = _mod("  %g = f32[8,512]{1,0} gather(%w, s32[8]{0} %i), "
               "offset_dims={1}")                       # 16 KiB activation
    bad = run_rules([rule], {"hlo": {"decode": big}, "dense_hlo": dense,
                             "plan": dict(_PERMUTED_PLAN)})
    assert bad.findings


def test_jit_counter_allowlist_and_suppression(tmp_path):
    """Registered trace counters may be bumped inside jitted fns, and a
    `# contract: ok` comment suppresses any AST rule on that line."""
    ok = ast_context([_write(
        tmp_path, "a.py",
        "import jax\n@jax.jit\ndef f(x):\n"
        "    global decode_traces\n    decode_traces = 1\n"
        "    global launch_count\n    launch_count = 1\n    return x\n")])
    assert not run_rules([REGISTRY["AST-JT1"]], ok).findings

    supp = ast_context([_write(
        tmp_path, "b.py",
        "import jax\n@jax.jit\ndef f(x):\n"
        "    global evil  # contract: ok - exercised in tests\n"
        "    evil = 1\n    return x\n")])
    assert not run_rules([REGISTRY["AST-JT1"]], supp).findings


def test_host_sync_rule_allows_shape_math(tmp_path):
    src = ("import jax\n@jax.jit\ndef f(x):\n"
           "    n = x.shape[0]\n"
           "    return x * float(n) + float(len(x.shape))\n")
    ctx = ast_context([_write(tmp_path, "c.py", src)])
    assert not run_rules([REGISTRY["AST-HS1"]], ctx).findings


def test_import_time_rule_ignores_function_bodies(tmp_path):
    src = ("import jax.numpy as jnp\n"
           "def f():\n    return jnp.zeros((3,))\n")
    ctx = ast_context([_write(tmp_path, "d.py", src)])
    assert not run_rules([REGISTRY["AST-IM1"]], ctx).findings


def test_determinism_rule_is_scoped(tmp_path):
    """time.time() outside the serve scope is not this rule's business."""
    ctx = ast_context([_write(
        tmp_path, "tools/bench.py",
        "import time\ndef t():\n    return time.time()\n")])
    assert not run_rules([REGISTRY["AST-DT1"]], ctx).findings


def test_determinism_rule_telemetry_carveout(tmp_path):
    """serve/telemetry.py is the ONE sanctioned clock source on serve
    paths (DESIGN.md §13): a wall-clock read there is clean, while the
    identical call in any OTHER repro/serve file still fires — both
    directions pinned so the carve-out can neither widen nor silently
    disable the rule."""
    src = "import time\ndef monotonic():\n    return time.monotonic()\n"
    ok = ast_context([_write(tmp_path, "repro/serve/telemetry.py", src)])
    assert not run_rules([REGISTRY["AST-DT1"]], ok).findings
    bad = ast_context([_write(tmp_path, "repro/serve/engine.py", src)])
    rep = run_rules([REGISTRY["AST-DT1"]], bad)
    assert rep.findings, "AST-DT1 went quiet outside the carve-out"
    assert all(f.rule_id == "AST-DT1" for f in rep.findings)


def test_donation_rule_clean_when_aliased():
    aliased = _mod("  %x = f32[8,16]{1,0} multiply(%w, %w)",
                   header="HloModule m, input_output_alias="
                          "{ {0}: (0, {}, must-alias) }")
    rep = run_rules([REGISTRY["HLO-DN1"]],
                    {"hlo": {"decode": aliased}, "donation_expected": True})
    assert not rep.findings


# ---------------------------------------------------------------------------
# framework plumbing
# ---------------------------------------------------------------------------

def test_register_rejects_duplicates_and_blank_ids():
    class Dup(Rule):
        id = "HLO-AG1"

    class Blank(Rule):
        id = ""

    with pytest.raises(ValueError, match="duplicate"):
        register(Dup())
    with pytest.raises(ValueError, match="no id"):
        register(Blank())


def test_report_renders_and_serializes():
    f = Finding("X-1", Severity.ERROR, "boom", subject="decode",
                details={"n": 3})
    rep = Report(subject="s", findings=[f], rules_run=["X-1"],
                 rules_skipped=["Y-1"])
    assert not rep.clean and rep.errors == [f]
    txt = rep.render()
    assert "VIOLATIONS" in txt and "X-1" in txt and "boom" in txt
    j = rep.to_json()
    assert j["clean"] is False and j["summary"]["ERROR"] == 1
    json.dumps(j)                                   # JSON-serializable
    with pytest.raises(ContractViolation) as ei:
        raise ContractViolation(rep)
    assert ei.value.report is rep


def test_trace_sentinel_accounting():
    s = TraceSentinel()
    s.observe("decode", (4, False))
    s.observe("decode", (4, False))
    s.observe("decode", (1, False))
    s.observe_lowering("decode")
    assert s.distinct("decode") == 2 and s.calls("decode") == 3
    snap = s.snapshot()
    assert snap["decode"] == {"distinct": 2, "calls": 3, "lowerings": 1}
    # counts within [distinct, distinct+lowerings] are clean; outside fires
    rule = REGISTRY["TRC-SG1"]
    ok = run_rules([rule], {"sentinel": s, "trace_counts": {"decode": 3}})
    assert not ok.findings
    bad = run_rules([rule], {"sentinel": s, "trace_counts": {"decode": 4}})
    assert bad.findings
    broken = run_rules([rule], {"sentinel": s, "trace_counts": {"decode": 1}})
    assert broken.findings            # counter under-reports: also a bug


# ---------------------------------------------------------------------------
# engine-integrated: live sentinel, golden reports, verify_contracts smoke
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_models():
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=64,
                              n_layers=1)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=2,
                      gptq_blocksize=32, ap=APConfig(2.2, 2, 4),
                      orr=ORConfig(0.1))
    calib = calibration_set(vocab=cfg.vocab, n_segments=2, seq_len=16)
    qparams, _ = claq_quantize(params, cfg, calib, qcfg)
    return cfg, params, qparams


def test_engine_sentinel_tracks_traces(small_models):
    """The live engine's sentinel agrees with its trace counters and the
    bucketing budget — the runtime form of TRC-CC1/TRC-SG1."""
    cfg, params, _ = small_models
    eng = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8,
                        prepare=False)
    uids = eng.add_requests([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=3)
    eng.run_to_completion()
    assert len(eng.take_finished()) == len(uids)
    assert eng.sentinel.distinct("prefill") == eng.prefill_traces
    assert eng.sentinel.distinct("decode") == eng.decode_traces
    rep = verify_engine(eng, with_baseline=False, raise_on_error=False,
                        subject="live")
    assert rep.clean, rep.render()
    assert {"TRC-CC1", "TRC-SG1"} <= set(rep.rules_run)


def _stable(report: Report):
    """Projection pinned by the goldens: which rules ran/skipped and which
    fired at what severity — byte counts and messages stay free to drift
    with XLA versions."""
    j = report.to_json()
    return {"subject": j["subject"], "clean": j["clean"],
            "rules_run": j["rules_run"],
            "rules_skipped": j["rules_skipped"],
            "findings": sorted({(f["rule"], f["severity"])
                                for f in j["findings"]})}


def _golden(name: str):
    doc = json.loads((GOLDEN / name).read_text())
    doc["findings"] = sorted(tuple(f) for f in doc["findings"])
    return doc


def test_golden_report_dense(small_models):
    cfg, params, _ = small_models
    eng = ServingEngine(params, cfg, n_slots=2, max_len=32, prepare=False)
    rep = verify_engine(eng, raise_on_error=False, subject="config:dense")
    assert _stable(rep) == _golden("contracts_dense.json")


def test_golden_report_ap_or(small_models):
    cfg, params, qparams = small_models
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=32)
    dense_eng = ServingEngine(params, cfg, n_slots=2, max_len=32,
                              prepare=False)
    assert plan_stats(eng.params)["n_permuted_groups"] > 0, \
        "AP model produced no permuted plan -> vacuous golden"
    rep = verify_engine(eng, dense_eng, raise_on_error=False,
                        subject="config:ap_or")
    assert _stable(rep) == _golden("contracts_ap_or.json")


def test_dense_twin_matches_engine_structure(small_models):
    cfg, _, qparams = small_models
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=32)
    twin = dense_twin_engine(eng)
    assert not plan_stats(twin.params)["has_plans"]
    assert (twin.n_slots, twin.max_len) == (eng.n_slots, eng.max_len)
    # twin serves: dequantized weights flow through the dense path
    twin.add_requests([[1, 2, 3]], max_new_tokens=2)
    twin.run_to_completion()


def test_weight_shard_threshold(small_models):
    cfg, _, qparams = small_models
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=32, plan_bn=32)
    assert weight_shard_threshold(eng.params, 1) is None
    t4 = weight_shard_threshold(eng.params, 4)
    assert t4 is not None and t4 > 0


def test_verify_contracts_raises_on_violation(small_models, monkeypatch):
    """End-to-end mutation: force a violating artifact through the init
    gate and the engine must refuse to come up."""
    from repro.analysis import artifacts as afx
    cfg, params, _ = small_models
    monkeypatch.setattr(
        afx, "lowered_decode_text",
        lambda engine: _mod(
            "  %o = token[] outfeed(%w, token[] %tok)"))
    with pytest.raises(ContractViolation, match="HLO-HT1"):
        ServingEngine(params, cfg, n_slots=2, max_len=32, prepare=False,
                      verify_contracts=True)


def test_verify_contracts_smoke_on_bench_substrate():
    """ISSUE 8 acceptance: engine init with verify_contracts=True over the
    trained bench substrate passes the artifact rules."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks.common import recipe, trained_model
    cfg, params, hessians = trained_model()
    from repro.launch.quantize import quantize_model_params
    qparams, _ = quantize_model_params(params, cfg, hessians,
                                       recipe("rtn3"))
    eng = ServingEngine(qparams, cfg, n_slots=2, max_len=64,
                        verify_contracts=True)
    assert eng.contract_report is not None and eng.contract_report.clean
    assert "HLO-GA1" in eng.contract_report.rules_run
