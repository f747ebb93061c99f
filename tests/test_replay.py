"""Trace-driven replay (DESIGN.md §13): trace synthesis/round-trip,
the scheduling report and its structural validator, byte-identical
deterministic replay under a preempt/resume storm, and the CLI paths
(`python -m repro.serve.replay`, `launch/serve.py --replay-trace`).

The determinism contract under test: one seed + a StepClock yields a
byte-identical report AND event stream across independent runs —
including runs where the fault injector's pressure windows preempt and
resume requests mid-flight.
"""
import dataclasses
import json

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import api
from repro.serve import (Arrival, FaultInjector, Replayer, ServingEngine,
                         StepClock, Telemetry, load_trace, save_trace,
                         synthesize_trace, validate_report)
from repro.serve import replay as replay_cli

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def fp_model():
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(fp_model, telemetry=None, faults=False, seed=0):
    cfg, params = fp_model
    inj = None
    if faults:
        # pressure-only plan: the windows' limit falls below running
        # fills, so replay exercises preempt/resume deterministically
        inj = FaultInjector(seed=seed + 7, horizon=32, nan_faults=0,
                            inf_faults=0, transient_failures=0,
                            pressure_windows=2, pressure_frac=(0.15, 0.25))
    return ServingEngine(params, cfg, n_slots=3, max_len=48, min_bucket=8,
                         clock=StepClock(10.0), telemetry=telemetry,
                         faults=inj, on_pressure="preempt")


# -------------------------------------------------------------------- trace

def test_synthesize_trace_is_seed_deterministic():
    a = synthesize_trace(seed=5, steps=20)
    b = synthesize_trace(seed=5, steps=20)
    assert a == b and len(a) > 0
    assert synthesize_trace(seed=6, steps=20) != a
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))
    # a deadline_frac slice of arrivals carries a tight SLO
    assert any(x.deadline_ms is not None for x in a)
    assert any(x.deadline_ms is None for x in a)


def test_trace_jsonl_roundtrip(tmp_path):
    trace = synthesize_trace(seed=1, steps=16)
    p = tmp_path / "trace.jsonl"
    save_trace(str(p), trace)
    assert load_trace(str(p)) == trace
    # optional fields are omitted from the JSON when defaulted
    line = json.loads(p.read_text().splitlines()[0])
    assert "priority" not in line or line["priority"] != 0
    # load sorts by arrival time (same multiset, non-decreasing t; the
    # sort is stable, so equal-t burst arrivals may keep written order)
    shuffled = tmp_path / "shuffled.jsonl"
    save_trace(str(shuffled), list(reversed(trace)))
    got = load_trace(str(shuffled))
    assert all(x.t <= y.t for x, y in zip(got, got[1:]))
    assert sorted(map(repr, got)) == sorted(map(repr, trace))


def test_load_trace_names_bad_line(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"t": 0.0, "prompt": [1]}\n{"t": "nope"}\n')
    with pytest.raises(ValueError, match=r"bad\.jsonl:2"):
        load_trace(str(p))


# ------------------------------------------------------------------- report

def test_replay_report_schema_and_percentiles(fp_model):
    trace = synthesize_trace(seed=0, steps=16, vocab=128, max_new=(4, 9))
    report = Replayer(_engine(fp_model, telemetry=Telemetry()), trace).run()
    validate_report(report)
    assert report["trace"]["n_arrivals"] == len(trace)
    assert report["requests"]["submitted"] == len(trace)
    # non-vacuous percentile fields
    assert report["ttft_ms"]["count"] > 0
    assert report["ttft_ms"]["p50"] <= report["ttft_ms"]["p99"]
    assert report["tokens"]["total_out"] > 0
    assert report["tokens"]["per_s_per_slot"] > 0
    assert len(report["per_request"]) == len(trace)
    # timelines sampled every engine step
    assert report["timelines"]["queue_depth"]["n"] > 0
    # TPOT is recomputable post-hoc from the per-request table
    for row in report["per_request"]:
        if row["tpot_ms"] is not None:
            assert row["tokens_out"] >= 2


def test_replay_is_byte_identical_under_preempt_storm(fp_model):
    trace = synthesize_trace(seed=2, steps=20, vocab=128, max_new=(4, 9))

    def run():
        tel = Telemetry()
        rep = Replayer(_engine(fp_model, telemetry=tel, faults=True),
                       trace).run()
        return rep, tel.events

    rep1, ev1 = run()
    rep2, ev2 = run()
    # the storm must actually preempt and resume — otherwise this proves
    # nothing about mid-flight determinism
    assert rep1["scheduling"]["preemptions"] >= 1
    assert rep1["scheduling"]["resumes"] >= 1
    assert json.dumps(rep1, sort_keys=True) == json.dumps(rep2,
                                                          sort_keys=True)
    assert json.dumps(ev1) == json.dumps(ev2)


def test_replay_without_telemetry_matches_token_streams(fp_model):
    trace = synthesize_trace(seed=3, steps=16, vocab=128, max_new=(4, 9))

    def run(tel):
        eng = _engine(fp_model, telemetry=tel, faults=True)
        rep = Replayer(eng, trace).run()
        fin = eng.take_finished()
        return rep, {u: list(r.tokens) for u, r in fin.items()}

    rep_off, toks_off = run(None)
    assert rep_off is None                 # no telemetry -> no report
    rep_on, toks_on = run(Telemetry())
    assert rep_on is not None
    assert toks_on == toks_off             # hooks are observation-only


def test_validate_report_names_every_problem(fp_model):
    trace = synthesize_trace(seed=0, steps=12, vocab=128)
    report = Replayer(_engine(fp_model, telemetry=Telemetry()), trace).run()
    bad = json.loads(json.dumps(report))
    bad["schema"] = "nope"
    bad["ttft_ms"]["p90"] = bad["ttft_ms"]["p50"] - 1.0  # non-monotone
    del bad["tokens"]["per_s_per_slot"]
    with pytest.raises(ValueError) as ei:
        validate_report(bad)
    msg = str(ei.value)
    assert "schema" in msg and "not monotone" in msg
    assert "per_s_per_slot" in msg


# ---------------------------------------------------------------------- cli

def test_replay_cli_smoke(tmp_path, capsys):
    rep_path = tmp_path / "report.json"
    tr_path = tmp_path / "trace.json"
    rc = replay_cli.main(["--smoke", "--faults", "--steps", "12",
                          "--report-json", str(rep_path),
                          "--perfetto", str(tr_path)])
    assert rc == 0
    report = validate_report(json.loads(rep_path.read_text()))
    assert report["ttft_ms"]["count"] > 0
    doc = json.loads(tr_path.read_text())
    assert doc["traceEvents"]
    out = capsys.readouterr().out
    assert "ttft_ms p50=" in out and "tokens/s/slot=" in out


def test_replay_with_contract_gate_and_telemetry(fp_model):
    """verify_contracts=True must stay green WITH telemetry attached —
    the hooks live host-side, outside every jit (PR 8 rules)."""
    cfg, params = fp_model
    tel = Telemetry()
    eng = ServingEngine(params, cfg, n_slots=2, max_len=32, min_bucket=8,
                        clock=StepClock(10.0), telemetry=tel,
                        verify_contracts=True)
    assert eng.contract_report.rules_run
    report = Replayer(eng, synthesize_trace(seed=0, steps=8,
                                            vocab=128)).run()
    validate_report(report)


def test_launch_cli_replay_and_exports(tmp_path, capsys, monkeypatch):
    """launch/serve.py end to end: --replay-trace drives the engine off a
    JSONL trace, --report-json / --telemetry-trace / --stats emit the
    report, a Perfetto-loadable trace, and the uniform metrics view."""
    from repro.launch import serve as launch_serve
    # the launcher then leaves the compile cache to the environment, which
    # this process read at start-up: the test writes nothing into the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    trace_p = tmp_path / "trace.jsonl"
    save_trace(str(trace_p), synthesize_trace(seed=4, steps=10, vocab=64,
                                              max_new=(3, 6)))
    rep_p = tmp_path / "report.json"
    pf_p = tmp_path / "perfetto.json"
    launch_serve.main(["--arch", "llama1_7b", "--smoke", "--bits", "3",
                       "--slots", "2", "--max-len", "48",
                       "--min-bucket", "8",
                       "--replay-trace", str(trace_p),
                       "--report-json", str(rep_p),
                       "--telemetry-trace", str(pf_p), "--stats"])
    report = validate_report(json.loads(rep_p.read_text()))
    assert report["ttft_ms"]["count"] > 0
    doc = json.loads(pf_p.read_text())
    tracks = [e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"]
    assert sorted(tracks) == ["engine", "queue", "slot 0", "slot 1"]
    out = capsys.readouterr().out
    assert "[serve metrics]" in out
    assert "serve.lifecycle.finished" in out
    # --stats carries the phase totals and the compile and GC counters
    assert "telemetry.phase.engine.decode.sync.count" in out
    assert "telemetry.compile.programs" in out
    assert "telemetry.host_gc.count" in out
