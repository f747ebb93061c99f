"""Telemetry layer (DESIGN.md §13): metrics registry units, per-request
span recording through the engine's lifecycle hooks, and the
Chrome/Perfetto trace export.

The structural contract under test: telemetry is OBSERVATION-ONLY (the
token stream with a recorder attached is bit-identical to one without),
every lifecycle edge emits exactly one structured event carrying both
the clock time and the engine step, histogram percentiles are
deterministic and always inside the observed [min, max], and the
Perfetto rendering is a loadable trace_event document with one named
track per slot plus a queue track.
"""
import dataclasses
import gc
import json
import math
import weakref

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import api
from repro.serve import (Histogram, MetricsRegistry, ServingEngine,
                         SpecConfig, StepClock, Telemetry, perfetto_trace,
                         registry_from_stats)
from repro.serve import telemetry as telemetry_mod
from repro.serve.telemetry import Timeline

jax.config.update("jax_platform_name", "cpu")

PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [9, 10, 11, 12, 13]]


@pytest.fixture(scope="module")
def fp_model():
    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(fp_model, telemetry=None, **kw):
    cfg, params = fp_model
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("clock", StepClock(10.0))
    return ServingEngine(params, cfg, telemetry=telemetry, **kw)


def _run(eng, prompts=PROMPTS, max_new=4):
    """Submit through the queue and step with the StepClock advancing —
    the deterministic driver loop every seeded latency test rides."""
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    while eng.active or len(eng.queue):
        eng.step()
        eng.clock.advance()
    fin = eng.take_finished()
    return {u: list(fin[u].tokens) for u in uids}


# ------------------------------------------------------------------- units

def test_histogram_percentiles_within_bucket_resolution():
    h = Histogram(lo=1e-3, hi=1e5, per_decade=8)
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    # a bucket spans 10**(1/8) ≈ 1.33x, so the reported midpoint is
    # within ~±16% of the exact rank value
    for q, exact in (("p50", 50.0), ("p90", 90.0), ("p99", 99.0)):
        assert s[q] == pytest.approx(exact, rel=0.2), (q, s)
    assert s["p50"] <= s["p90"] <= s["p99"]
    # percentiles are pure functions of the counts: re-query is identical
    assert h.percentile(0.5) == h.percentile(0.5)


def test_histogram_zero_underflow_and_overflow():
    h = Histogram(lo=1e-3, hi=10.0, per_decade=4)
    h.observe(0.0)
    h.observe(0.0)
    assert h.counts[0] == 2
    assert h.percentile(0.5) == 0.0        # clamped to observed min
    h.observe(1e9)                         # way past hi: overflow bucket
    assert h.counts[-1] == 1
    assert h.percentile(0.99) <= h.max     # clamp keeps it in range
    s = h.summary()
    assert s["max"] == 1e9 and s["min"] == 0.0


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError, match="histogram"):
        Histogram(lo=0.0)
    with pytest.raises(ValueError, match="histogram"):
        Histogram(lo=1.0, hi=0.5)
    with pytest.raises(ValueError, match="histogram"):
        Histogram(per_decade=0)


def test_registry_type_conflict_and_render():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("lat_ms").observe(2.0)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a")
    assert reg.names() == ["a", "b", "lat_ms"]
    out = reg.render(title="t")
    assert out.splitlines()[0] == "[t]"
    assert "  a: 3" in out and "lat_ms: n=1" in out
    # prefix filter restricts the report
    assert "lat_ms" not in reg.render(prefix="a")


def test_registry_from_stats_projects_nested_dicts():
    reg = registry_from_stats(
        {"steps": 7, "paged": {"pages_in_use": 3, "ladder": [1, 2]},
         "guards": True, "name": "x"})
    assert reg.get("serve.steps").value == 7
    assert reg.get("serve.paged.pages_in_use").value == 3
    assert reg.get("serve.guards").value == 1          # bool -> int
    assert reg.get("serve.name").value == "x"
    assert reg.get("serve.paged.ladder") is None       # lists skipped


def test_timeline_same_step_overwrites():
    tl = Timeline()
    tl.sample(0, 0.0, 1)
    tl.sample(1, 0.01, 2)
    tl.sample(1, 0.02, 5)                  # same engine step: overwrite
    s = tl.snapshot()
    assert s["n"] == 2 and s["values"] == [1.0, 5.0]
    assert s["last"] == 5.0 and s["max"] == 5.0


def test_telemetry_attach_is_single_use(fp_model):
    tel = Telemetry()
    _engine(fp_model, telemetry=tel)
    with pytest.raises(ValueError, match="already attached"):
        _engine(fp_model, telemetry=tel)


# ------------------------------------------------- engine instrumentation

def test_engine_emits_full_lifecycle_spans(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    toks = _run(eng)
    kinds = {e["kind"] for e in tel.events}
    assert {"submit", "admit", "first_token", "step", "retire"} <= kinds
    # every event carries the virtual-clock time AND the engine step
    assert all("t" in e and "step" in e for e in tel.events)
    assert len(tel.records) == len(PROMPTS)
    for uid, r in tel.records.items():
        assert r["state"] == "finished"
        assert r["tokens_out"] == len(toks[uid]) > 0
        assert r["submit_step"] <= r["admit_step"] <= r["first_token_step"]
        assert r["submit_t"] <= r["admit_t"] <= r["first_token_t"]
    # retirement feeds the latency histograms: one sample per request
    for name in ("ttft_ms", "queue_wait_ms"):
        assert tel.registry.histogram(name).count == len(PROMPTS)
    # under a StepClock the derived latencies are exact step multiples
    step_ms = 10.0
    for r in tel.records.values():
        ttft = (r["first_token_t"] - r["submit_t"]) * 1e3
        assert ttft == pytest.approx(
            (r["first_token_step"] - r["submit_step"]) * step_ms)


def test_telemetry_is_observation_only(fp_model):
    base = _run(_engine(fp_model))
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    instrumented = _run(eng)
    assert instrumented == base
    # the instrumented run really recorded its phases: one root per step
    roots = [s for s in tel.spans if s["name"] == "engine.step"]
    assert roots and tel.spans and tel.compiles
    assert len({s["step"] for s in roots}) == eng.engine_steps


class TickClock:
    """A clock that moves 1 ms on every read, so each span gets a
    distinct, deterministic, non-zero duration."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def _children(tel, span):
    return [s["name"] for s in tel.spans if s["parent"] == span["id"]]


def _by_id(tel):
    return {s["id"]: s for s in tel.spans}


def test_decode_step_spans_nest_under_root(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    eng.add_requests(PROMPTS[:2], max_new_tokens=4)
    n = len(tel.spans)
    eng.step()
    new = tel.spans[n:]
    roots = [s for s in new if s["name"] == "engine.step"]
    assert len(roots) == 1
    root = roots[0]
    assert root["parent"] is None and root["step"] == 0
    assert root["attrs"] == {"mode": "decode", "active": 2, "admitted": [],
                             "prompt_tokens": 0}
    assert _children(tel, root) == ["engine.pump", "engine.decode.dispatch",
                                    "engine.decode.sync",
                                    "engine.decode.emit"]
    # every span of the step is a direct child of the root, on its step,
    # and under a StepClock every duration is exactly 0
    assert all(s["parent"] == root["id"] for s in new if s is not root)
    assert all(s["step"] == 0 and s["start"] == s["end"] == 0.0
               for s in new)


def test_admission_spans_carry_group_and_durations(fp_model):
    tel = Telemetry(clock=TickClock())
    eng = _engine(fp_model, telemetry=tel)
    uids = [eng.submit(p, max_new_tokens=4) for p in PROMPTS[:2]]
    eng.step()
    admits = [s for s in tel.spans if s["name"] == "engine.admit"]
    assert len(admits) == 1
    adm = admits[0]
    # prompts of 3 and 4 tokens share bucket 8 in one 2-row prefill
    assert adm["attrs"] == {"uids": uids, "bucket": 8, "rows": 2,
                            "prompt_tokens": 7}
    assert _children(tel, adm) == ["engine.admit.fragment",
                                   "engine.admit.prefill",
                                   "engine.admit.first_token",
                                   "engine.admit.insert"]
    ids = _by_id(tel)
    pump = ids[adm["parent"]]
    root = ids[pump["parent"]]
    assert pump["name"] == "engine.pump" and root["name"] == "engine.step"
    assert root["attrs"]["admitted"] == uids
    assert root["attrs"]["prompt_tokens"] == 7
    # children lie inside their parent on the recorder's clock
    for s in tel.spans:
        if s["parent"] is not None:
            p = ids[s["parent"]]
            assert p["start"] < s["start"] <= s["end"] < p["end"]
    # the admit and step events take their durations from the spans
    ev_admit = [e for e in tel.events if e["kind"] == "admit"][0]
    ev_step = [e for e in tel.events if e["kind"] == "step"][0]
    assert ev_admit["dur"] == adm["end"] - adm["start"] > 0
    assert ev_step["dur"] == root["end"] - root["start"] > 0


def test_span_streams_are_byte_identical_across_seeded_runs(fp_model):
    def run():
        tel = Telemetry()
        _run(_engine(fp_model, telemetry=tel))
        return tel.spans

    s1, s2 = run(), run()
    assert len(s1) > 10
    assert json.dumps(s1) == json.dumps(s2)


def test_new_bucket_compiles_under_prefill_span(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    eng.add_requests([[1, 2, 3]], max_new_tokens=1)      # bucket 8
    n0 = len(tel.compiles)
    eng.add_requests([list(range(1, 11))], max_new_tokens=1)   # bucket 16
    prefill = [s for s in tel.spans
               if s["name"] == "engine.admit.prefill"][-1]
    under = [c for c in tel.compiles[n0:] if c["parent"] == prefill["id"]]
    assert [c["fun"] for c in under] == ["jit(_prefill_fn)"]
    rec = under[0]
    assert rec["step"] == prefill["step"]
    assert min(rec["trace_s"], rec["lower_s"], rec["compile_s"]) > 0
    assert rec["s"] == pytest.approx(
        rec["trace_s"] + rec["lower_s"] + rec["compile_s"])
    # the same (rows, bucket) shape again obtains no program at all
    n1 = len(tel.compiles)
    eng.add_requests([list(range(1, 13))], max_new_tokens=1)   # bucket 16
    assert tel.compiles[n1:] == []
    summ = tel.summary()["compile"]
    assert summ["programs"] == len(tel.compiles)
    assert summ["seconds"] == pytest.approx(sum(c["s"]
                                                for c in tel.compiles))


def test_gen2_collection_is_a_host_gc_child_span():
    tel = Telemetry()
    tel.attach(n_slots=1, clock=StepClock(10.0))
    with tel.span("outer", 3) as outer:
        gc.collect(2)
    pauses = [g for g in tel.gc_pauses if g["parent"] == outer["id"]]
    assert len(pauses) == 1
    assert pauses[0]["name"] == "host.gc" and pauses[0]["step"] == 3
    # a younger generation is not a pause this counter records
    n = len(tel.gc_pauses)
    gc.collect(0)
    assert len(tel.gc_pauses) == n
    assert tel.summary()["host_gc"]["count"] == n


def test_dropped_recorder_leaves_no_reference(fp_model):
    from jax._src import monitoring as jax_monitoring
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    _run(eng)
    tel_ref, eng_ref = weakref.ref(tel), weakref.ref(eng)
    del tel, eng
    gc.collect()
    assert tel_ref() is None and eng_ref() is None
    # the process hooks are installed once, however many recorders came
    assert gc.callbacks.count(telemetry_mod._on_gc) == 1
    assert jax_monitoring.get_event_duration_listeners().count(
        telemetry_mod._on_duration_event) == 1
    assert jax_monitoring.get_event_listeners().count(
        telemetry_mod._on_event) == 1
    assert all(t is not tel_ref() for t in telemetry_mod._live)


def test_preempt_resume_events_and_accounting(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel, on_pressure="preempt")
    uids = eng.add_requests(PROMPTS[:2], max_new_tokens=6)
    eng.step()
    eng.set_cache_pressure(3)              # below running fills: preempt
    eng.step()
    eng.set_cache_pressure(None)
    eng.run_to_completion()
    fin = eng.take_finished()
    assert all(fin[u].state.value == "finished" for u in uids)
    preempts = [e for e in tel.events if e["kind"] == "preempt"]
    resumes = [e for e in tel.events if e["kind"] == "resume"]
    assert preempts and resumes
    assert preempts[0]["reason"] and preempts[0]["uids"]
    # preempt events capture the slot BEFORE it is cleared
    assert all(s >= 0 for s in preempts[0]["slots"])
    assert sum(r["preemptions"] for r in tel.records.values()) >= 1
    # resume replays the already-generated prefix teacher-forced
    assert all(r["replayed"] >= 0 for r in resumes)


def test_spec_steps_carry_window_summaries(fp_model):
    cfg, params = fp_model
    draft = api.init_params(jax.random.PRNGKey(99), cfg)
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel, draft_params=draft,
                  spec=SpecConfig(gamma=2, draft_bits=2))
    toks = _run(eng)
    assert _run(_engine(fp_model)) == toks   # speculation stays lossless
    steps = [e for e in tel.events if e["kind"] == "step"]
    assert steps and all(e["mode"] == "spec" for e in steps)
    for e in steps:
        w = e["window"]
        assert w["gamma"] == 2
        assert 0 <= w["accepted"] <= w["proposed"]
        assert len(e["uids"]) == len(e["tokens"]) == len(e["slots"])
    assert tel.registry.histogram("spec_accepted_per_window").count > 0


def test_perfetto_trace_structure(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel, on_pressure="preempt")
    eng.add_requests(PROMPTS[:2], max_new_tokens=6)
    eng.step()
    eng.set_cache_pressure(3)
    eng.step()
    eng.set_cache_pressure(None)
    eng.run_to_completion()
    eng.take_finished()
    doc = perfetto_trace(tel)
    json.loads(json.dumps(doc))            # valid JSON document
    evs = doc["traceEvents"]
    tracks = {e["args"]["name"]: e["tid"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert sorted(tracks) == ["engine", "queue", "slot 0", "slot 1"]
    spans = [e for e in evs if e["ph"] == "X" and e["cat"] == "serve"]
    assert spans and all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    assert {e["name"] for e in spans} <= {"prefill", "decode", "spec",
                                          "resume"}
    # slot spans land on slot tracks (1..n_slots), never the queue track
    assert all(1 <= e["tid"] <= tel.n_slots for e in spans)
    names = {e["name"] for e in evs if e["ph"] == "i"}
    assert "submit" in names and "preempt" in names
    assert any(n.startswith("retire:") for n in names)
    counters = [e for e in evs if e["ph"] == "C"]
    assert {"queue_depth", "active_slots"} <= {e["name"] for e in counters}
    # phase spans: one X event per recorded span, all on the engine track
    phases = [e for e in evs if e["ph"] == "X" and e["cat"] == "engine"]
    assert len(phases) == len(tel.spans)
    assert all(e["tid"] == tracks["engine"] for e in phases)
    assert {"engine.step", "engine.decode.sync",
            "engine.admit.prefill"} <= {e["name"] for e in phases}
    compiles = [e for e in evs if e["ph"] == "i"
                and e["name"].startswith("compile:")]
    assert len(compiles) == len(tel.compiles)
    assert all(e["tid"] == tracks["engine"] for e in compiles)


def test_engine_metrics_consolidates_stats(fp_model):
    tel = Telemetry()
    eng = _engine(fp_model, telemetry=tel)
    _run(eng)
    reg = eng.metrics()
    assert reg is tel.registry             # one registry, not a copy
    assert reg.get("serve.engine_steps").value == eng.engine_steps
    assert reg.get("serve.lifecycle.finished").value == len(PROMPTS)
    out = reg.render()
    assert "serve.lifecycle.finished" in out and "ttft_ms" in out
    # works without telemetry too (fresh registry off stats())
    bare = _engine(fp_model)
    _run(bare)
    assert bare.metrics().get("serve.engine_steps").value > 0
