"""Serving-loop benchmark: vanilla vs self-speculative decode.

Wall times on this CPU container are NOT TPU estimates; the structural,
deterministic quantities are the deliverable: decode STEPS to drain a
request wave and accepted tokens per step (the decode-cadence multiplier
speculation buys), draft acceptance rate (how well a 2-bit CLAQ draft
tracks its higher-bit target when both come from ONE calibration pass),
and the compile counts proving the speculative path adds a constant
number of traces.  Greedy speculation is lossless, so the bench also
ASSERTS token parity between the vanilla and speculative engines — a
benchmark that cannot silently measure a broken configuration.

The substrate is benchmarks.common.trained_model(): a model trained until
it clearly beats unigram, so its logits are PEAKED — on a random-init
model any quantization noise flips the near-uniform argmax and acceptance
collapses to ~0, which measures nothing.  Target and draft are quantized
from the model's one set of tapped Hessians (the
`claq_quantize_with_draft` contract with calibration amortized).

The ROBUSTNESS scenario drives an engine through a seeded deterministic
fault plan (serve/faults.py: NaN/Inf logit injection, cache-pressure
windows forcing preemption+resume, bursty Poisson arrivals against a
bounded queue, transient step failures absorbed by bounded retry) and
ASSERTS the lifecycle contract instead of timing it: zero hangs, every
submitted request terminal, FINISHED requests' tokens bit-identical to a
clean engine's (including preempted-and-resumed ones), and an exact
replay under the same seed.  Counters (terminal states, preemptions,
resumes, backpressure) land in BENCH_serve.json next to the speed rows.
It runs on an fp smoke model — lifecycle behavior is numerics-blind, so
CI's `--inject-faults` mode skips the trained-model setup entirely.

The PAGED CAPACITY scenario fixes an HBM budget (the contiguous
layout's slot-cache bytes) and counts admissions before typed
backpressure under a shared system prompt: contiguous slots vs a paged
pool of the same byte size (DESIGN.md §11), fp and int8 resident pages.
It ASSERTS paged >= 2x contiguous and int8 >= paged fp, and the counts
land in BENCH_serve.json under ``paged_capacity``.

The REPLAY scenario (serve/replay.py, DESIGN.md §13) drives a seeded
synthesized arrival trace through a telemetry-instrumented engine under
a pressure-window fault plan and records the scheduling report —
TTFT/TPOT p50/p90/p99, tokens/s/slot, queue-depth and page-occupancy
timelines — under ``results["replay"]``.  It ASSERTS that the telemetry
hooks are observation-only: the telemetry-on and telemetry-off token
streams must be bit-identical, and the preempt/resume path must have
actually fired (a latency report over an idle engine proves nothing).

`serve_bench()` writes BENCH_serve.json at the repo root (the serving
trajectory's counterpart to BENCH_kernel.json); CI runs `--smoke` and
the fault-injection smoke `--smoke --inject-faults`.

  PYTHONPATH=src python -m benchmarks.serve_bench --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

from repro.core import APConfig, CLAQConfig, ORConfig, draft_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.quantize import quantize_model_params
from repro.serve import (AdmissionRejected, FaultInjector, Replayer,
                         RetryPolicy, ServingEngine, SpecConfig, StepClock,
                         Telemetry, synthesize_trace, validate_report)

_BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json")

GAMMAS = (2, 4)


def _run(eng, prompts, max_new):
    """Admit everything, decode to completion; returns (tokens in prompt
    order, steps, decode seconds)."""
    uids = eng.add_requests(prompts, max_new_tokens=max_new)
    steps = 0
    t_decode = 0.0
    while eng.active:
        t0 = time.perf_counter()
        eng.step()
        t_decode += time.perf_counter() - t0
        steps += 1
    fin = eng.take_finished()
    return [fin[u].tokens for u in uids], steps, t_decode


def robustness_scenario(smoke: bool = False, seed: int = 0) -> dict:
    """Seeded fault-plan survival run (see module docstring).  Returns the
    counters recorded under ``results["robustness"]``; raises on any
    lifecycle-contract violation (hang, non-terminal request, parity break,
    replay divergence) so CI cannot silently pass a broken engine."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import api

    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)

    horizon = 24 if smoke else 48
    # pressure_frac is tuned so the windows' limit (frac * max_len) falls
    # BELOW running fills — a pressure window that never preempts anything
    # would record a vacuous survival
    injector_kw = dict(seed=seed, horizon=horizon, arrival_lambda=0.25,
                       burst_every=10, burst_size=2, pressure_windows=2,
                       pressure_frac=(0.15, 0.25))
    max_new = 8 if smoke else 12
    n_slots, max_len = 3, 48
    prng = np.random.default_rng(1)
    prompts = [prng.integers(1, cfg.vocab,
                             size=prng.integers(3, 11)).tolist()
               for _ in range(4 * horizon)]

    def engine(**kw):
        return ServingEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                             min_bucket=8, **kw)

    def run_once():
        injector = FaultInjector(**injector_kw)
        clock = StepClock(step_ms=10.0)
        eng = engine(guards=True, faults=injector, clock=clock,
                     queue_depth=4, on_pressure="preempt")
        retry = RetryPolicy(max_attempts=4, backoff_s=0.0)
        submitted = []                       # (uid, prompt index)
        pending = []
        next_idx = 0
        retries = backpressure = 0
        step = 0
        max_steps = 40 * horizon             # hang budget, far above need
        while step < max_steps:
            if step < injector.horizon:
                for _ in range(injector.arrivals(step)):
                    pending.append(next_idx)
                    next_idx += 1
            while pending:
                idx = pending[0]
                # every third request carries a tight SLO: under queueing
                # and pressure windows some of these MUST abandon
                dl = 150.0 if idx % 3 == 2 else None
                try:
                    uid = eng.submit(prompts[idx], max_new_tokens=max_new,
                                     deadline_ms=dl)
                except AdmissionRejected:
                    backpressure += 1        # bounded queue pushed back
                    break
                submitted.append((uid, idx))
                pending.pop(0)
            _, r = retry.run(eng.step)
            retries += r
            clock.advance()
            step += 1
            if (step >= injector.horizon and not pending and not eng.active
                    and not len(eng.queue)):
                break
        fin = eng.take_finished()
        outcome = [(idx,
                    fin[uid].state.value if uid in fin else "nonterminal",
                    list(fin[uid].tokens) if uid in fin else None)
                   for uid, idx in submitted]
        return {"outcome": outcome, "stats": eng.stats(),
                "retries": retries, "backpressure": backpressure,
                "steps": step, "hang": step >= max_steps}

    r1 = run_once()
    assert not r1["hang"], (
        f"robustness scenario did not drain in {r1['steps']} driver steps")
    assert all(state != "nonterminal" for _, state, _ in r1["outcome"]), (
        f"non-terminal requests survived the run: {r1['outcome']}")

    # exact replay: same seed -> bit-identical outcomes and counters
    r2 = run_once()
    assert r1["outcome"] == r2["outcome"], "seeded fault plan did not replay"
    assert r1["stats"]["lifecycle"] == r2["stats"]["lifecycle"]
    assert r1["retries"] == r2["retries"]

    # FINISHED parity: a clean engine over the same prompts must emit the
    # same tokens — in particular for requests preempted and resumed
    fin_idx = [idx for idx, state, _ in r1["outcome"] if state == "finished"]
    assert fin_idx, "no request finished under the fault plan"
    clean = engine()
    base = {}
    for i in range(0, len(fin_idx), n_slots):
        chunk = fin_idx[i:i + n_slots]
        uids = clean.add_requests([prompts[j] for j in chunk],
                                  max_new_tokens=max_new)
        clean.run_to_completion()
        fin = clean.take_finished()
        for j, u in zip(chunk, uids):
            base[j] = fin[u].tokens
    for idx, state, toks in r1["outcome"]:
        if state == "finished":
            assert toks == base[idx], (
                f"request {idx} finished with divergent tokens under "
                f"faults: {toks} vs clean {base[idx]}")

    st = r1["stats"]
    # the plan must have actually exercised the preemption/resume path —
    # a survival claim over faults that never fired proves nothing
    assert st["preemptions"] >= 1 and st["resumes"] >= 1, (
        f"fault plan never preempted (preemptions={st['preemptions']}, "
        f"resumes={st['resumes']}): scenario is vacuous, retune "
        f"pressure_frac")
    assert r1["retries"] >= 1, "no transient step failure was retried"
    return {
        "plan": FaultInjector(**injector_kw).describe(),
        "submitted": len(r1["outcome"]),
        "driver_steps": r1["steps"],
        "lifecycle": st["lifecycle"],
        "preemptions": st["preemptions"],
        "resumes": st["resumes"],
        "admission_rejections": st["admission_rejections"],
        "backpressure_waits": r1["backpressure"],
        "transient_retries": r1["retries"],
        "finished": len(fin_idx),
        "finished_parity": True,
        "deterministic_replay": True,
        "all_terminal": True,
    }


def replay_scenario(smoke: bool = False, seed: int = 0) -> dict:
    """Trace-driven replay under a preempt/resume storm (see module
    docstring).  Returns the scheduling-report subset recorded under
    ``results["replay"]``; raises if telemetry perturbs the token stream
    or the pressure plan never preempted."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import api

    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    steps = 16 if smoke else 32
    trace = synthesize_trace(seed=seed, steps=steps, vocab=cfg.vocab,
                             max_new=(4, 9))

    def run(telemetry):
        # pressure-only fault plan: deterministic preempt/resume churn,
        # no numeric faults (latency accounting, not quarantine, is under
        # test here)
        injector = FaultInjector(seed=seed + 7, horizon=max(16, steps),
                                 nan_faults=0, inf_faults=0,
                                 transient_failures=0, pressure_windows=2,
                                 pressure_frac=(0.15, 0.25))
        eng = ServingEngine(params, cfg, n_slots=3, max_len=48,
                            min_bucket=8, clock=StepClock(step_ms=10.0),
                            faults=injector, on_pressure="preempt",
                            telemetry=telemetry)
        rep = Replayer(eng, trace).run()
        fin = eng.take_finished()
        return rep, {u: list(r.tokens) for u, r in fin.items()}

    rep_off, toks_off = run(None)
    assert rep_off is None       # no telemetry -> no report, by contract
    report, toks_on = run(Telemetry())
    validate_report(report)
    # the hooks must be observation-only: bit-identical token streams
    assert toks_on == toks_off, (
        "telemetry-on token stream diverged from telemetry-off")
    sched = report["scheduling"]
    assert sched["preemptions"] >= 1 and sched["resumes"] >= 1, (
        f"pressure plan never preempted (preemptions="
        f"{sched['preemptions']}, resumes={sched['resumes']}): replay "
        f"scenario is vacuous, retune pressure_frac")
    assert report["ttft_ms"]["count"] >= 1, "no request reached a first token"
    return {
        "trace": report["trace"],
        "requests": report["requests"],
        "ttft_ms": report["ttft_ms"],
        "tpot_ms": report["tpot_ms"],
        "queue_wait_ms": report["queue_wait_ms"],
        "tokens": report["tokens"],
        "scheduling": sched,
        "driver_steps": report["driver_steps"],
        "telemetry_parity": True,
    }


def paged_capacity_scenario(smoke: bool = False) -> dict:
    """Admission capacity at a FIXED HBM budget: contiguous slots vs a
    paged pool of the same byte size (fp and int8 resident pages), under
    a common system prompt.  Counts requests admitted before typed
    backpressure (AdmissionRejected / PoolExhausted) with no decoding —
    pure cache-capacity accounting, deterministic by construction.

    The contiguous layout pins n_slots * max_len positions no matter how
    short the requests are; the paged layout pins only the pages each
    request touches, prefix sharing collapses the common system prompt to
    ONE physical copy, and int8 pages fit ~4x the tokens per byte.  The
    scenario ASSERTS paged >= 2x contiguous and int8 >= paged fp, so CI
    cannot silently regress the capacity win."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import api as mapi

    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = mapi.init_params(jax.random.PRNGKey(0), cfg)
    n_slots, max_len, ps = 4, 64, 8
    max_new = 4
    sys_prompt = list(range(1, 25))          # 24-token shared system prompt

    def admit_until_full(eng, budget):
        count = 0
        for i in range(budget):
            try:
                eng.add_request(sys_prompt + [30 + i % (cfg.vocab - 31)],
                                max_new_tokens=max_new)
            except AdmissionRejected:        # PoolExhausted subclasses it
                break
            count += 1
        return count

    def paged(n_pages, kv_dtype=None, share=True):
        # slots are table rows (tiny) for the paged layout — size the slot
        # count so only the PAGE POOL can be the binding constraint
        return ServingEngine(params, cfg, n_slots=n_pages,
                             max_len=max_len, min_bucket=8, prepare=False,
                             kv_layout="paged", page_size=ps,
                             kv_pages=n_pages, kv_dtype=kv_dtype,
                             share_prefixes=share)

    contig = ServingEngine(params, cfg, n_slots=n_slots, max_len=max_len,
                           min_bucket=8, prepare=False)
    cap_contig = admit_until_full(contig, 4 * n_slots)

    pool_fp = n_slots * (max_len // ps)      # capacity-equivalent fp pool
    probe_fp = paged(pool_fp).stats()["paged"]
    hbm_budget = pool_fp * probe_fp["bytes_per_page"]
    probe_i8 = paged(pool_fp, kv_dtype="int8").stats()["paged"]
    pool_i8 = hbm_budget // probe_i8["bytes_per_page"]

    budget = 4 * pool_i8
    cap_fp_noshare = admit_until_full(paged(pool_fp, share=False), budget)
    eng_fp = paged(pool_fp)
    cap_fp = admit_until_full(eng_fp, budget)
    eng_i8 = paged(pool_i8, kv_dtype="int8")
    cap_i8 = admit_until_full(eng_i8, budget)

    assert cap_fp >= 2 * cap_contig, (
        f"paged fp capacity {cap_fp} < 2x contiguous {cap_contig} at the "
        f"same HBM budget — the paged layout lost its capacity win")
    assert cap_i8 >= cap_fp, (
        f"int8-page capacity {cap_i8} < paged fp {cap_fp} — int8 pages "
        f"stopped paying for themselves")
    st = eng_fp.stats()["paged"]
    return {
        "n_slots_contiguous": n_slots, "max_len": max_len,
        "page_size": ps, "system_prompt_tokens": len(sys_prompt),
        "hbm_budget_bytes": int(hbm_budget),
        "pool_pages": {"fp": pool_fp, "int8": int(pool_i8)},
        "capacity": {"contiguous": cap_contig,
                     "paged_fp_noshare": cap_fp_noshare,
                     "paged_fp": cap_fp, "paged_int8": cap_i8},
        "paged_fp_stats": {k: st[k] for k in
                           ("prefix_hits", "prefix_shared_tokens",
                            "pages_in_use", "pool_utilization")},
    }


def overload_scenario(smoke: bool = False, seed: int = 0) -> dict:
    """Overload control-plane A/B/C (DESIGN.md §14): one seeded burst
    trace through three engines under the SAME deterministic step-cost
    model — no controller, admission-only ([nominal, shed]), and the
    full degradation ladder — recording p99 TTFT/TPOT, goodput
    (finished tokens per virtual second) and shed rate for each.

    ASSERTS the control claim instead of just charting it: the
    uncontrolled baseline must VIOLATE the p99 TTFT target, the full
    ladder must MEET it, and the full ladder must do so at
    equal-or-better goodput than admission-only shedding (degrading
    before abandoning is the whole point of the ladder)."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.serve import AdmissionController, SLOConfig, StepCostModel
    from repro.serve.replay import overload_trace

    cfg = dataclasses.replace(get_smoke_config("llama1_7b"), vocab=128,
                              n_layers=2)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    target = 250.0
    # ONE pinned storm for smoke and full mode: the trace is a seeded
    # artifact the assertions are tuned against, not a scale knob
    steps = 32
    trace = overload_trace(seed=seed, steps=steps, vocab=cfg.vocab)
    cost = StepCostModel()

    def arm(controller=None, chunk=None):
        eng = ServingEngine(params, cfg, n_slots=3, max_len=64,
                            min_bucket=8, clock=StepClock(step_ms=10.0),
                            telemetry=Telemetry(), queue_depth=48,
                            chunked_prefill=chunk, controller=controller,
                            cost_model=cost)
        t0 = eng.clock()
        report = Replayer(eng, trace, retry=RetryPolicy(backoff_s=0.0)).run()
        validate_report(report)
        elapsed_s = eng.clock() - t0
        fin = eng.take_finished()
        good = sum(len(r.tokens) for r in fin.values()
                   if r.state.value == "finished")
        out = {
            "ttft_p99_ms": report["ttft_ms"]["p99"],
            "ttft_count": report["ttft_ms"]["count"],
            "tpot_p99_ms": report["tpot_ms"]["p99"],
            "goodput_tok_per_s": good / max(elapsed_s, 1e-9),
            "finished_tokens": good,
            "elapsed_virtual_s": elapsed_s,
            "submitted": len(fin),
            "sheds": controller.sheds if controller else 0,
            "shed_rate": (controller.sheds / max(len(fin), 1)
                          if controller else 0.0),
        }
        if controller is not None:
            out["controller"] = controller.stats()
        return out

    slo = SLOConfig(ttft_p99_ms=target)
    base = arm()
    adm = arm(AdmissionController(slo, mode="admission"), chunk=8)
    full = arm(AdmissionController(slo, mode="full"), chunk=8)

    assert base["ttft_p99_ms"] > target, (
        f"baseline p99 TTFT {base['ttft_p99_ms']:.1f}ms already meets "
        f"the {target:.0f}ms target: the overload storm is not a storm")
    assert full["ttft_p99_ms"] <= target, (
        f"full-ladder p99 TTFT {full['ttft_p99_ms']:.1f}ms misses the "
        f"{target:.0f}ms target the controller exists to defend")
    assert full["goodput_tok_per_s"] >= adm["goodput_tok_per_s"], (
        f"full ladder goodput {full['goodput_tok_per_s']:.1f} tok/s < "
        f"admission-only {adm['goodput_tok_per_s']:.1f} tok/s — "
        f"degrading before shedding stopped paying for itself")
    assert full["controller"]["rung_changes"] > 0 and (
        full["sheds"] > 0 or full["controller"]["defers"] > 0), (
        "vacuous full-ladder run: the controller never acted")
    return {
        "ttft_p99_ms_target": target,
        "trace": {"arrivals": len(trace), "seed": seed, "steps": steps},
        "baseline": base,
        "admission_only": adm,
        "full_ladder": full,
        "slo_defended": True,
    }


def serve_bench(out_json: str = _BENCH_JSON, smoke: bool = False,
                faults_only: bool = False):
    if faults_only:
        # CI fault-injection smoke: lifecycle contract only, no trained
        # model, no timing rows
        rob = robustness_scenario(smoke=smoke)
        results = {"smoke": smoke, "faults_only": True, "robustness": rob}
        with open(out_json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"serve/robustness,{rob['driver_steps']},"
              f"submitted={rob['submitted']};finished={rob['finished']};"
              f"preemptions={rob['preemptions']};resumes={rob['resumes']};"
              f"lifecycle={json.dumps(rob['lifecycle'])}")
        return []
    from benchmarks.common import trained_model

    cfg, params, hessians = trained_model()
    qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=4,
                      gptq_blocksize=32, ap=APConfig(2.2, 2, 4),
                      orr=ORConfig(0.1))
    t0 = time.perf_counter()
    qparams, rep = quantize_model_params(params, cfg, hessians, qcfg)
    dparams, drep = quantize_model_params(params, cfg, hessians,
                                          draft_config(qcfg, 2))
    t_quant = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    n_req = 4 if smoke else 8
    max_new = 12 if smoke else 24
    prompts = [rng.integers(1, cfg.vocab, size=rng.integers(3, 14)).tolist()
               for _ in range(n_req)]

    def make(spec=None, **kw):
        return ServingEngine(
            qparams, cfg, n_slots=n_req, max_len=64, min_bucket=8,
            draft_params=dparams if spec else None, spec=spec, **kw)

    rows = []
    results = {
        "model": {"arch": "llama1_7b-smoke-trained",
                  "n_layers": cfg.n_layers, "vocab": cfg.vocab,
                  "d_model": cfg.d_model},
        "target_bits": rep.mean_effective_bits,
        "draft_bits": drep.mean_effective_bits,
        "quantize_pair_s": t_quant,
        "requests": n_req,
        "max_new": max_new,
        "smoke": smoke,
    }

    base_tokens, steps, secs = _run(make(), prompts, max_new)
    total = sum(len(t) for t in base_tokens)
    results["vanilla"] = {
        "tokens": total, "steps": steps,
        "tokens_per_step": total / steps,
        "ms_per_step": secs / steps * 1e3,
    }
    rows.append(("serve/decode_vanilla", secs / steps * 1e6,
                 f"steps={steps};tokens_per_step={total / steps:.2f}"))

    for gamma in GAMMAS:
        eng = make(SpecConfig(gamma=gamma, draft_bits=2))
        toks, steps, secs = _run(eng, prompts, max_new)
        # greedy speculation is LOSSLESS — a divergence means the bench is
        # measuring a bug, so fail loudly instead of recording it
        assert toks == base_tokens, (
            f"speculative gamma={gamma} diverged from vanilla greedy")
        st = eng.stats()
        total = sum(len(t) for t in toks)
        results[f"spec_gamma{gamma}"] = {
            "tokens": total, "steps": steps,
            "tokens_per_step": total / steps,
            "ms_per_step": secs / steps * 1e3,
            "acceptance_rate": st["acceptance_rate"],
            "verify_traces": st["verify_traces"],
            "draft_decode_traces": st["draft_decode_traces"],
        }
        rows.append((f"serve/decode_spec_gamma{gamma}", secs / steps * 1e6,
                     f"steps={steps};"
                     f"tokens_per_step={total / steps:.2f};"
                     f"acceptance={st['acceptance_rate']:.2f}"))

    # draft-specific plan tiles (ROADMAP spec item b): the 2-bit draft's
    # groups are skinnier than the target's, so its plans get their own
    # bn cap — losslessness is tile-independent, so parity still ASSERTS,
    # and the recorded delta is pure plan-tile effect on the draft chain
    gamma = GAMMAS[0]
    eng = make(SpecConfig(gamma=gamma, draft_bits=2), draft_plan_bn=32)
    toks, steps, secs = _run(eng, prompts, max_new)
    assert toks == base_tokens, (
        f"draft_plan_bn=32 gamma={gamma} diverged from vanilla greedy")
    st = eng.stats()
    total = sum(len(t) for t in toks)
    results[f"spec_gamma{gamma}_draft_bn32"] = {
        "tokens": total, "steps": steps,
        "tokens_per_step": total / steps,
        "ms_per_step": secs / steps * 1e3,
        "acceptance_rate": st["acceptance_rate"],
    }
    rows.append((f"serve/decode_spec_gamma{gamma}_draft_bn32",
                 secs / steps * 1e6,
                 f"steps={steps};tokens_per_step={total / steps:.2f};"
                 f"acceptance={st['acceptance_rate']:.2f}"))

    cap = paged_capacity_scenario(smoke=smoke)
    results["paged_capacity"] = cap
    rows.append(("serve/paged_capacity", float(cap["capacity"]["paged_fp"]),
                 f"contiguous={cap['capacity']['contiguous']};"
                 f"paged_fp={cap['capacity']['paged_fp']};"
                 f"paged_int8={cap['capacity']['paged_int8']};"
                 f"hbm_bytes={cap['hbm_budget_bytes']}"))

    rob = robustness_scenario(smoke=smoke)
    results["robustness"] = rob
    rows.append(("serve/robustness", float(rob["driver_steps"]),
                 f"submitted={rob['submitted']};"
                 f"finished={rob['finished']};"
                 f"preemptions={rob['preemptions']};"
                 f"resumes={rob['resumes']};"
                 f"abandoned={rob['lifecycle']['abandoned']};"
                 f"failed={rob['lifecycle']['failed']}"))

    ov = overload_scenario(smoke=smoke)
    results["overload"] = ov
    rows.append(("serve/overload", ov["full_ladder"]["ttft_p99_ms"],
                 f"target={ov['ttft_p99_ms_target']:.0f};"
                 f"base_p99={ov['baseline']['ttft_p99_ms']:.1f};"
                 f"adm_p99={ov['admission_only']['ttft_p99_ms']:.1f};"
                 f"full_p99={ov['full_ladder']['ttft_p99_ms']:.1f};"
                 f"full_goodput={ov['full_ladder']['goodput_tok_per_s']:.1f};"
                 f"adm_goodput="
                 f"{ov['admission_only']['goodput_tok_per_s']:.1f};"
                 f"full_shed_rate={ov['full_ladder']['shed_rate']:.2f}"))

    rp = replay_scenario(smoke=smoke)
    results["replay"] = rp
    rows.append(("serve/replay", rp["ttft_ms"]["p50"],
                 f"ttft_p50={rp['ttft_ms']['p50']:.2f};"
                 f"ttft_p99={rp['ttft_ms']['p99']:.2f};"
                 f"tpot_p50={rp['tpot_ms']['p50']:.2f};"
                 f"tok_s_slot={rp['tokens']['per_s_per_slot']:.2f};"
                 f"preemptions={rp['scheduling']['preemptions']}"))

    with open(out_json, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small request count / budgets (CI mode)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="run ONLY the seeded fault-plan robustness "
                         "scenario (CI fault-injection smoke: asserts "
                         "zero hangs and every request terminal)")
    ap.add_argument("--out", default=_BENCH_JSON)
    args = ap.parse_args()
    use_compile_cache()
    serve_bench(out_json=args.out, smoke=args.smoke,
                faults_only=args.inject_faults)


if __name__ == "__main__":
    main()
