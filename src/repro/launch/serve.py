"""Serving launcher: quantize (optional) + batched engine demo.

  PYTHONPATH=src python -m repro.launch.serve --arch llama1_7b --smoke \
      --bits 3 --requests 8

Multi-device serving: ``--mesh-shape 2x4`` (or ``--dp 2 --tp 4``) builds a
(data, model) mesh and wires the engine onto it — prepared CLAQ plans
shard along N over "model" (whole (bn, bk) tile groups per shard), the
slot cache shards over "dp".  On a single host, force device count first:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Lifecycle / robustness knobs (DESIGN.md §10): ``--queue-depth`` bounds
the admission queue (requests beyond it see backpressure and wait in the
launcher), ``--deadline-ms`` attaches an SLO deadline to every request
(expired work is ABANDONED, queued or running), ``--guards`` folds the
per-step finite check into the decode jit (non-finite rows quarantine
only their own request), and ``--inject-faults`` drives the whole thing
with a seeded deterministic fault plan (NaN/Inf logits, cache-pressure
windows forcing preemption+resume, transient step failures absorbed by
bounded retry) — the demo must end with every request terminal.

Telemetry / replay (DESIGN.md §13): ``--telemetry`` attaches the
per-request event and engine phase-span recorder (zero overhead when
off), ``--replay-trace
trace.jsonl`` drives submissions from a JSONL arrival trace instead of
``--requests`` (synthesize one with ``python -m repro.serve.replay``),
``--report-json out.json`` writes the end-of-run scheduling report
(TTFT/TPOT p50/p90/p99, tokens/s/slot, queue/occupancy timelines,
preemption accounting), ``--telemetry-trace out.json`` writes a
Chrome/Perfetto ``trace_event`` file (one track per slot and an
``engine`` track of nested phase spans with GC and compile instants —
open it at ui.perfetto.dev), and ``--stats`` prints every engine counter,
the phase totals and the compile and GC counters through the ONE
uniform metrics registry instead of ad-hoc dicts.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import APConfig, CLAQConfig, ORConfig
from repro.data import calibration_set
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.quantize import claq_quantize, claq_quantize_with_draft
from repro.models import api
from repro.serve import (AdmissionController, AdmissionRejected,
                         FaultInjector, Replayer, RetryPolicy, ServingEngine,
                         SLOConfig, SpecConfig, StepClock, StepCostModel,
                         Telemetry, build_report, load_trace,
                         write_perfetto)


def _build_mesh(args):
    """Resolve --mesh-shape / --dp / --tp into a (data, model) mesh, or
    None for single-device serving."""
    if args.mesh_shape:
        try:
            dp, tp = (int(v) for v in args.mesh_shape.lower().split("x"))
        except ValueError as e:
            raise SystemExit(
                f"--mesh-shape must be DPxTP (e.g. 2x4), got "
                f"{args.mesh_shape!r}") from e
    else:
        dp, tp = max(args.dp, 1), max(args.tp, 1)
    if dp * tp <= 1:
        return None
    n_dev = len(jax.devices())
    if dp * tp > n_dev:
        raise SystemExit(
            f"mesh {dp}x{tp} needs {dp * tp} devices, have {n_dev} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"to emulate on one host)")
    return make_mesh((dp, tp), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--bits", type=float, default=0,
                    help="0 = fp; else CLAQ-quantize to this avg bit-width")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--min-bucket", type=int, default=16,
                    help="smallest prefill length bucket")
    ap.add_argument("--no-bucketing", action="store_true",
                    help="admit at exact prompt lengths (one compile each)")
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="speculation window length; 0 = vanilla decode. "
                         ">0 quantizes a low-bit draft of the same "
                         "checkpoint from the same calibration pass and "
                         "serves with propose/verify/rollback windows "
                         "(lossless for greedy decoding)")
    ap.add_argument("--draft-bits", type=int, default=2,
                    help="code bit-width of the speculative draft model")
    ap.add_argument("--draft-plan-bn", type=int, default=0,
                    help="plan N-tile cap for the DRAFT's prepared plans "
                         "(0 = inherit the target's; the 2-bit draft's "
                         "skinnier groups often want smaller tiles)")
    ap.add_argument("--draft-plan-bk", type=int, default=0,
                    help="plan K-block cap for the draft's prepared plans "
                         "(0 = inherit)")
    ap.add_argument("--act-dtype", choices=("f32", "int8"), default="f32",
                    help="activation precision for quantized matmuls: int8 "
                         "= per-token dynamic absmax quantization folded "
                         "into the fused kernel (opt-in; changes numerics "
                         "within the documented bound, DESIGN.md §9)")
    ap.add_argument("--kv-layout", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="slot KV cache layout: contiguous (one "
                         "(max_len,) strip per slot) or paged (global "
                         "page pool + per-slot page tables; pages "
                         "allocated on demand, freed at retirement, "
                         "shared across common prompt prefixes — "
                         "DESIGN.md §11)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout; must divide "
                         "--max-len)")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="page-pool size (paged layout; 0 = capacity-"
                         "equivalent to contiguous: slots * max_len / "
                         "page_size).  Larger overcommits admission "
                         "against typed PoolExhausted backpressure")
    ap.add_argument("--kv-dtype", choices=("f32", "int8"), default="f32",
                    help="resident-page precision (paged layout): int8 "
                         "stores K/V quantized per token row with absmax "
                         "scales (~4x tokens per byte, bounded error, "
                         "no preemption)")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="bounded admission queue depth (0 = engine "
                         "default, 2x slots); submissions beyond it see "
                         "typed backpressure and wait in the launcher")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="per-request SLO deadline; expired work is "
                         "ABANDONED (queued or running), 0 = none")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked prefill: split admitted prompts' prefill "
                         "into fixed chunks of this many tokens interleaved "
                         "with decode (0 = monolithic; must divide "
                         "--max-len; bitwise-identical token streams, "
                         "DESIGN.md §14)")
    ap.add_argument("--slo-ttft-p99-ms", type=float, default=0,
                    help="attach the SLO-guarded admission controller "
                         "defending this p99 TTFT target via the "
                         "graceful-degradation ladder (0 = off)")
    ap.add_argument("--controller-mode", choices=("admission", "full"),
                    default="full",
                    help="controller ladder: 'admission' = defer/shed "
                         "only; 'full' adds spec_half/spec_off/kv_int8 "
                         "degradation rungs (capability-gated)")
    ap.add_argument("--cost-model", action="store_true",
                    help="price each step from the work it ran (padded "
                         "prefill tokens, decode/draft calls, verify "
                         "span) and advance the virtual clock by it — "
                         "implied by --slo-ttft-p99-ms")
    ap.add_argument("--guards", action="store_true",
                    help="fold a per-step finite check into the decode "
                         "jit; a non-finite row quarantines only its own "
                         "request (FAILED + diagnostics)")
    ap.add_argument("--on-pressure", choices=("preempt", "truncate"),
                    default="preempt",
                    help="cache-pressure policy: preempt (evict + resume "
                         "bit-identically, default) or truncate (opt-in "
                         "legacy behavior)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="drive the run under a seeded deterministic "
                         "fault plan (NaN/Inf logits, pressure windows, "
                         "transient step failures); implies --guards and "
                         "a virtual clock so outcomes replay exactly")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the injected fault plan")
    ap.add_argument("--verify-contracts", action="store_true",
                    help="run the repro.analysis contract rules over the "
                         "engine's compiled artifacts at init and refuse "
                         "to serve on any ERROR finding (DESIGN.md §12)")
    ap.add_argument("--mesh-shape", default=None,
                    help="DPxTP device mesh, e.g. 2x4 (data x model)")
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel mesh size (alternative to "
                         "--mesh-shape)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor(model)-parallel mesh size")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the telemetry recorder "
                         "(serve/telemetry.py) — structured lifecycle "
                         "events, TTFT/TPOT histograms, engine phase "
                         "spans, compile and GC counters; host-side only, "
                         "zero overhead when off")
    ap.add_argument("--replay-trace", metavar="PATH",
                    help="drive submissions from this JSONL arrival trace "
                         "instead of --requests (implies --telemetry; "
                         "synthesize a trace with `python -m "
                         "repro.serve.replay`)")
    ap.add_argument("--report-json", metavar="PATH",
                    help="write the end-of-run scheduling report here — "
                         "TTFT/TPOT p50/p90/p99, tokens/s/slot, timelines, "
                         "preemption accounting (implies --telemetry)")
    ap.add_argument("--telemetry-trace", metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON here — "
                         "one track per slot, spans for prefill/decode/"
                         "spec/resume, and an engine track of nested "
                         "phase spans with GC and compile instants; open "
                         "at ui.perfetto.dev (implies --telemetry)")
    ap.add_argument("--stats", action="store_true",
                    help="print the uniform metrics report at exit (every "
                         "stats() counter, and with --telemetry the phase "
                         "totals and compile/GC counters, through the "
                         "metrics registry)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = api.init_params(jax.random.PRNGKey(0), cfg)

    spec = (SpecConfig(gamma=args.spec_gamma, draft_bits=args.draft_bits)
            if args.spec_gamma > 0 else None)
    draft_params = None
    if args.bits > 0:
        base = int(args.bits)
        qcfg = CLAQConfig(
            bits=base, method="kmeans", kmeans_iters=6, gptq_blocksize=32,
            ap=(APConfig(args.bits, base, 4) if args.bits != base else None))
        calib = calibration_set(cfg.vocab, n_segments=8, seq_len=64)
        t0 = time.time()
        if spec is not None:
            # one calibration pass, two quantizations: the serving target
            # and the low-bit speculative draft share the tapped Hessians
            (params, report), (draft_params, drep) = claq_quantize_with_draft(
                params, cfg, calib, qcfg, draft_bits=spec.draft_bits)
            print(f"[serve] CLAQ-quantized target to "
                  f"{report.mean_effective_bits:.2f} bits + draft to "
                  f"{drep.mean_effective_bits:.2f} bits in "
                  f"{time.time() - t0:.1f}s (one calibration pass)")
        else:
            params, report = claq_quantize(params, cfg, calib, qcfg)
            print(f"[serve] CLAQ-quantized to "
                  f"{report.mean_effective_bits:.2f} "
                  f"bits in {time.time() - t0:.1f}s")
    elif spec is not None:
        # fp target: the draft is still a CLAQ quantization of the same
        # weights, with Outlier Reservation kept — the cheap accuracy
        # lever that keeps the draft's argmax tracking the target
        # (core.draft_config's contract)
        calib = calibration_set(cfg.vocab, n_segments=8, seq_len=64)
        dcfg = CLAQConfig(bits=args.draft_bits, method="kmeans",
                          kmeans_iters=6, gptq_blocksize=32,
                          orr=ORConfig(0.1))
        draft_params, drep = claq_quantize(params, cfg, calib, dcfg)
        print(f"[serve] fp target + {drep.mean_effective_bits:.2f}-bit "
              f"CLAQ draft")

    mesh = _build_mesh(args)
    if mesh is not None:
        print(f"[serve] mesh {dict(mesh.shape)} over {mesh.size} devices")

    injector = None
    clock = None
    if args.inject_faults:
        # faults imply guards (NaN injection must quarantine, not poison)
        # and a virtual clock (deadline outcomes must replay exactly)
        injector = FaultInjector(seed=args.fault_seed)
        clock = StepClock()
        print(f"[serve] fault plan (seed {args.fault_seed}): "
              f"{json.dumps(injector.describe())}")
    # any telemetry-consuming flag turns the recorder on; otherwise the
    # engine hooks stay None and cost nothing on the hot path
    telemetry = (Telemetry()
                 if (args.telemetry or args.replay_trace or args.report_json
                     or args.telemetry_trace) else None)
    controller = None
    if args.slo_ttft_p99_ms > 0:
        controller = AdmissionController(
            SLOConfig(ttft_p99_ms=args.slo_ttft_p99_ms),
            mode=args.controller_mode)
    cost_model = (StepCostModel()
                  if args.cost_model or controller is not None else None)
    eng = ServingEngine(params, cfg, n_slots=args.slots,
                        max_len=args.max_len, min_bucket=args.min_bucket,
                        bucketing=not args.no_bucketing, mesh=mesh,
                        draft_params=draft_params, spec=spec,
                        draft_plan_bn=args.draft_plan_bn or None,
                        draft_plan_bk=args.draft_plan_bk or None,
                        act_dtype=args.act_dtype,
                        guards=args.guards or args.inject_faults,
                        faults=injector,
                        queue_depth=args.queue_depth or None,
                        on_pressure=args.on_pressure, clock=clock,
                        kv_layout=args.kv_layout,
                        page_size=(args.page_size
                                   if args.kv_layout == "paged" else None),
                        kv_pages=(args.kv_pool_pages or None
                                  if args.kv_layout == "paged" else None),
                        kv_dtype=(args.kv_dtype
                                  if args.kv_layout == "paged"
                                  and args.kv_dtype != "f32" else None),
                        verify_contracts=args.verify_contracts,
                        telemetry=telemetry,
                        chunked_prefill=args.chunk_tokens or None,
                        controller=controller, cost_model=cost_model)
    if controller is not None:
        print(f"[serve] SLO controller: p99 TTFT target "
              f"{args.slo_ttft_p99_ms:.0f}ms, ladder "
              f"{'->'.join(controller.ladder)}")
    if args.chunk_tokens:
        print(f"[serve] chunked prefill: {args.chunk_tokens}-token chunks "
              f"interleaved with decode")
    if args.verify_contracts:
        rep = eng.contract_report
        print(f"[serve] contracts: {len(rep.rules_run)} rules clean "
              f"({len(rep.findings)} warning(s)) over the compiled "
              f"decode artifacts")
    if args.kv_layout == "paged":
        print(f"[serve] paged KV cache: page_size={eng.page_size}, "
              f"pool={eng.n_pages} pages, resident dtype "
              f"{eng.kv_dtype or 'fp'}")
    if args.act_dtype != "f32":
        print(f"[serve] activations: per-token {args.act_dtype} "
              f"(opt-in weight-activation quantized serving)")
    rng = np.random.default_rng(0)
    pending = [rng.integers(1, cfg.vocab, size=rng.integers(4, 12)).tolist()
               for _ in range(args.requests)]
    # bounded retry absorbs the injected transient step failures; under
    # the virtual clock the backoff never wall-sleeps
    retry = RetryPolicy(max_attempts=4,
                        backoff_s=0.0 if injector is not None else 0.05)
    t0 = time.time()
    steps = 0
    step_tokens = 0
    t_decode = 0.0
    backpressure_waits = 0
    fault_retries = 0
    report = None
    if args.replay_trace:
        # trace-driven mode: the Replayer owns arrivals, stepping, and the
        # end-of-run scheduling report; --requests is ignored
        trace = load_trace(args.replay_trace)
        print(f"[serve] replaying {len(trace)} arrivals from "
              f"{args.replay_trace}")
        report = Replayer(eng, trace, retry=retry).run()
        steps = report["driver_steps"]
        backpressure_waits = report["scheduling"]["backpressure_waits"]
        fault_retries = report["scheduling"]["transient_retries"]
    else:
        while pending or eng.active or len(eng.queue):
            while pending:
                try:
                    eng.submit(pending[0], max_new_tokens=args.max_new,
                               deadline_ms=args.deadline_ms or None)
                    pending.pop(0)
                except AdmissionRejected:
                    if not eng.active and not len(eng.queue):
                        raise    # empty engine rejected it: will never fit
                    backpressure_waits += 1  # queue full: drain first
                    break
            ts = time.time()
            emitted, retries = retry.run(eng.step)
            fault_retries += retries
            if clock is not None:
                clock.advance()
            if emitted:
                steps += 1
                # speculative steps emit LISTS of accepted tokens per
                # request; only those count toward throughput (rejected
                # drafts are rolled back, not served)
                step_tokens += sum(len(v) if isinstance(v, list) else 1
                                   for v in emitted.values())
                t_decode += time.time() - ts
    finished = eng.take_finished()
    dt = time.time() - t0
    # Throughput counts tokens actually emitted — EOS can retire a request
    # before its max_new_tokens budget, so `done * max_new` overcounts.
    total_tokens = sum(r.tokens_out for r in finished.values())
    st = eng.stats()
    print(f"[serve] {len(finished)} requests, {total_tokens} tokens, "
          f"{dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    if steps and t_decode:
        print(f"[serve] {steps} decode steps, "
              f"{step_tokens / steps:.2f} tokens/step, "
              f"{t_decode / steps * 1e3:.1f} ms/step "
              f"({step_tokens / max(t_decode, 1e-9):.1f} decode tok/s)")
    if spec is not None:
        print(f"[serve] speculative gamma={spec.gamma} "
              f"draft_bits={spec.draft_bits}: acceptance rate "
              f"{st['acceptance_rate']:.0%} "
              f"({st['spec_accepted']}/{st['spec_drafted']} drafts), "
              f"{st['tokens_per_step']:.2f} accepted tokens/step")
    print(f"[serve] prefill traces {st['prefill_traces']} "
          f"(buckets {st['buckets']}), compile-cache hit rate "
          f"{st['bucket_hit_rate']:.0%}")
    if "paged" in st and not args.stats:
        # paged counters now live on the metrics registry (one uniform
        # naming scheme); --stats prints the full report, this is the
        # abbreviated default view rendered from the same registry
        print(eng.metrics().render(prefix="serve.paged",
                                   title="serve.paged"))
    lc = st["lifecycle"]
    nonterminal = len(eng.active) + st["queued"]
    print(f"[serve] lifecycle: {json.dumps(lc)}, preemptions "
          f"{st['preemptions']}, resumes {st['resumes']}, backpressure "
          f"waits {backpressure_waits}, transient-fault retries "
          f"{fault_retries}")
    if telemetry is not None and report is None:
        # non-replay run with telemetry on: build the same scheduling
        # report the Replayer would have produced
        report = build_report(
            eng, elapsed=dt, driver_steps=steps,
            extra={"backpressure_waits": backpressure_waits,
                   "transient_retries": fault_retries,
                   "expired_at_submit": 0,
                   "rejected_unfittable": 0})
    if report is not None:
        tt, tp = report["ttft_ms"], report["tpot_ms"]
        print(f"[serve] ttft_ms p50/p90/p99 = {tt['p50']:.2f}/"
              f"{tt['p90']:.2f}/{tt['p99']:.2f}  tpot_ms p50/p90/p99 = "
              f"{tp['p50']:.2f}/{tp['p90']:.2f}/{tp['p99']:.2f}  "
              f"tokens/s/slot = "
              f"{report['tokens']['per_s_per_slot']:.2f}")
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"[serve] scheduling report -> {args.report_json}")
    if args.telemetry_trace:
        write_perfetto(args.telemetry_trace, telemetry)
        print(f"[serve] perfetto trace -> {args.telemetry_trace} "
              f"(open at ui.perfetto.dev)")
    if args.stats:
        print(eng.metrics().render(title="serve metrics"))
    if nonterminal:
        raise SystemExit(
            f"[serve] {nonterminal} requests never reached a terminal "
            f"state — lifecycle invariant violated")
    if args.inject_faults:
        print("[serve] fault plan survived: every request terminal")


if __name__ == "__main__":
    main()
