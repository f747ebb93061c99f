"""Chip benchmark of CLAQ serving: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chips.  It builds the cell's seeded CLAQ weights on
the device (on a mesh where the configuration file states one, each chip
building its share), starts the program's `ServingEngine` on them (with
that mesh), warms up every prefill and decode shape the cell's traffic
uses, fills the slots, then serves the traffic for `--seconds` and prints
one JSON line last on stdout: `correct`, `attempted`, `failed`,
`metrics`, `device` (and with `--trace 1` a `breakdown`), and last the
numbers compared with their limits under `checks`.  With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics.  It exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import check, e2e, engine_spans, serve_loop  # noqa: E402

BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"        # traces; inside the checkout, git-ignored
DRAIN_SECONDS = 60.0             # wait past the close for first tokens
SLOW_STEP = 5.0                  # a stall: a step over this x the median


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), file=sys.stderr,
          flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_exit(need: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench: needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs[:need]


def setup_cache() -> str:
    """JAX's persistent compile cache in the checkout (or where
    $JAX_COMPILATION_CACHE_DIR says), keeping every program the cell
    needs, however quick to compile, so a run after the first compiles
    nothing."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def count_compiles():
    """Programs compiled or loaded from the persistent cache, with their
    times: one event per executable JAX had to obtain."""
    import jax

    times = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            times.append(time.monotonic())
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return times


def mesh_for(conf, devs):
    """The configuration's (data, model) mesh over `devs`, the chips
    `chips_or_exit` gave the cell, with every axis Auto as the program's
    launcher builds it (`launch.mesh.make_mesh`); None without."""
    if "mesh" not in conf:
        return None
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(cells.mesh_shape(conf), cells.MESH_AXES,
                         devices=devs,
                         axis_types=(AxisType.Auto,) * len(cells.MESH_AXES))


def start_engine(cell, cfg, seed: int, devs, act_dtype=None):
    """The program's engine on the cell's seeded weights, as the cell
    serves them: bf16 slot cache, the contiguous layout, the compiled
    kernel the platform picks, the configuration's mesh over `devs`, no
    speculation, paging or controller."""
    import jax.numpy as jnp

    from repro.serve import ServingEngine, Telemetry

    conf, mix = cell.conf, cell.traffic
    mesh = mesh_for(conf, devs)
    params = cells.weights_for(conf["family"], BENCH).served_params(
        seed, conf, mesh)
    return ServingEngine(params, cfg, n_slots=mix["n_slots"],
                         max_len=mix["max_len"], dtype=jnp.bfloat16,
                         act_dtype=act_dtype, mesh=mesh,
                         telemetry=Telemetry(), **conf["engine"])


def stalls(tel, t_open: float, t_close: float) -> dict:
    """The longest `engine.step` inside the window and the phase that took
    most of it; and, over the steps that admitted nothing (decode alone,
    whose time is steady), the longest and how many took over SLOW_STEP x
    their median.  A stall shows here before it shows as spread in
    `tokens_per_s`; a refill's prefill is no stall."""
    steps = [s for s in engine_spans.steps(tel)
             if t_open <= s["start"] and s["end"] <= t_close]
    if not steps:
        return {"max_step_ms": "na"}
    slow = max(steps, key=engine_spans.wall)
    kids = engine_spans.children(tel).get(slow["id"], {})
    phase = max(kids.values(), key=engine_spans.wall)["name"] if kids \
        else "none"
    out = {"max_step_ms": f"{1e3 * engine_spans.wall(slow):.3f}",
           "max_step_phase": phase}
    walls = [engine_spans.wall(s) for s in steps
             if not s["attrs"].get("admitted")]
    if walls:
        median = statistics.median(walls)
        out.update(max_decode_step_ms=f"{1e3 * max(walls):.3f}",
                   median_decode_step_ms=f"{1e3 * median:.3f}",
                   slow_decode_steps=sum(w > SLOW_STEP * median
                                         for w in walls))
    return out


def warm_up(eng, requests, batches, max_tokens) -> int:
    """Run every prefill shape the traffic can need (each length bucket at
    each admission batch size up to `max_tokens` padded tokens) and the
    decode step once, through the engine's own admission path.  Group
    inserts depend on the group's rows, not its bucket, so all row counts
    run at the smallest bucket."""
    buckets = sorted({eng.bucketing.bucket_for(len(r["prompt"]))
                      for r in requests})
    calls = 0
    for b in buckets:
        rows = range(1, max(batches) + 1) if b == buckets[0] else batches
        for n in rows:
            if n > 1 and n * b > max_tokens:
                continue
            eng.add_requests([[1] * (b - 1)] * n, max_new_tokens=1)
            calls += 1
    eng.add_requests([[1] * (buckets[0] - 1)], max_new_tokens=2)
    eng.step()
    eng.take_finished()
    if eng.mesh is not None:
        calls += warm_mesh_layouts(eng, buckets[0], max(batches))
    return calls


def warm_mesh_layouts(eng, bucket: int, max_rows: int) -> int:
    """On a mesh a step's programs depend on the slot cache's layout too:
    an insert pins it, a decode leaves it as the compiler chose.  Run a
    decode after a decode, and an insert of each group size after a
    decode, each a program of its own there.  One chip has one layout."""
    eng.add_requests([[1] * (bucket - 1)], max_new_tokens=max_rows + 4)
    eng.step()
    eng.step()
    for n in range(1, max_rows + 1):
        eng.add_requests([[1] * (bucket - 1)] * n, max_new_tokens=1)
        eng.step()
    eng.run_to_completion()
    eng.take_finished()
    return max_rows + 1


def trace_window(driver, t0, t_open, t_close, seconds):
    """Serve until t_close, tracing the last `seconds`; returns the end,
    the trace's log dir and the step calls logged while it ran."""
    import jax

    t_trace = max(t_open, t_close - seconds)
    driver.run_until(t0, t_trace)
    log_dir = OUT / "trace"
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.block_until_ready(driver.eng.cache)
    jax.profiler.start_trace(str(log_dir))
    driver.calls = []
    with jax.profiler.TraceAnnotation("bench.window"):
        end = driver.run_until(t0, t_close)
        jax.block_until_ready(driver.eng.cache)
    jax.profiler.stop_trace()
    calls, driver.calls = driver.calls, None
    return end, log_dir, calls


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cells.metric_reader(m["name"], BENCH).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.load_cell(args.workload, ROOT, BENCH)
    devs = chips_or_exit(cell.chips)

    import jax

    cache_dir = setup_cache()
    compiles = count_compiles()
    log(device=devs[0].platform, device_kind=devs[0].device_kind,
        devices=len(devs), jax=jax.__version__, compile_cache=cache_dir,
        workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace)
    result = measure(args, cell, devs, compiles, T_START)
    for name, c in result["checks"].items():
        log(check=name, value=c["value"], limit=c["limit"])
    print(json.dumps(result), flush=True)
    return 0


def measure(args, cell, devs, compiles, t_start: float,
            act_dtype=None) -> dict:
    """One run of the cell from `t_start` (set-up is timed from there):
    the result line's fields, checks last.  `act_dtype="int8"` serves
    with the program's int8-activation path, the lower-precision control
    (`bench/calibrate.py`)."""
    import jax

    from bench import trace_reduce

    conf, mix = cell.conf, cell.traffic
    cfg = cells.model_config(conf)
    gen = cells.traffic_kind(mix["kind"], BENCH)
    requests = gen.generate(mix, args.seed, args.seconds, conf["vocab_size"])

    t_engine = serve_loop.clock()
    eng = start_engine(cell, cfg, args.seed, devs, act_dtype)
    t_warm = serve_loop.clock()
    warm_calls = warm_up(eng, requests, mix["warm_batches"],
                         mix["warm_max_tokens"])
    t_fill = serve_loop.clock()
    driver = serve_loop.Driver(eng, gen.MODE, requests)
    t0 = serve_loop.clock()
    if gen.MODE == "closed":
        driver.fill_slots(per_step=gen.fill_per_step(mix))
    else:
        # the pre-roll: arrivals due before the window, sent on schedule
        t0 = serve_loop.clock() + mix["preroll_s"]
        driver.run_until(t0, t0)
    jax.block_until_ready(eng.cache)
    t_open = serve_loop.clock()
    setup_s = t_open - t_start
    n_compiled_setup = len(compiles)
    if gen.MODE == "closed":
        t0 = t_open
    t_close = t_open + args.seconds
    if args.trace:
        t_close, log_dir, calls = trace_window(
            driver, t0, t_open, t_close, mix["trace_s"])
    else:
        t_close = driver.run_until(t0, t_close)
    in_window_compiles = sum(1 for t in compiles if t_open <= t <= t_close)
    if gen.MODE == "open":
        driver.drain(t_open, t_close, t0, DRAIN_SECONDS)
    jax.block_until_ready(eng.cache)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    recs = [r for r in driver.recs]
    window_recs = [r for r in e2e.in_window(recs, t_open, t_close)]
    # refused, or (open loop, after the drain) never given a first token;
    # a closed loop's last refills are still on their way, not failed
    failed = sum(1 for r in window_recs if r.rejected or (
        gen.MODE == "open" and not r.token_t))
    late = [(r.submit_t - r.due) * 1e3 for r in window_recs
            if r.due is not None]
    stats = eng.stats()
    log(setup_s=f"{setup_s:.3f}", to_engine_s=f"{t_engine - t_start:.3f}",
        engine_s=f"{t_warm - t_engine:.3f}", warm_s=f"{t_fill - t_warm:.3f}",
        fill_s=f"{t_open - t_fill:.3f}", warm_calls=warm_calls,
        compiles_in_setup=n_compiled_setup,
        compiles_in_window=in_window_compiles,
        prefill_traces=stats["prefill_traces"],
        decode_traces=stats["decode_traces"], engine_steps=stats[
            "engine_steps"], window_steps=driver.steps,
        preemptions=stats["preemptions"], queued_at_end=stats["queued"],
        rejected=stats["admission_rejections"])
    log(**stalls(eng.telemetry, t_open, t_close))
    log(generator_late_ms_p50=f"{np.percentile(late, 50):.3f}"
        if late else "na",
        generator_late_ms_max=f"{max(late):.3f}" if late else "na",
        peak_hbm_bytes=peak, **e2e.samples(recs, t_open, t_close))

    telemetry = eng.telemetry
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(window_recs),
              "failed": failed}
    if args.trace:
        path = trace_reduce.find_trace(str(log_dir))
        red = trace_reduce.reduce(jax.profiler.ProfileData.from_file(path))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            conf=conf, mix=mix, peaks=peaks_for(devs[0]), trace=red,
            calls=calls, trace_end=red.window_end, telemetry=telemetry,
            window_recs=window_recs)
        metrics = per_layer(cell, ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                               "idle_gaps": [list(x) for x in red.idle_gaps]}
        kinds = {}
        for e in red.execs:
            kinds[e.kind] = kinds.get(e.kind, 0) + 1
        log(trace_execs=json.dumps(kinds), kernel_ops=red.kernel_ops,
            logged_calls=len(calls))
    else:
        metrics = {m["name"]: {"value": v, "unit": m["unit"]}
                   for m, v in zip(cell.end_to_end, e2e.compute(
                       [m["name"] for m in cell.end_to_end], recs, t_open,
                       t_close, setup_s).values())}
    result["metrics"] = metrics
    result["device"] = device

    # the comparison with the plain reference, once the program is gone
    sample = check.sample(driver.done, args.seed)
    del eng, driver.eng, telemetry
    gc.collect()
    checks = {}
    limits = check.limits_for(cell.name, BENCH)
    if sample:
        ref = cells.reference_for(conf["family"], BENCH)
        t_ref = time.monotonic()
        got = check.widest_gap(ref, conf, args.seed, sample)
        log(reference_s=f"{time.monotonic() - t_ref:.3f}",
            **{k: v for k, v in got.items()})
        # a number with no limit (while limits are being set) is shown
        # with limit null and decides nothing
        for key in check.NUMBERS:
            if key in limits or not limits:
                checks[key] = {"value": got[key], "limit": limits.get(key)}
    result["correct"] = bool(sample) and bool(limits) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    checks["failed_requests"] = {"value": failed, "limit": 0}
    result["checks"] = checks
    return result


def peaks_for(dev) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if dev.device_kind not in table:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       f"bench/peaks.json")
    return table[dev.device_kind]


if __name__ == "__main__":
    sys.exit(main())
