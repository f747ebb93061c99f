"""Kernel microbenchmarks + structural perf accounting.

Wall times on this CPU container are NOT TPU estimates; the TPU-relevant
derived quantities are structural: HBM bytes per matmul for the CLAQ
kernel path vs the dense-bf16 path (the memory-bound decode speedup the
deployment format buys), kernel-launch counts for the ahead-of-time plan
path vs the per-stripe path, and interpret-mode correctness timing.

Rows cover decode-shaped matmuls (M=1 single-token, M=8 a decode batch)
next to the prefill-ish M=64: the prepared matmul (aligned block reads
for integer bit-widths, one XLA take per permuted group for
mixed-precision plans; DESIGN.md §9) against the opt-in int8 activation
path, whose measured error against the f32 reference is checked under
the documented bound.
A small bk/bn sweep at 4 bits chases the near-parity prepared result
PR 1 left on the table.

`kernel_bench()` writes BENCH_kernel.json at the repo root so the
prepared-vs-unprepared perf trajectory is tracked across PRs.  `--smoke`
(the CI step) shrinks reps and SELF-ASSERTS the structural claim:
prepared runs at or under the unprepared time at every bit-width (a 25%
tolerance plus a 4x-reps re-measure absorbs shared-box noise; the
interleaved min-of-N sampling cancels drift).

  PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import APConfig, CLAQConfig, ORConfig, quantize_matrix
from repro.kernels import dequant_matmul as dm
from repro.kernels import ops, ref as ref_lib
from repro.kernels.plan import prepare_for_inference
from repro.launch.compile_cache import use_compile_cache

_BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_kernel.json")

# shared-box noise tolerance for the smoke-mode self-asserts
_SMOKE_SLACK = 1.25


def _sample(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6


def _time_pair(fn_a, fn_b, *args, reps=11):
    """Interleaved A/B timing: alternating samples cancel container CPU
    drift; min-of-N is robust to additive noise (this box is shared)."""
    for fn in (fn_a, fn_b):
        fn(*args)  # compile / warm caches
        fn(*args)
    a, b = [], []
    for _ in range(reps):
        a.append(_sample(fn_a, *args))
        b.append(_sample(fn_b, *args))
    return float(np.min(a)), float(np.min(b))


def _assert_not_slower(fast_fn, base_fn, x, us_fast, us_base, reps, label):
    """Smoke-mode perf claim with one escalation: this box's wall times
    swing ~2x under neighboring load, so a first-pass miss re-measures
    with 4x the samples before declaring a regression."""
    if us_fast <= us_base * _SMOKE_SLACK:
        return us_fast, us_base
    us_base, us_fast = _time_pair(base_fn, fast_fn, x, reps=4 * reps)
    assert us_fast <= us_base * _SMOKE_SLACK, (
        f"{label}: {us_fast:.0f}us vs baseline {us_base:.0f}us "
        f"(> {_SMOKE_SLACK:.2f}x, re-measured)")
    return us_fast, us_base


def _quantize(W, bits):
    """One tensor per benchmarked bit-width; fractional widths get the
    paper's AP+OR fusion (multi-stripe mixed precision + outliers)."""
    base = int(bits)
    ap = orr = None
    if bits != base:
        ap = APConfig(base + (bits - base) * 0.6, base, 4)
        orr = ORConfig((bits - base) * 0.4)
    qt, _, _ = quantize_matrix(W, None, CLAQConfig(
        bits=base, method="kmeans", kmeans_iters=4, gptq_blocksize=128,
        ap=ap, orr=orr))
    return qt


def kernel_bench(out_json: str = _BENCH_JSON, smoke: bool = False):
    rows = []
    results = {}
    rng = np.random.default_rng(0)
    n, k_dim = 512, 512
    reps = 9 if smoke else 17
    ms = (1, 8) if smoke else (1, 8, 64)
    W = jnp.asarray(rng.normal(size=(n, k_dim)).astype(np.float32))
    xs = {m: jnp.asarray(rng.normal(size=(m, k_dim)).astype(np.float32))
          for m in ms}

    for bits in (2, 2.5, 3, 4):
        qt = _quantize(W, bits)
        pqt = prepare_for_inference(qt)

        # structural HBM bytes per token for the weight stream:
        dense_bytes = n * k_dim * 2                       # bf16 weights
        q_bytes = sum(s.packed.size * 4 + s.codebook.size * 2
                      for s in qt.stripes)
        ratio = dense_bytes / q_bytes

        def run_unprep(a, q=qt):
            return ops.qmatmul(a, q, use_kernel=True)

        def run_prepared(a, q=pqt):
            return ops.prepared_qmatmul(a, q)

        def run_int8(a, q=pqt):
            return ops.prepared_qmatmul(a, q, act_dtype="int8")

        x_big = xs[max(ms)]
        c0 = dm.launch_count
        run_unprep(x_big)
        launches_unprep = dm.launch_count - c0
        c0 = dm.launch_count
        run_prepared(x_big)
        launches_prep = dm.launch_count - c0

        # prepared-vs-unprepared continuity row (PR 1's fusion claim) at
        # the largest M
        us_unprep, us_prep = _time_pair(run_unprep, run_prepared,
                                        x_big, reps=reps)
        err = float(jnp.max(jnp.abs(run_prepared(x_big)
                                    - ref_lib.ref_qmatmul(x_big, qt))))
        if smoke:
            us_prep, us_unprep = _assert_not_slower(
                run_prepared, run_unprep, x_big, us_prep, us_unprep,
                reps, f"{bits}-bit prepared-vs-unprepared")

        key = str(bits)
        results[key] = {
            "stripes": [(s.bits, s.n_cols) for s in qt.stripes],
            "distinct_bitwidths": len({s.bits for s in qt.stripes}),
            "x_gather_free": pqt.x_gather_free,
            "launches_unprepared": launches_unprep,
            "launches_prepared": launches_prep,
            "interp_us_unprepared": us_unprep,
            "interp_us_prepared": us_prep,
            "weight_bytes_ratio_vs_bf16": ratio,
            "prepared_max_err_vs_ref": err,
        }
        rows.append((f"kernel/dequant_matmul_{key}bit_interp_unprepared",
                     us_unprep, f"weight_bytes_ratio={ratio:.2f};"
                     f"launches={launches_unprep}"))
        rows.append((f"kernel/dequant_matmul_{key}bit_interp_prepared",
                     us_prep, f"launches={launches_prep};max_err={err:.2e}"))

        # decode-shaped rows: prepared vs int8 activations
        Wd = qt.dequantize()
        for m in ms:
            x = xs[m]
            # recorded figures sample at 4x the smoke budget, in TWO
            # temporally separated passes min-combined (smoke keeps the
            # small budget)
            us_prep_m, us_i8 = _time_pair(run_prepared, run_int8, x,
                                          reps=reps if smoke else 4 * reps)
            if not smoke:
                p2, i2 = _time_pair(run_prepared, run_int8, x, reps=4 * reps)
                us_prep_m, us_i8 = min(us_prep_m, p2), min(us_i8, i2)
            y_ref = ref_lib.ref_qmatmul(x, qt)
            err_el = jnp.abs(run_int8(x) - y_ref)
            bound_el = ref_lib.ref_act_int8_bound(x, Wd)
            # per-ELEMENT check (the documented guarantee is per output
            # element; a global max-vs-max compare would let one token's
            # violation hide under another token's larger bound)
            assert bool(jnp.all(err_el <= bound_el * 1.01 + 1e-5)), \
                (bits, m, float(jnp.max(err_el - bound_el)))
            i8_err = float(jnp.max(err_el))
            i8_bound = float(jnp.max(bound_el))
            results[key][f"m{m}"] = {
                "interp_us_prepared": us_prep_m,
                "interp_us_int8": us_i8,
                "int8_max_err": i8_err,
                "int8_err_bound": i8_bound,
            }
            rows.append((f"kernel/dequant_matmul_{key}bit_m{m}_prepared",
                         us_prep_m, f"gather_free={pqt.x_gather_free}"))
            rows.append((f"kernel/dequant_matmul_{key}bit_m{m}_act_int8",
                         us_i8, f"max_err={i8_err:.2e};bound={i8_bound:.2e}"))

    # bk/bn sweep at 4 bits, decode batch shape (the near-parity cell PR 1
    # left: plan tiles were never tuned below the defaults)
    if not smoke:
        qt4 = _quantize(W, 4)
        x8 = xs[8]
        sweep = {}
        for bk, bn in ((128, 128), (256, 128), (512, 128), (512, 256),
                       (512, 512)):
            p = prepare_for_inference(qt4, bn=bn, bk=bk)

            def run(a, q=p):
                return ops.prepared_qmatmul(a, q)

            run(x8)
            us = min(_sample(run, x8) for _ in range(reps))
            sweep[f"bk{bk}_bn{bn}"] = us
            rows.append((f"kernel/sweep_4bit_m8_bk{bk}_bn{bn}", us, ""))
        best = min(sweep, key=sweep.get)
        results["sweep_4bit_m8"] = {**sweep, "best": best}
        rows.append((f"kernel/sweep_4bit_m8_best", sweep[best], best))

    with open(out_json, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
        f.write("\n")
    rows.append(("kernel/bench_json_written", 0.0, out_json))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


def roofline_rows(dryrun_path="experiments/dryrun.json"):
    """Surface the dry-run roofline table through the benchmark CSV."""
    rows = []
    if not os.path.exists(dryrun_path):
        print("roofline/missing,0.0,run launch.dryrun first")
        return rows
    with open(dryrun_path) as f:
        results = json.load(f)
    for key, v in sorted(results.items()):
        if v.get("status") != "ok":
            continue
        r = v["roofline"]
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        rows.append((f"roofline/{key}", dom * 1e6,
                     f"bottleneck={r['bottleneck']};"
                     f"frac={r['roofline_fraction']:.4f};"
                     f"useful={r['useful_flop_fraction']:.3f}"))
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewer reps, decode shapes only, and "
                         "self-assert that prepared runs no slower than "
                         "unprepared at every bit-width")
    ap.add_argument("--out", default=_BENCH_JSON)
    args = ap.parse_args()
    use_compile_cache()
    kernel_bench(out_json=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
