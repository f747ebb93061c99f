"""The lower-precision control at a size a CPU test run holds.  The
configurations serve bfloat16; the program's own int8-activation path
(`ServingEngine(act_dtype="int8")`, `bench/calibrate.py --control-seeds`) is
the nearest precision below, and the comparison has to read it as worse
than the program.  The engine is driven through the harness's closed loop
for a fixed number of steps (not a timed window), so the served tokens do
not depend on the machine's speed.  At this size single seeds overlap (a
widest gap is a tail), so the readings are pooled over four seeds; the
cells' limits come from chip runs of both at the cells' sizes
(PERF.md)."""
import json

import jax.numpy as jnp
import numpy as np

from bench_tiny import ROOT

from bench import cell, check, serve_loop, weights

SIZE = dict(num_hidden_layers=4, hidden_size=512, num_attention_heads=8,
            num_key_value_heads=2, head_dim=64, intermediate_size=1536,
            vocab_size=4096, rotary_dim=64)
MIX = {"kind": "closed_loop", "n_slots": 4, "max_len": 128, "pool": 64,
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                  "min": 8, "max": 64},
       "output": {"dist": "uniform", "min": 12, "max": 40},
       "warm_batches": [1, 2]}
STEPS = 80
SEEDS = (3, 4, 5, 6)


def readings(seed, act_dtype):
    from repro.serve import ServingEngine, Telemetry

    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    conf.update(SIZE)
    cfg = cell.model_config(conf, strict=False)
    gen = cell.traffic_kind(MIX["kind"])
    reqs = gen.generate(MIX, seed, 1.0, conf["vocab_size"])
    eng = ServingEngine(weights.served_params(seed, conf), cfg, n_slots=4,
                        max_len=128, dtype=jnp.bfloat16, min_bucket=16,
                        act_dtype=act_dtype, telemetry=Telemetry())
    drv = serve_loop.Driver(eng, gen.MODE, reqs)
    drv.fill_slots(per_step=gen.fill_per_step(MIX))
    for _ in range(STEPS):
        drv.refill()
        drv.step()
    got = check.widest_gap(cell.reference_for("dense"), conf, seed,
                           check.sample(drv.done, seed))
    assert got["tokens_compared"] >= 100
    return got


def test_control_reads_worse_than_the_program():
    prog = [readings(s, None) for s in SEEDS]
    ctl = [readings(s, "int8") for s in SEEDS]
    mean = lambda rs: np.mean([r["mean_logit_gap"] for r in rs])  # noqa: E731
    assert mean(ctl) > 2.5 * mean(prog)
    assert max(r["max_logit_gap"] for r in ctl) > max(
        r["max_logit_gap"] for r in prog)
