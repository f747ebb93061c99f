"""Device time of the CLAQ kernel's Outlier-Reservation (OR) epilogue on a
TPU.  Two parts, each printed as JSON lines:

* `launch`: one gate-shaped launch of `dequant_matmul` (N 13696, K 3072,
  M 32, 2 bits, bf16 compute) with k_out outlier slots per column in
  {0, 34, 120}, every slot holding an outlier, rows spread evenly down
  each column as `bench/weights.py` spreads them.  The launch is given no
  window table, so it visits every slot.
* `matrix`: `prepared_qmatmul` at M 32 (bf16) on one layer of each
  glm4_9b matrix kind in the benchmark's seeded CLAQ layout
  (`bench/weights.py`: AP 2.5 over 2/4-bit columns, OR 0.1), and their
  sum over one decode step's 40 layers.

Each time is the median over `--reps` repeats of a jitted loop of
`--loops` calls, on the host clock around `block_until_ready`, per call.
It refuses to run off a TPU: a CPU time says nothing about the chip.

  PYTHONPATH=src python -m benchmarks.or_epilogue [--reps 5] [--loops 20]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import dequant_matmul as dm
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[1]
M = 32


def per_call_ms(fn, args, loops: int, reps: int) -> float:
    """Median ms of one `fn(*args)` call.  Each loop step perturbs the
    activations by 0 * the running sum, so XLA cannot hoist the call out
    of the loop."""
    @jax.jit
    def many(x, *rest):
        def body(_, acc):
            y = fn(x + (acc * 0).astype(x.dtype), *rest)
            return acc + y[0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, loops, body, jnp.float32(0))

    jax.block_until_ready(many(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(many(*args))
        times.append((time.perf_counter() - t) * 1e3 / loops)
    return statistics.median(times)


def launch_part(loops: int, reps: int):
    n, k, bits, bn, bk = 13696, 3072, 2, 128, 512
    key = jax.random.PRNGKey(0)
    kx, kp, kc, ko, kv = jax.random.split(key, 5)
    x = jax.random.normal(kx, (M, k), jnp.float32).astype(jnp.bfloat16)
    planes = (jax.random.bits(kp, (n // 16, k), jnp.uint32),)
    cb = jnp.sort(jax.random.normal(kc, (1 << bits, k)), axis=0) * k ** -0.5
    for k_out in (0, 34, 120):
        oi = ov = None
        if k_out:
            step = n // k_out
            off = jax.random.randint(ko, (k,), 0, n)
            oi = jnp.sort((off[None, :] + step * jnp.arange(k_out)[:, None])
                          % n, axis=0).astype(jnp.int32)
            ov = jax.random.normal(kv, (k_out, k), jnp.float32)

        def fn(x, planes, cb, oi, ov):
            return dm.dequant_matmul(
                x, planes, cb, oi, ov, bits=bits, n=n, bm=M, bn=bn, bk=bk,
                compute_dtype=jnp.bfloat16)

        ms = per_call_ms(fn, (x, planes, cb, oi, ov), loops, reps)
        print(json.dumps({"part": "launch", "k_out": k_out, "ms": ms}),
              flush=True)


def matrix_part(loops: int, reps: int):
    import sys
    sys.path.insert(0, str(ROOT))
    from bench import weights

    conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
    key = weights.base_key(1)
    total = 0.0
    for m_i, (name, (rows, cols)) in enumerate(
            weights.matrix_shapes(conf).items()):
        lay = weights.layout(rows, cols, conf["claq"])
        qt = jax.jit(lambda k, lay=lay: weights.quantized_matrix(k, lay))(
            weights.matrix_key(key, 0, m_i))
        pqt = qt.prepare()
        x = jax.random.normal(jax.random.PRNGKey(m_i), (M, cols),
                              jnp.float32).astype(jnp.bfloat16)

        def fn(x, pqt):
            return ops.prepared_qmatmul(x, pqt, compute_dtype=jnp.bfloat16)

        ms = per_call_ms(fn, (x, pqt), loops, reps)
        total += ms
        print(json.dumps({"part": "matrix", "matrix": name, "rows": rows,
                          "cols": cols, "k_top": lay.k_top,
                          "k_rest": lay.k_rest, "ms": ms}), flush=True)
    layers = conf["num_hidden_layers"]
    print(json.dumps({"part": "matrix", "matrix": "decode_step",
                      "layers": layers, "ms": total * layers}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--loops", type=int, default=20)
    ap.add_argument("--part", choices=("launch", "matrix", "both"),
                    default="both")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    print(json.dumps({"device": dev.device_kind, "jax": jax.__version__}),
          flush=True)
    if args.part in ("launch", "both"):
        launch_part(args.loops, args.reps)
    if args.part in ("matrix", "both"):
        matrix_part(args.loops, args.reps)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
