"""Compile a cell's served steps for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload glm4_9b.batch

Lowers and compiles, at the cell's published widths and depth, the decode
step and the prefill step at the largest length bucket for each admission
batch size the cell warms up (the same `api.decode_step` /
`api.prefill_step` calls `ServingEngine` jits), on abstract weights of the
cell's CLAQ layout.  The TPU compiler refuses here what it would refuse on
the chip (kernel blocks, VMEM, a program that does not fit HBM), and
`memory_analysis()` gives each program's bytes.  Nothing runs: no result
or time comes from this.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _gb(n: int) -> str:
    return f"{n / 1e9:.3f}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--buckets", default="max",
                    help="'max' (largest bucket only) or 'all'")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cell as cells
    from bench import weights
    from repro.kernels import dequant_matmul as dm
    from repro.kernels.plan import prepare_tree
    from repro.models import api
    from repro.serve.bucketing import BucketingPolicy

    cell = cells.load_cell(args.workload)
    conf, mix = cell.conf, cell.traffic
    cfg = cells.model_config(conf)
    gen = cells.traffic_kind(mix["kind"])
    reqs = gen.generate(mix, 0, 40.0, conf["vocab_size"])
    pol = BucketingPolicy(min_bucket=conf["engine"]["min_bucket"],
                          max_len=mix["max_len"])
    buckets = sorted({pol.bucket_for(len(r["prompt"])) for r in reqs})
    if args.buckets == "max":
        buckets = buckets[-1:]

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    dm._platform = lambda: "tpu"          # trace the compiled-kernel path

    mc = weights.model_items(conf)
    claq = tuple(sorted(conf["claq"].items()))
    params = jax.eval_shape(
        lambda k: prepare_tree(weights._build(k, mc, claq)),
        jax.random.PRNGKey(0))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = sds(params)
    w_bytes = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    n = mix["n_slots"]
    cache = sds(jax.eval_shape(lambda: api.make_cache(
        cfg, n, mix["max_len"], dtype=jnp.bfloat16)))
    c_bytes = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(cache))
    print(json.dumps({"workload": cell.name, "weights_gb": _gb(w_bytes),
                      "cache_gb": _gb(c_bytes)}), flush=True)

    def decode_fn(p, t, c):
        return api.decode_step(p, cfg, t, c)

    def prefill_fn(p, t, c, lens):
        return api.prefill_step(p, cfg, {"tokens": t}, c,
                                logits_at=lens - 1)

    tok = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one)
    comp = jax.jit(decode_fn).lower(params, tok, cache).compile()
    ma = comp.memory_analysis()
    print(json.dumps({"program": "decode", "rows": n,
                      "kernel": "tpu_custom_call" in comp.as_text(),
                      "args_gb": _gb(ma.argument_size_in_bytes),
                      "out_gb": _gb(ma.output_size_in_bytes),
                      "temp_gb": _gb(ma.temp_size_in_bytes)}), flush=True)
    for b in buckets:
        for rows in sorted({min(1 << (r - 1).bit_length(), n)
                            for r in mix["warm_batches"]}):
            frag = sds(jax.eval_shape(lambda: api.make_cache(
                cfg, rows, mix["max_len"], dtype=jnp.bfloat16)))
            t = jax.ShapeDtypeStruct((rows, b), jnp.int32, sharding=one)
            lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
            comp = jax.jit(prefill_fn).lower(params, t, frag, lens).compile()
            ma = comp.memory_analysis()
            # the slot cache stays resident beside a prefill
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes + c_bytes)
            print(json.dumps({"program": "prefill", "rows": rows,
                              "bucket": b,
                              "args_gb": _gb(ma.argument_size_in_bytes),
                              "out_gb": _gb(ma.output_size_in_bytes),
                              "temp_gb": _gb(ma.temp_size_in_bytes),
                              "with_slot_cache_gb": _gb(peak)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
