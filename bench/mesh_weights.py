"""Chip check of the weights drawn per chip: for a configuration served
on a mesh, the tree each chip builds (`served_params(seed, conf, mesh)`)
is the one-device build bit for bit, shard by shard on the chip that
holds it, and each chip holds an equal share.

    python3 bench/mesh_weights.py --config glm4_9b --data 1 --model 4 \\
        --seed 3000001622 --seed 3000001624

It needs data x model chips and room on the first for the one-device
build beside its share; each seed prints one JSON line, and the last
line says whether every seed held.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def compare(seed: int, conf, mesh) -> dict:
    """Build both trees, compare every shard of the mesh's tree with the
    same slice of the one-device tree on the shard's chip."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W

    t = time.monotonic()
    sh = jax.block_until_ready(W.served_params(seed, conf, mesh))
    t_mesh = time.monotonic() - t
    t = time.monotonic()
    one = jax.block_until_ready(W.served_params(seed, conf))
    t_one = time.monotonic() - t
    la = jax.tree_util.tree_leaves_with_path(one)
    lb = jax.tree_util.tree_leaves_with_path(sh)
    if [p for p, _ in la] != [p for p, _ in lb]:
        raise ValueError("the two trees have different leaves")
    held, bad, shards, total = {}, [], 0, 0
    for (path, a), (_, b) in zip(la, lb):
        total += a.nbytes
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            bad.append(f"{jax.tree_util.keystr(path)} shape or dtype")
            continue
        for s in b.addressable_shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
            shards += 1
            if not bool(jnp.array_equal(jax.device_put(a[s.index], s.device),
                                        s.data)):
                bad.append(f"{jax.tree_util.keystr(path)} {s.index}")
    shares = [held.get(d.id, 0) for d in mesh.devices.flat]
    return {"seed": seed, "leaves": len(la), "shards": shards,
            "mismatched": len(bad), "first_mismatched": bad[:10],
            "total_bytes": total, "held_bytes": shares,
            "equal_shares": all(h * len(shares) == total for h in shares),
            "build_s_mesh": t_mesh, "build_s_one": t_one}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--data", type=int, required=True)
    ap.add_argument("--model", type=int, required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)

    from bench import cell, run

    conf = json.loads((ROOT / "bench/configs" / f"{args.config}.json")
                      .read_text())
    conf["mesh"] = {"data": args.data, "model": args.model}
    devs = run.chips_or_exit(args.data * args.model)
    run.setup_cache()
    mesh = run.mesh_for(conf, devs)
    ok = True
    for seed in args.seed:
        got = compare(seed, conf, mesh)
        got["peak_bytes"] = [(d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in devs]
        ok = ok and got["mismatched"] == 0 and got["equal_shares"]
        print(json.dumps(got), flush=True)
    print(json.dumps({"ok": ok, "mesh": cell.mesh_shape(conf),
                      "device": devs[0].device_kind, "count": len(devs)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
