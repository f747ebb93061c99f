"""Readings for a cell's correctness limits: the program over many seeds
and the lower-precision control over a few, in one process that holds the
chip (each seed still builds its own weights and engine; compiled
programs come from the cache after the first).

    python3 bench/calibrate.py --workload glm4_9b.batch --seconds 50 \\
        --seeds 11,12,13 --control-seeds 21,22,23

Prints one JSON line per run: the seed, whether it ran the control
(the program's int8-activation path, `ServingEngine(act_dtype="int8")`,
the nearest precision below the bfloat16 the configurations state), and
the numbers `bench/check.py` compares.  The benchmark's own runs never
run this; PERF.md gives the readings each limit was set from.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench import run, serve_loop  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload, run.ROOT, run.BENCH)
    devs = run.chips_or_exit(cell.chips)
    run.setup_cache()
    compiles = run.count_compiles()
    plan = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), "int8") for s in args.control_seeds.split(",") if s]
    for seed, act in plan:
        ns = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        res = run.measure(ns, cell, devs, compiles, serve_loop.clock(), act)
        print(json.dumps({"seed": seed, "control": act,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
