"""A benchmark root at a size a CPU test run holds: the repo's `bench/`
files copied beside a BENCHMARK.json with one tiny cell (the qwen2_1p5b
layout at smoke widths), and a way to drive `bench/run.py` through it with
the look for a chip skipped."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256,
            vocab_size=512, rotary_dim=32)

CHAT = {"kind": "poisson", "rate_per_s": 4.0, "preroll_s": 0.5,
        "n_slots": 4, "max_len": 128,
        "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                   "min": 4, "max": 60},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                   "min": 4, "max": 24},
        "warm_batches": [1, 2], "warm_max_tokens": 4096, "trace_s": 1.0}

BATCH = {"kind": "closed_loop", "n_slots": 4, "max_len": 128, "pool": 64,
         "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                    "min": 8, "max": 64},
         "output": {"dist": "uniform", "min": 8, "max": 40},
         "warm_batches": [1, 2], "warm_max_tokens": 4096, "trace_s": 1.0}


def tiny_root(tmp: pathlib.Path, mix=CHAT, limit: float = 0.5,
              workload: str = "tiny.chat", conf_extra=None,
              chips: int = 1) -> pathlib.Path:
    """`limit` is on the mean logit gap, the number the cells compare;
    `conf_extra` is put into the tiny configuration file (a `mesh`,
    `qk_norm`, another depth)."""
    root = tmp / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    conf.update(TINY)
    conf["engine"] = {"min_bucket": 16}
    conf.update(conf_extra or {})
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    (root / "bench/traffic/tinymix.json").write_text(json.dumps(mix))
    spec["configs"] = [{"name": "tiny", "source": "smoke widths",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU test size"}]
    spec["workloads"] = [{"name": workload, "config": "tiny",
                          "traffic": "tinymix", "chips": chips,
                          "why": "CPU test size"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/limits").mkdir(exist_ok=True)
    (root / f"bench/limits/{workload}.json").write_text(
        json.dumps({"mean_logit_gap": limit}))
    return root


def off_chip(patch, root: pathlib.Path) -> None:
    """Point bench/run.py at `root` and skip its look for a chip, its
    compile cache and the strict check of the tiny configuration, through
    `patch` (pytest's `monkeypatch.setattr`, or plain `setattr` in a
    throwaway process)."""
    import jax

    from bench import cell, run

    patch(run, "ROOT", root)
    patch(run, "BENCH", root / "bench")
    patch(run, "OUT", root / ".bench_out")
    patch(run, "chips_or_exit", lambda n: jax.devices()[:n])
    patch(run, "setup_cache", lambda: "off")
    strict = cell.model_config
    patch(cell, "model_config",
          lambda conf, strict_=True: strict(conf, False))


def in_subprocess(code: str, devices: int = 4, timeout: int = 600) -> str:
    """Run `code` in a fresh Python that sees `devices` CPU devices (this
    process keeps its one), with the repo and these helpers importable;
    returns its stdout."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), str(pathlib.Path(__file__).parent),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n"
        f"{proc.stderr[-8000:]}")
    return proc.stdout


def drive(monkeypatch, capsys, root: pathlib.Path, *extra, seed=7,
          seconds="3", workload="tiny.chat"):
    """Run bench/run.py's main on the CPU through `root`; returns the
    parsed last stdout line."""
    from bench import run

    off_chip(monkeypatch.setattr, root)
    assert run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", seconds, "--trace", "0", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
