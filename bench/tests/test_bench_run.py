"""bench/run.py end to end at a CPU test size, with the look for a chip
skipped: an unbroken run is correct, and each fault a serving cell can
have, planted in the timed path, makes `correct` come out false."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BATCH, ROOT, drive, tiny_root

# the limit on the mean logit gap, the number the cells compare: the tiny
# model's program reads 0 to 4.4e-05 against the float32 reference (seeds
# 7-9), a token altered 0.058-0.080 and a decode that drops its cache
# 0.118-0.245
TINY_LIMIT = 0.01


WORKLOAD = json.loads((ROOT / "BENCHMARK.json").read_text())[
    "workloads"][0]["name"]


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and not p.stdout.strip()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"), limit=TINY_LIMIT)


def test_unbroken_run_is_correct(monkeypatch, capsys, root):
    out = drive(monkeypatch, capsys, root)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["mean_logit_gap"]["limit"] == TINY_LIMIT
    spec = json.loads((root / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert out["device"]["count"] == 1


def test_closed_loop_run_is_correct(monkeypatch, capsys, tmp_path):
    root = tiny_root(tmp_path, mix=BATCH, limit=TINY_LIMIT,
                     workload="tiny.batch")
    out = drive(monkeypatch, capsys, root, workload="tiny.batch")
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert out["attempted"] > 0


def _alter_token(monkeypatch):
    from repro.serve import engine

    orig = engine.ServingEngine._append_token

    def bad(self, req, t):
        if len(req.tokens) == 2:              # the third token produced
            t = (t + 1) % self.cfg.vocab
        return orig(self, req, t)
    monkeypatch.setattr(engine.ServingEngine, "_append_token", bad)


def _state_unchanged(monkeypatch):
    from repro.serve import engine

    orig = engine.ServingEngine.__init__

    def init(self, *a, **k):
        orig(self, *a, **k)
        dec = self._decode

        def stale(p, t, c, iv):
            logits, _, nf = dec(p, t, c, iv)
            return logits, c, nf              # the decode's cache dropped
        self._decode = stale
    monkeypatch.setattr(engine.ServingEngine, "__init__", init)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_in_timed_path_is_not_correct(monkeypatch, capsys, root,
                                            fault):
    fault(monkeypatch)
    out = drive(monkeypatch, capsys, root)
    assert out["correct"] is False
    assert out["checks"]["mean_logit_gap"]["value"] > TINY_LIMIT
