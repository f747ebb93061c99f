"""BENCHMARK.json against the benchmark's contract, and every file it
names found by name; a cell, a mix and a metric added as files alone."""
import json
import re

import pytest

from bench_tiny import ROOT

from bench import cell, check

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_what_its_metrics_need():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for name in cells:
        c = cell.load_cell(name)
        got = {m["name"] for m in c.end_to_end}
        assert "setup_s" in got and len(got) >= 2 and c.per_layer
        for m in c.per_layer:
            assert m["moves"] in got, (name, m["name"])
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= set(cells)


@pytest.mark.parametrize("w", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(w):
    c = cell.load_cell(w)
    cell.model_config(c.conf)              # states the program's config
    assert c.traffic["kind"] and cell.traffic_kind(c.traffic["kind"]).MODE
    assert (ROOT / "bench/limits" / f"{w}.json").exists()
    cell.reference_for(c.conf["family"])
    assert callable(cell.weights_for(c.conf["family"]).served_params)
    for m in c.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)


def test_configs_hold_their_sources():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


QWEN3_32B = {
    "arch": "qwen3_32b", "family": "dense",
    "source": "https://huggingface.co/Qwen/Qwen3-32B",
    "num_hidden_layers": 64, "hidden_size": 5120, "num_attention_heads": 64,
    "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 25600,
    "vocab_size": 151936, "rotary_dim": 128, "rope_theta": 1000000.0,
    "attention_bias": False, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-06, "qk_norm": True, "reduced": [],
    "dtype": "bfloat16", "mesh": {"data": 1, "model": 4},
    "engine": {"min_bucket": 128}}


def test_new_cell_from_files_alone(tmp_path):
    """A later cell: a config, a mix and a metric written as new files,
    plus entries in BENCHMARK.json, load with no edit to existing files;
    so does a four-chip cell whose configuration states qk-norm and a
    mesh."""
    import shutil

    root = tmp_path / "r"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    (root / "bench/configs/other.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix["rate_per_s"] = 2.0
    (root / "bench/traffic/slowchat.json").write_text(json.dumps(mix))
    (root / "bench/metrics/new.metric_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    spec["configs"].append({"name": "other", "source": conf["source"],
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other.slowchat", "config": "other",
                              "traffic": "slowchat", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "new.metric_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "jitted steps",
                              "moves": "tokens_per_s",
                              "workloads": ["other.slowchat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    conf4 = dict(QWEN3_32B, claq=conf["claq"])
    (root / "bench/configs/qwen3_32b.json").write_text(json.dumps(conf4))
    (root / "bench/traffic/chat4.json").write_text(json.dumps(mix))
    (root / "bench/limits/qwen3_32b.chat4.json").write_text(
        json.dumps({"mean_logit_gap": 0.01}))
    spec["configs"].append({"name": "qwen3_32b", "source": conf4["source"],
                            "file": "bench/configs/qwen3_32b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "qwen3_32b.chat4",
                              "config": "qwen3_32b", "traffic": "chat4",
                              "chips": 4, "why": "test"})
    spec["workloads"].append({"name": "qwen3_32b.chat1",
                              "config": "qwen3_32b", "traffic": "chat4",
                              "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = cell.load_cell("other.slowchat", root, root / "bench")
    assert c.traffic["rate_per_s"] == 2.0 and c.conf == conf
    c4 = cell.load_cell("qwen3_32b.chat4", root, root / "bench")
    assert c4.chips == 4 and cell.mesh_shape(c4.conf) == (1, 4)
    cfg = cell.model_config(c4.conf)       # strict, qk_norm compared
    assert cfg.qk_norm and cfg.n_layers == 64
    assert check.limits_for("qwen3_32b.chat4", root / "bench") == {
        "mean_logit_gap": 0.01}
    with pytest.raises(ValueError, match="mesh of 1 x 4"):
        cell.load_cell("qwen3_32b.chat1", root, root / "bench")
    # a file that leaves qk_norm out states it false, which Qwen3 is not
    no_qk = {k: v for k, v in c4.conf.items() if k != "qk_norm"}
    with pytest.raises(ValueError, match="qk_norm"):
        cell.model_config(no_qk)
    names = [m["name"] for m in c.per_layer]
    assert "new.metric_ms" in names
    assert cell.metric_reader("new.metric_ms", root / "bench").read(
        None) == 1.5
