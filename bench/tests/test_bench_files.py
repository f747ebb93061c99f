"""BENCHMARK.json against the benchmark's contract, and every file it
names found by name; a cell, a mix and a metric added as files alone."""
import json
import re

import pytest

from bench_tiny import ROOT

from bench import cell

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
    for m in c.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)


def test_configs_hold_their_sources():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_new_cell_from_files_alone(tmp_path):
    """A later cell: a config, a mix and a metric written as new files,
    plus entries in BENCHMARK.json, load with no edit to existing files."""
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
    c = cell.load_cell("other.slowchat", root, root / "bench")
    assert c.traffic["rate_per_s"] == 2.0 and c.conf == conf
    names = [m["name"] for m in c.per_layer]
    assert "new.metric_ms" in names
    assert cell.metric_reader("new.metric_ms", root / "bench").read(
        None) == 1.5
