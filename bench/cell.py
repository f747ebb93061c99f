"""A cell of `BENCHMARK.json`, resolved to its files by name.

A workload names a configuration and a traffic mix; each is a data file
found by name (`bench/configs/<config>.json`, `bench/traffic/<mix>.json`),
and each per-layer metric a reader `bench/metrics/<metric>.py`.  Adding a
cell, a mix or a metric therefore adds files and an entry, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

# configuration-file key -> repro ModelConfig field
MODEL_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "attention_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "family": "family",
}
# ModelConfig fields a configuration file may set apart from the registry
NUMERICS = ("norm_eps",)


@dataclasses.dataclass
class Cell:
    name: str
    conf: Dict          # bench/configs/<config>.json
    traffic: Dict       # bench/traffic/<mix>.json
    chips: int
    end_to_end: List[Dict]   # the cell's end-to-end metrics
    per_layer: List[Dict]    # the cell's per-layer metrics


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench: pathlib.Path = BENCH) -> Cell:
    spec = load_spec(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(work)}")
    w = work[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    conf = json.loads((root / conf_entry["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name=name, conf=conf, traffic=traffic, chips=w["chips"],
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric readers, traffic generators,
    references have names that are not Python identifiers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "traffic" / f"{kind}.py", f"traffic_{kind}")


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "metrics" / f"{name}.py",
                       "metric_" + name.replace(".", "_").replace("-", "_"))


def reference_for(family: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "reference" / f"{family}.py",
                       f"reference_{family}")


def model_config(conf: Dict, strict: bool = True):
    """The program's ModelConfig for a configuration file: the program's
    registry entry with the file's values put in (the file is what is
    run).  `strict`: the file has to state the architecture exactly as
    the registry builds it; numerics that the file states, such as the
    norm's epsilon, are taken from the file either way."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    changes = {MODEL_FIELDS[k]: conf[k] for k in MODEL_FIELDS}
    rot = conf["rotary_dim"]
    changes["rotary_dim"] = 0 if rot == conf["head_dim"] else rot
    changes["dtype"] = conf["dtype"]
    new = dataclasses.replace(cfg, **changes)
    arch = {k: v for k, v in changes.items() if k not in NUMERICS}
    if strict and dataclasses.replace(cfg, **arch) != cfg:
        diff = {k: (getattr(cfg, k), v) for k, v in arch.items()
                if getattr(cfg, k) != v}
        raise ValueError(f"{conf['arch']}: configuration file differs from "
                         f"repro.configs: {diff}")
    return new
