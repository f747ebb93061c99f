"""The harness on a mesh, at a CPU test size, each case in a fresh Python
that sees four CPU devices: the weights drawn per chip are the one-device
weights bit for bit, each chip holding its share; a configuration at
Qwen3-32B widths splits to a share that fits a v5e; the tiny cell served
on a mesh through `run.measure` is correct, compiles nothing inside its
window, and serves the tokens the one-device engine serves."""
import json

from bench_tiny import in_subprocess

SHARDED_BUILD = r"""
import json
import jax, numpy as np
from bench_tiny import ROOT, TINY
from bench import weights as W
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
for name, extra in (("glm4_9b", {"qk_norm": True}), ("qwen2_1p5b", {})):
    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    conf.update(TINY, num_hidden_layers=4, rotary_dim=16, **extra)
    one = jax.tree_util.tree_leaves_with_path(W.served_params(2**40 + 5, conf))
    got = jax.tree_util.tree_leaves_with_path(
        W.served_params(2**40 + 5, conf, mesh))
    assert [p for p, _ in one] == [p for p, _ in got], name
    held = {}
    for (path, a), (_, b) in zip(one, got):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), (name, path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} {path}")
        for s in b.addressable_shards:
            held[s.device] = held.get(s.device, 0) + s.data.nbytes
    total = sum(a.nbytes for _, a in one)
    assert set(held) == set(mesh.devices.flat), held
    assert all(v == total // 4 for v in held.values()), (name, total, held)
    print(name, total, sorted(held.values()))
"""


def test_sharded_build_is_the_one_device_build():
    out = in_subprocess(SHARDED_BUILD, timeout=600)
    assert "glm4_9b" in out and "qwen2_1p5b" in out


QWEN3_32B = r"""
import json, pathlib, shutil
import jax, numpy as np
from bench_tiny import ROOT
from bench import cell, run
from bench import weights as W

conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
conf.update(arch="qwen3_32b", source="https://huggingface.co/Qwen/Qwen3-32B",
            num_hidden_layers=64, hidden_size=5120, num_attention_heads=64,
            num_key_value_heads=8, head_dim=128, intermediate_size=25600,
            vocab_size=151936, rotary_dim=128, rope_theta=1000000.0,
            attention_bias=False, tie_word_embeddings=False,
            rms_norm_eps=1e-06, qk_norm=True, mesh={"data": 1, "model": 4})
root = pathlib.Path(TMP)
shutil.copytree(ROOT / "bench", root / "bench",
                ignore=shutil.ignore_patterns("tests", "__pycache__"))
(root / "bench/configs/qwen3_32b.json").write_text(json.dumps(conf))
spec = json.loads((ROOT / "BENCHMARK.json").read_text())
spec["configs"].append({"name": "qwen3_32b", "source": conf["source"],
                        "file": "bench/configs/qwen3_32b.json",
                        "reduced": [], "why": "test"})
spec["workloads"].append({"name": "qwen3_32b.batch-4chip",
                          "config": "qwen3_32b", "traffic": "batch",
                          "chips": 4, "why": "test"})
(root / "BENCHMARK.json").write_text(json.dumps(spec))
c = cell.load_cell("qwen3_32b.batch-4chip", root, root / "bench")
cfg = cell.model_config(c.conf)          # strict: as repro.configs builds it
assert cfg.qk_norm and cfg.n_layers == 64 and c.chips == 4
devs = jax.devices()[:c.chips]
mesh = run.mesh_for(c.conf, devs)
assert dict(mesh.shape) == {"data": 1, "model": 4}
assert set(mesh.devices.flat) == set(devs)
# the shardings the compiled build gives its outputs, compiled and never
# run: each leaf's shard on a chip, as the build lays it out
lowered = W._build_sharded.lower(
    W.base_key(3), W.model_items(c.conf),
    tuple(sorted(c.conf["claq"].items())), mesh)
compiled = lowered.compile()
leaves = jax.tree_util.tree_leaves(lowered.out_info)
shardings = jax.tree_util.tree_leaves(compiled.output_shardings)
assert len(leaves) == len(shardings)
per_chip = total = 0
for leaf, sh in zip(leaves, shardings):
    assert sh.device_set == set(devs), sh
    per_chip += int(np.prod(sh.shard_shape(leaf.shape))) * leaf.dtype.itemsize
    total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
assert per_chip * 4 == total, (per_chip, total)
assert per_chip <= 4.5e9, per_chip
print(json.dumps({"total": total, "per_chip": per_chip}))
"""


def test_qwen3_32b_width_cell_splits_to_a_chip_share(tmp_path):
    """A four-chip cell at Qwen3-32B's published widths, with qk-norm and
    a 1 x 4 mesh, loads from added files alone, passes the strict check
    against repro.configs, and its weights come to at most 4.5 GB a chip
    (about 15.2 GB in all)."""
    code = QWEN3_32B.replace("TMP", repr(str(tmp_path)))
    got = json.loads(in_subprocess(code, timeout=300)
                     .strip().splitlines()[-1])
    assert 15e9 < got["total"] < 16e9


MESH_RUN = r"""
import json, pathlib, types
from bench_tiny import BATCH, off_chip, tiny_root
from bench import cell, check, run, serve_loop

tmp = pathlib.Path(TMP)
served = {}
sample = check.sample

def keep_tokens(done, seed):
    served[name] = {" ".join(map(str, r.prompt)): list(r.req.tokens)
                    for r in done if r.req is not None and r.req.done}
    return sample(done, seed)

check.sample = keep_tokens
stalls = run.stalls
obtained = {}

def keep_window_programs(tel, t_open, t_close):
    obtained[name] = [c["fun"] for c in tel.compiles
                      if t_open <= c["t"] <= t_close]
    return stalls(tel, t_open, t_close)

run.stalls = keep_window_programs
out = {}
for name, extra, chips in (
        ("one", {}, 1),
        ("mesh", {"mesh": {"data": 2, "model": 2}}, 4),
        ("mesh1x4", {"mesh": {"data": 1, "model": 4}}, 4)):
    root = tiny_root(tmp / name, mix=BATCH, limit=0.01,
                     workload="tiny.batch", chips=chips,
                     conf_extra=dict(num_hidden_layers=4, **extra))
    off_chip(setattr, root)
    c = cell.load_cell("tiny.batch", root, root / "bench")
    devs = run.chips_or_exit(c.chips)
    args = types.SimpleNamespace(seed=11, seconds=3.0, trace=0)
    res = run.measure(args, c, devs, [], serve_loop.clock())
    out[name] = {"correct": res["correct"], "count": res["device"]["count"],
                 "checks": res["checks"], "in_window": obtained[name]}
# where two runs part, both tokens go to the reference at that position
for mesh in ("mesh", "mesh1x4"):
    both = set(served["one"]) & set(served[mesh])
    parted = []
    for p in sorted(both):
        a, b = served["one"][p], served[mesh][p]
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is None:
            continue
        prompt = [int(t) for t in p.split()] + a[:i]
        for t in (a[i], b[i]):
            parted.append(types.SimpleNamespace(
                prompt=prompt, req=types.SimpleNamespace(tokens=[t])))
    out[mesh].update(compared=len(both), parted=len(parted) // 2)
    out[mesh]["tie_gap"] = (check.widest_gap(
        cell.reference_for("dense"), c.conf, 11, parted)["max_logit_gap"]
        if parted else 0.0)
print(json.dumps(out))
"""


# Where the mesh and the one-device engine part, both tokens lie within
# this of the reference's best: a tie at bfloat16 rounding.  The tiny
# model's logits are near-uniform (largest ~0.7, where a bfloat16 step is
# 0.004), so the program's mesh, which partitions the bfloat16 dense
# layers differently, parts from one device on about a third of the
# requests (the engine alone, given the same weights, does so too).  At
# seed 11, 28 of 64 requests part and read at most 0.0185; with every chip
# drawing the first chip's layers the mesh parts on all 64, reads 1.30,
# and is not correct.
TIE = 0.03


def test_mesh_cell_is_correct_and_serves_the_one_device_tokens(tmp_path):
    """The configuration file's mesh reaches `ServingEngine` through
    `run.measure`: on a 2x2 and a 1x4 CPU mesh the tiny closed-loop cell
    is correct, obtains no program inside its window, and every request that both runs finished got the tokens it
    got on one device, up to the point where the reference itself ranks
    the two runs' tokens within rounding of each other."""
    code = MESH_RUN.replace("TMP", repr(str(tmp_path)))
    got = json.loads(in_subprocess(code, timeout=900)
                     .strip().splitlines()[-1])
    assert got["one"]["correct"] and got["one"]["in_window"] == [], got
    for mesh in ("mesh", "mesh1x4"):
        m = got[mesh]
        assert m["correct"] and m["count"] == 4, got
        # on a mesh a decode after a decode can be a program of its own
        # (its cache in the layout the last decode left, not the one an
        # insert pins), which the warm-up has to have run
        assert m["in_window"] == [], got
        assert m["compared"] >= 4 and m["tie_gap"] <= TIE, got


MESH_DEVICES = r"""
import jax
from bench import run

host = jax.devices()
for conf, devs in (({"mesh": {"data": 1, "model": 2}}, host[2:]),
                   ({"mesh": {"data": 2, "model": 2}}, host[::-1])):
    mesh = run.mesh_for(conf, devs)
    assert sorted(d.id for d in mesh.devices.flat) == sorted(
        d.id for d in devs), (conf, mesh.devices)
    assert all(str(t) == "AxisType.Auto" for t in mesh.axis_types), mesh
assert run.mesh_for({}, host[:1]) is None
print("ok")
"""


def test_mesh_is_built_over_the_chips_the_cell_was_given():
    """A cell's mesh spans the devices `chips_or_exit` handed it, not the
    host's first ones, with Auto axes as the program's launcher builds
    it; a configuration with no mesh gets none."""
    assert in_subprocess(MESH_DEVICES, timeout=300).strip() == "ok"


MESH_WEIGHTS_CHECK = r"""
import json
import jax
from bench_tiny import ROOT, TINY
from bench import mesh_weights, run, weights as W

conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
conf.update(TINY, num_hidden_layers=4, rotary_dim=16,
            mesh={"data": 1, "model": 4})
mesh = run.mesh_for(conf, jax.devices()[:4])
good = mesh_weights.compare(2**33 + 17, conf, mesh)
# a build whose chips all draw the first chip's layers must be caught
# (the one-device build stays compiled as it was)
real = W._stacks
W._stacks = lambda key, mc, claq, layers: real(key, mc, claq,
                                               layers % layers.shape[0])
W._build_sharded.clear_cache()
bad = mesh_weights.compare(2**33 + 17, conf, mesh)
print(json.dumps({"good": good, "bad": bad}))
"""


def test_chip_weights_check_passes_the_build_and_catches_a_wrong_one():
    """bench/mesh_weights.py's comparison, at a CPU size on a 1 x 4 mesh:
    the per-chip build matches the one-device build shard by shard with
    equal shares, and a build in which every chip draws the first chip's
    layers does not."""
    got = json.loads(in_subprocess(MESH_WEIGHTS_CHECK, timeout=300)
                     .strip().splitlines()[-1])
    good, bad = got["good"], got["bad"]
    assert good["mismatched"] == 0 and good["equal_shares"], good
    assert good["shards"] == 4 * good["leaves"], good
    assert bad["mismatched"] > 0, bad
