"""Traffic generators: a seed reproduces its requests byte for byte, and
seeds change only the order of one set of sizes and gaps."""
import json

import pytest

from bench_tiny import ROOT

from bench import cell

MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))


def gen(name, seed, seconds=20.0):
    mix = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
    return mix, cell.traffic_kind(mix["kind"]).generate(mix, seed, seconds,
                                                        1000)


@pytest.mark.parametrize("name", MIXES)
def test_seed_reproduces_bytes(name):
    _, a = gen(name, 2**35 + 11)
    _, b = gen(name, 2**35 + 11)
    assert json.dumps(a).encode() == json.dumps(b).encode()
    _, c = gen(name, 2**35 + 12)
    assert json.dumps(a) != json.dumps(c)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_sizes(name):
    mix, a = gen(name, 1)
    _, b = gen(name, 2)
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    if mix["kind"] == "poisson":
        assert sorted(r["max_new"] for r in a) == sorted(
            r["max_new"] for r in b)
        assert all(y["due"] >= x["due"] for x, y in zip(a, a[1:]))
    for r in a:
        assert len(r["prompt"]) + r["max_new"] <= mix["max_len"]
        assert (mix["prompt"]["min"] <= len(r["prompt"])
                <= mix["prompt"]["max"])
