"""bench/trace_reduce.py and the trace-fed metric readers on a small
trace of known layout: two chips' worth of planes are not needed, one
device plane with module and op lines and a host plane with the
harness's spans are."""
import dataclasses
import json
import types
from typing import List, Tuple

import pytest

from bench_tiny import ROOT

from bench import cell, trace_reduce

MS = 1_000_000          # ns


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: List[Tuple[str, str]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


KERNEL_OP = ("%_dequant_matmul.7 = f32[32,256]{1,0} custom-call(bf16[32,1536] "
             "%x, u32[16,1536] %p)")


def recorded():
    """Window 0-100 ms.  decode 10-30 (kernel ops 12-18, 20-26), prefill
    40-70 (kernel ops 42-60), decode 80-90 (kernel 81-89); a small eager
    op at 32-34.  Host: bench.step spans around each; idle 0-10."""
    ops = [Ev(KERNEL_OP, 12 * MS, 6 * MS), Ev(KERNEL_OP, 20 * MS, 6 * MS),
           Ev("fusion.1", 26 * MS, 4 * MS), Ev("fusion.2", 10 * MS, 2 * MS),
           Ev("argmax", 32 * MS, 2 * MS),
           Ev(KERNEL_OP, 42 * MS, 18 * MS), Ev("fusion.3", 60 * MS, 10 * MS),
           Ev(KERNEL_OP, 81 * MS, 8 * MS), Ev("fusion.4", 80 * MS, 1 * MS),
           Ev("fusion.5", 89 * MS, 1 * MS)]
    mods = [Ev("jit__decode_fn", 10 * MS, 20 * MS),
            Ev("jit_argmax", 32 * MS, 2 * MS),
            Ev("jit__prefill_fn", 40 * MS, 30 * MS),
            Ev("jit__decode_fn", 80 * MS, 10 * MS)]
    host = [Ev("bench.window", 0, 100 * MS),
            Ev("bench.step", 5 * MS, 30 * MS),
            Ev("bench.step", 36 * MS, 56 * MS),
            Ev("bench.idle", 92 * MS, 8 * MS)]
    return types.SimpleNamespace(planes=[
        Plane("/host:CPU", [Line("python", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", mods),
                                Line("XLA Ops", ops)])])


@pytest.fixture
def red():
    return trace_reduce.reduce(recorded())


def test_busy_union_and_window(red):
    assert red.window_s == pytest.approx(0.1)
    # busy: 10-18, 20-30, 32-34, 42-70, 80-90 = 8 + 10 + 2 + 28 + 10 ms
    assert red.busy_s == pytest.approx(0.058)


def test_kernel_time_inside_each_execution(red):
    kinds = [(e.kind, round(e.kernel_s * 1e3, 6)) for e in red.execs]
    assert kinds == [("decode", 12.0), ("other", 0.0), ("prefill", 18.0),
                     ("decode", 8.0)]
    assert red.kernel_ops == 4


def test_idle_gaps_labelled_by_host_span(red):
    got = sorted((round(s * 1e3, 6), lbl) for lbl, s in red.idle_gaps)
    # 0-10 (mid 5: first step), 18-20, 30-32, 34-42 (second step),
    # 70-80 (second step), 90-100 (mid 95: bench.idle)
    assert got == sorted([(10.0, "bench.step"), (2.0, "bench.step"),
                          (2.0, "bench.step"), (8.0, "bench.step"),
                          (10.0, "bench.step"), (10.0, "bench.idle")])


def test_periods_and_matching(red):
    # decode 10 -> prefill 40 and decode 80 -> window end 100
    assert trace_reduce.periods(red.execs, "decode", 0.1) == \
        pytest.approx(0.030 + 0.020)
    assert trace_reduce.periods(red.execs, "prefill", 0.1) == \
        pytest.approx(0.040)
    calls = [{"kind": "decode"}, {"kind": "prefill"}, {"kind": "decode"}]
    pairs = trace_reduce.match_calls(red.execs, calls)
    assert [(e.kind, c["kind"]) for e, c in pairs] == [
        ("decode", "decode"), ("prefill", "prefill"), ("decode", "decode")]


def test_readers_on_the_trace(red):
    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    ctx = types.SimpleNamespace(
        conf=conf, mix={"n_slots": 32}, trace=red, trace_end=0.1,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        calls=[{"kind": "decode", "rows": 32, "active": 2,
                "context": [100, 200]},
               {"kind": "prefill", "rows": 1, "bucket": 256, "lens": [200]},
               {"kind": "decode", "rows": 32, "active": 1,
                "context": [300]}])
    read = lambda n: cell.metric_reader(n).read(ctx)  # noqa: E731
    assert read("device.idle_share") == pytest.approx(42.0)
    assert read("step.decode_device_ms") == pytest.approx(15.0)
    from bench import flops
    least, _, _ = flops.least_time_s(flops.kernel_calls(conf, 32),
                                     ctx.peaks)
    assert read("dequant_matmul_roofline.decode") == pytest.approx(
        100 * 2 * least / 0.020)
    ops = sum(flops.decode_token_ops(conf, c) for c in (100, 200, 300))
    assert read("model.mfu.decode") == pytest.approx(
        100 * ops / (0.050 * 197e12))
