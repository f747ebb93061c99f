"""bench/trace_reduce.py and the trace-fed metric readers on a small
trace of known layout: one device plane (or four, as a mesh writes them)
with module and op lines, and a host plane with the harness's spans."""
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


def recorded(planes: int = 1, scale: float = 1.0):
    """Window 0-100 ms.  decode 10-30 (kernel ops 12-18, 20-26), prefill
    40-70 (kernel ops 42-60), decode 80-90 (kernel 81-89); a small eager
    op at 32-34.  Host: bench.step spans around each; idle 0-10.  Every
    time multiplied by `scale`; `planes` chips with the same lines."""
    ms = MS * scale
    ops = [Ev(KERNEL_OP, 12 * ms, 6 * ms), Ev(KERNEL_OP, 20 * ms, 6 * ms),
           Ev("fusion.1", 26 * ms, 4 * ms), Ev("fusion.2", 10 * ms, 2 * ms),
           Ev("argmax", 32 * ms, 2 * ms),
           Ev(KERNEL_OP, 42 * ms, 18 * ms), Ev("fusion.3", 60 * ms, 10 * ms),
           Ev(KERNEL_OP, 81 * ms, 8 * ms), Ev("fusion.4", 80 * ms, 1 * ms),
           Ev("fusion.5", 89 * ms, 1 * ms)]
    mods = [Ev("jit__decode_fn", 10 * ms, 20 * ms),
            Ev("jit_argmax", 32 * ms, 2 * ms),
            Ev("jit__prefill_fn", 40 * ms, 30 * ms),
            Ev("jit__decode_fn", 80 * ms, 10 * ms)]
    host = [Ev("bench.window", 0, 100 * ms),
            Ev("bench.step", 5 * ms, 30 * ms),
            Ev("bench.step", 36 * ms, 56 * ms),
            Ev("bench.idle", 92 * ms, 8 * ms)]
    return types.SimpleNamespace(planes=[
        Plane("/host:CPU", [Line("python", host)])] + [
        Plane(f"/device:TPU:{i}", [Line("XLA Modules", mods),
                                   Line("XLA Ops", ops)])
        for i in range(planes)])


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


def _ctx(conf, trace, end):
    return types.SimpleNamespace(
        conf=conf, mix={"n_slots": 32}, trace=trace, trace_end=end,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        calls=[{"kind": "decode", "rows": 32, "active": 2,
                "context": [100, 200]},
               {"kind": "prefill", "rows": 1, "bucket": 256, "lens": [200]},
               {"kind": "decode", "rows": 32, "active": 1,
                "context": [300]}])


def _parents_kernel_calls(conf, m):
    """bench/flops.kernel_calls as it read before it counted chips."""
    from bench import weights as W

    out = []
    for rows, cols in W.matrix_shapes(conf).values():
        lay = W.layout(rows, cols, conf["claq"])
        outliers = lay.n_top * lay.k_top + (cols - lay.n_top) * lay.k_rest
        for bits, k in ((lay.p_lo, cols - lay.n_hi), (lay.p_hi, lay.n_hi)):
            if k == 0:
                continue
            ops = 2.0 * m * rows * k
            nbytes = (rows * k * bits / 8 + (1 << bits) * k * 4
                      + outliers * k / cols * 8 + m * k * 2 + m * rows * 4)
            out.append((ops, nbytes))
    return out * conf["num_hidden_layers"]


def test_one_chip_readers_read_as_before(red):
    """Without a mesh the roofline and the mfu are, to the last bit, the
    formulas they had before they counted chips."""
    from bench import flops

    conf = json.loads((ROOT / "bench/configs/glm4_9b.json").read_text())
    ctx = _ctx(conf, red, 0.1)
    assert flops.kernel_calls(conf, 32) == _parents_kernel_calls(conf, 32)
    least, _, _ = flops.least_time_s(_parents_kernel_calls(conf, 32),
                                     ctx.peaks)
    decode = [e for e in red.execs if e.kind == "decode"]
    assert cell.metric_reader("dequant_matmul_roofline.decode").read(
        ctx) == 100.0 * least * len(decode) / sum(e.kernel_s for e in decode)
    ops = sum(flops.decode_token_ops(conf, c) for c in (100, 200, 300))
    period = trace_reduce.periods(red.execs, "decode", 0.1)
    assert cell.metric_reader("model.mfu.decode").read(ctx) == (
        100.0 * ops / (period * 197e12))


def test_four_chips_each_doing_a_quarter_read_as_one_doing_all():
    """Four device planes, each running every execution in a quarter of
    the time one chip takes to do all of it: the kernel time is summed
    over the planes, the decode steps' period is one chip's, and the
    peak is four chips'.  The mfu then reads as on the one chip doing
    all the work; the roofline reads as on that chip's trace given the
    same calls, which are each chip's own, summed."""
    from bench import flops

    conf = json.loads((ROOT / "bench/configs/qwen2_1p5b.json").read_text())
    mesh = dict(conf, mesh={"data": 1, "model": 4})
    four = trace_reduce.reduce(recorded(planes=4))
    one = trace_reduce.reduce(recorded(planes=1, scale=4.0))
    assert [(e.kind, e.name) for e in four.execs] == [
        (e.kind, e.name) for e in one.execs]
    for a, b in zip(four.execs, one.execs):
        assert a.kernel_s == pytest.approx(b.kernel_s, rel=1e-12)
    assert four.kernel_ops == 4 * one.kernel_ops
    assert four.busy_s == pytest.approx(one.busy_s / 4, rel=1e-12)

    read = lambda n, ctx: cell.metric_reader(n).read(ctx)  # noqa: E731
    roof = "dequant_matmul_roofline.decode"
    least, _, _ = flops.least_time_s(flops.kernel_calls(mesh, 32),
                                     {"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    assert read(roof, _ctx(mesh, four, 0.1)) == pytest.approx(
        read(roof, _ctx(mesh, one, 0.4)), rel=1e-12)
    assert read(roof, _ctx(mesh, four, 0.1)) == pytest.approx(
        100 * 2 * least / (4 * 0.020), rel=1e-12)
    mfu = "model.mfu.decode"
    assert read(mfu, _ctx(mesh, four, 0.1)) == pytest.approx(
        read(mfu, _ctx(conf, one, 0.4)), rel=1e-12)
    # the per-chip readers are left as they were: one chip's decode time
    assert read("step.decode_device_ms", _ctx(mesh, four, 0.1)) == \
        pytest.approx(15.0)


def test_a_chip_whose_clock_is_shifted_still_sums_into_its_twin():
    """Planes whose executions start a little apart (the chips' clocks,
    or the launch) still add their kernel time to the same execution."""
    pd = recorded(planes=2)
    shifted = recorded(planes=1, scale=1.0).planes[1]
    for ln in shifted.lines:
        ln.events = [Ev(e.name, e.start_ns + 0.5 * MS, e.duration_ns)
                     for e in ln.events]
    pd.planes[2] = shifted
    red = trace_reduce.reduce(pd)
    assert [round(e.kernel_s * 1e3, 6) for e in red.execs] == [
        24.0, 0.0, 36.0, 16.0]


def test_a_step_execution_with_no_twin_on_the_first_chip_is_refused():
    """A decode on another chip that overlaps no decode of the first
    chip (clocks too far apart) would leave its kernel time uncounted and
    the roofline reading high: the reducer refuses the trace."""
    pd = recorded(planes=2)
    lone = recorded(planes=1).planes[1]
    mods = next(ln for ln in lone.lines if ln.name == "XLA Modules")
    mods.events = [Ev(e.name, 35 * MS, 4 * MS) if i == 0 else e
                   for i, e in enumerate(mods.events)]
    pd.planes[2] = lone
    with pytest.raises(ValueError, match="overlaps no jit__decode_fn"):
        trace_reduce.reduce(pd)
