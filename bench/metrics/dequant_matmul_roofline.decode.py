"""Share of its roofline the CLAQ dequant-GEMM reaches inside decode
executions: the least time of its calls at the chip's peaks (per call the
larger of operations / peak FLOP/s and bytes / peak HBM bandwidth, from
the plan's shapes, bench/flops.py) over the summed device time of its
operations, in percent.  On a mesh both sides are summed over the chips:
each chip's calls, and each chip's kernel time."""
from bench import flops


def read(ctx):
    execs = [e for e in ctx.trace.execs if e.kind == "decode"]
    kernel_s = sum(e.kernel_s for e in execs)
    if not execs or kernel_s <= 0:
        return None
    least, _, _ = flops.least_time_s(
        flops.kernel_calls(ctx.conf, ctx.mix["n_slots"]), ctx.peaks)
    return 100.0 * least * len(execs) / kernel_s
