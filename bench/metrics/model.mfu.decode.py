"""Whole-step model FLOP utilization of decode: operations of the tokens
the decode steps produced (2 x matmul parameters + attention over each
slot's live context, bench/flops.py) over the wall period of those steps
(from each decode execution's start to the next step program's start,
idle gaps included) times the peak of all the chips of the configuration's
mesh, in percent."""
from bench import cell, flops, trace_reduce


def read(ctx):
    ops = 0.0
    n = 0
    for e, call in trace_reduce.match_calls(ctx.trace.execs, ctx.calls):
        if e.kind == "decode" and call is not None:
            ops += sum(flops.decode_token_ops(ctx.conf, c)
                       for c in call["context"])
            n += 1
    period = trace_reduce.periods(ctx.trace.execs, "decode",
                                  ctx.trace_end)
    if n == 0 or period <= 0:
        return None
    data, model = cell.mesh_shape(ctx.conf)
    return 100.0 * ops / (period * ctx.peaks["bf16_flops"] * data * model)
