"""Device time of one execution of the engine's decode program
(`jit__decode_fn`), mean over the traced window, from the device trace."""


def read(ctx):
    ds = [e.end - e.start for e in ctx.trace.execs if e.kind == "decode"]
    return 1e3 * sum(ds) / len(ds) if ds else None
