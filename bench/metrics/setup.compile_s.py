"""Seconds the program spent obtaining its executables from the time the
engine's recorder was attached to the end of the run: per executable the
trace, lower and compile (or persistent-cache load) seconds that
`jax.monitoring` reports, summed over the recorder's `compile` records."""


def read(ctx):
    recs = getattr(ctx.telemetry, "compiles", None)
    return sum(c["s"] for c in recs) if recs else None
