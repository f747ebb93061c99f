"""Host time lost to the Python runtime's generation-2 garbage
collections while every slot was decoding: the summed durations of the
recorder's `host.gc` spans that start between the start of the first and
the end of the last full-slot engine step, in ms."""
from bench import engine_spans


def read(ctx):
    tel = ctx.telemetry
    pauses = getattr(tel, "gc_pauses", None)
    full = engine_spans.full_slot_steps(tel)
    if pauses is None or not full:
        return None
    a, b = full[0]["start"], full[-1]["end"]
    return 1e3 * sum(g["end"] - g["start"] for g in pauses
                     if a <= g["start"] <= b)
