"""Host time between decode steps with no decode queued on the device:
the median, over consecutive engine steps in which every slot decoded,
of the end of one step's `engine.decode.sync` (the host holds the tokens,
so that decode has finished) to the end of the next step's
`engine.decode.dispatch` (the next decode is queued), in ms.  Read from
the engine's own spans on the host clock."""
import statistics

from bench import engine_spans


def read(ctx):
    tel = ctx.telemetry
    steps = engine_spans.steps(tel)
    kids = engine_spans.children(tel)
    gaps = []
    for a, b in zip(steps, steps[1:]):
        if not (engine_spans.is_full(a, tel) and engine_spans.is_full(b, tel)):
            continue
        sync = kids.get(a["id"], {}).get("engine.decode.sync")
        dispatch = kids.get(b["id"], {}).get("engine.decode.dispatch")
        if sync is not None and dispatch is not None:
            gaps.append(dispatch["end"] - sync["end"])
    return 1e3 * statistics.median(gaps) if gaps else None
