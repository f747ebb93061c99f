"""What admitting the window's requests cost the engine, per thousand
prompt tokens: over the engine steps that admitted a request of the
window, the sum of each step's wall time less the median wall time of
the full-slot steps that admitted nothing, over the admitted requests'
real (unpadded) prompt tokens, in ms per 1000 tokens.  Read from the
engine's `engine.step` spans on the host clock."""
import statistics

from bench import engine_spans


def read(ctx):
    tel = ctx.telemetry
    prompt = {r.uid: len(r.prompt) for r in ctx.window_recs}
    steps = engine_spans.steps(tel)
    plain = [engine_spans.wall(s) for s in steps
             if engine_spans.is_full(s, tel)
             and not s["attrs"].get("admitted")]
    refills = [s for s in steps
               if any(u in prompt for u in s["attrs"].get("admitted", ()))]
    if not plain or not refills:
        return None
    base = statistics.median(plain)
    extra_s = sum(engine_spans.wall(s) - base for s in refills)
    tokens = sum(prompt[u] for s in refills
                 for u in s["attrs"]["admitted"] if u in prompt)
    return 1e6 * extra_s / tokens
