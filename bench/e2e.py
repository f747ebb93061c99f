"""End-to-end metrics of one run, from the harness's own records on the
host clock (`time.monotonic`).  Only a benchmark change edits this file.

Records (`serve_loop.Record`) hold each request's due time (open loop),
submit time and the time each of its tokens came back from `step()`.
"""
from __future__ import annotations

from typing import Dict, List


def in_window(recs, t_open: float, t_close: float):
    """Requests due inside the window (open loop: by due time; closed
    loop: by submit time)."""
    return [r for r in recs
            if t_open <= (r.due if r.due is not None else r.submit_t)
            < t_close]


def ttfts_ms(recs, t_open, t_close) -> List[float]:
    """Due time (submit time in a closed loop) to first token, for every
    request due in the window that got one."""
    return [(r.token_t[0] - (r.due if r.due is not None else r.submit_t))
            * 1e3 for r in in_window(recs, t_open, t_close) if r.token_t]


def itls_ms(recs, t_open, t_close) -> List[float]:
    """Every gap between consecutive tokens of a request whose later
    token came back inside the window."""
    out = []
    for r in recs:
        ts = r.token_t
        for a, b in zip(ts, ts[1:]):
            if t_open <= b <= t_close:
                out.append((b - a) * 1e3)
    return out


def tokens_in_window(recs, t_open, t_close) -> int:
    return sum(1 for r in recs for t in r.token_t if t_open <= t <= t_close)


def compute(names, recs, t_open, t_close, setup_s) -> Dict[str, float]:
    """The named end-to-end metrics of a run."""
    table = {
        "setup_s": lambda: setup_s,
        "tokens_per_s": lambda: tokens_in_window(recs, t_open, t_close)
        / (t_close - t_open),
    }
    return {n: table[n]() for n in names}


def samples(recs, t_open, t_close) -> Dict[str, int]:
    """Sample counts behind the percentiles, printed beside them."""
    return {"requests_due": len(in_window(recs, t_open, t_close)),
            "ttft_samples": len(ttfts_ms(recs, t_open, t_close)),
            "itl_samples": len(itls_ms(recs, t_open, t_close)),
            "tokens": tokens_in_window(recs, t_open, t_close)}
