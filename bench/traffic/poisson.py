"""Open-loop Poisson arrivals: independent users who send on a schedule
whatever the server's state.  Every request is timed from its due time.

Mix keys: rate_per_s, preroll_s (arrivals before the window, sent during
set-up so that the window starts loaded), prompt and output length
distributions (bench/dists.py), n_slots, max_len.
"""
from __future__ import annotations

import math

import numpy as np

from bench import dists

MODE = "open"
TAIL_S = 10.0     # arrivals keep coming this long past the window's close


def generate(mix, seed: int, seconds: float, vocab: int):
    """Requests with due times in seconds from the window's start; those
    due before 0 are the pre-roll."""
    rate = mix["rate_per_s"]
    pre = mix["preroll_s"]
    n = max(1, math.ceil(rate * (pre + seconds + TAIL_S)))
    rng = np.random.default_rng([seed % (1 << 64), 1])
    gaps = dists.poisson_gaps(rate, n, rng)
    plen = dists.sizes(mix["prompt"], n, rng)
    olen = dists.sizes(mix["output"], n, rng)
    prompts = dists.prompt_tokens(plen, vocab, rng)
    due = np.cumsum(gaps) - gaps[0] - pre
    return [{"prompt": p, "max_new": o, "due": float(t)}
            for p, o, t in zip(prompts, olen, due)]
