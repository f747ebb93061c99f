"""Closed loop that keeps every slot busy: an offline batch job whose next
request is admitted as soon as one retires.

Mix keys: n_slots, max_len, pool (requests drawn; more than a window can
retire), prompt and output length distributions, warm_batches (the
largest is how many slots the driver fills a step during set-up).

The window has to open in the steady state of such a job, and every seed
has to do the same work in it.  So the first n_slots requests, which fill
the slots during set-up, get remaining outputs at stratified quantiles of
that steady state (`dists.residuals`), each lengthened by the tokens it
makes before the window opens: slots retire at the same window steps for
every seed.  The seed deals those remainders and a stratified set of
prompt lengths to the slots in an order of its own.  A window admits only
a few refills, and no order drawn from the seed would give each seed
alike ones, so the refills that follow take their sizes in one fixed
order (`dists.spread_order`) and the seed draws only their tokens.
"""
from __future__ import annotations

import numpy as np

from bench import dists

MODE = "closed"


def fill_per_step(mix) -> int:
    """Slots the driver fills in each set-up step."""
    return max(mix["warm_batches"])


def generate(mix, seed: int, seconds: float, vocab: int):
    n, slots = mix["pool"], mix["n_slots"]
    rng = np.random.default_rng([seed % (1 << 64), 2])
    order = dists.spread_order(n)
    plen = [dists.stratified(mix["prompt"], n)[i] for i in order]
    olen = [dists.stratified(mix["output"], n)[i] for i in order]
    plen[:slots] = [plen[i] for i in rng.permutation(slots)]
    rest = dists.residuals(mix["output"], slots)
    per, rounds = fill_per_step(mix), -(-slots // fill_per_step(mix))
    for i, j in enumerate(rng.permutation(slots)):
        # filled in set-up round i // per, a request has made this many
        # tokens when the window opens: its prefill's and one a step
        olen[i] = rest[j] + rounds - i // per + 1
    prompts = dists.prompt_tokens(plen, vocab, rng)
    return [{"prompt": p, "max_new": o, "due": None}
            for p, o in zip(prompts, olen)]
