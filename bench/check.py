"""Whether what the timed path served is correct.

After the window closes (and the program's state is freed), a sample of
the requests the engine finished, drawn from the seed and always holding
the one with the most served tokens, is run once through the plain
reference over prompt + served tokens.  For each served token the gap by
which its reference logit lies below the reference's best at that
position is read; the numbers compared are the widest gap and the mean
gap.  Under greedy decoding a correct server's tokens are the reference's
argmax up to rounding, so the gaps stay small; a token altered where it
is produced, or numerics of a lower precision than the configuration
states, reads far above them.  The limits are per cell
(`bench/limits/<workload>.json`), set from readings (see PERF.md).
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import numpy as np

# served tokens a sample should hold: some hundreds
SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 8


def sample(done: List, seed: int) -> List:
    """Finished requests to compare: the longest served, then others in an
    order drawn from the seed until SAMPLE_TOKENS served tokens."""
    fin = [r for r in done if r.req is not None
           and r.req.state.value == "finished" and r.req.tokens]
    if not fin:
        return []
    fin.sort(key=lambda r: (-len(r.req.tokens), r.uid))
    rest = fin[1:]
    order = np.random.default_rng([seed % (1 << 64), 3]).permutation(
        len(rest))
    out, total = [fin[0]], len(fin[0].req.tokens)
    for i in order:
        if total >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        total += len(rest[i].req.tokens)
    return out


def widest_gap(ref_module, conf: Dict, seed: int, recs: List) -> Dict:
    """Run the reference over each sampled request's prompt + served
    tokens; returns the widest logit gap and how many tokens it covers."""
    seqs, pos, served = [], [], []
    for r in recs:
        toks = list(r.req.tokens)
        n = len(r.prompt)
        seqs.append(list(r.prompt) + toks[:-1])
        pos.append(list(range(n - 1, n - 1 + len(toks))))
        served.append(toks)
    S = max(len(s) for s in seqs)
    P = max(len(p) for p in pos)
    tokens = np.zeros((len(seqs), S), np.int32)
    positions = np.zeros((len(seqs), P), np.int32)
    for i, (s, p) in enumerate(zip(seqs, pos)):
        tokens[i, :len(s)] = s
        positions[i, :len(p)] = p
        positions[i, len(p):] = p[-1]
    logits = np.asarray(ref_module.logits(conf, seed, tokens, positions))
    gaps = []
    for i, toks in enumerate(served):
        lg = logits[i, :len(toks)]
        gaps.append(lg.max(axis=-1) - lg[np.arange(len(toks)), toks])
    gaps = np.concatenate(gaps)
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "tokens_compared": int(gaps.size),
            "requests_compared": len(recs),
            "argmax_agree": int((gaps == 0).sum())}


# the numbers a limit file may hold, each with its own limit
NUMBERS = ("max_logit_gap", "mean_logit_gap")


def limits_for(workload: str, bench: pathlib.Path) -> Dict[str, float]:
    """The cell's limits ({} where none is set yet)."""
    path = bench / "limits" / f"{workload}.json"
    if not path.exists():
        return {}
    return {k: v for k, v in json.loads(path.read_text()).items()
            if k in NUMBERS}
