"""The engine's own phase spans (`repro.serve.telemetry`), as the span
readers in `bench/metrics/` see them: the recorder's `engine.step` spans
in the order they ran, each with its direct children by name.

A program that records no spans gives an empty list, so each reader
returns nothing there rather than raising.
"""
from __future__ import annotations

from typing import Dict, List


def steps(tel) -> List[Dict]:
    """The root `engine.step` spans, in the order the engine ran them."""
    return sorted((s for s in getattr(tel, "spans", None) or ()
                   if s["name"] == "engine.step"), key=lambda s: s["id"])


def is_full(step: Dict, tel) -> bool:
    """Every slot of the engine decoded in this step."""
    n = getattr(tel, "n_slots", 0)
    return n > 0 and step["attrs"].get("active") == n


def full_slot_steps(tel) -> List[Dict]:
    return [s for s in steps(tel) if is_full(s, tel)]


def children(tel) -> Dict[int, Dict[str, Dict]]:
    """Span id -> {name: span} of its direct children."""
    out: Dict[int, Dict[str, Dict]] = {}
    for s in getattr(tel, "spans", None) or ():
        if s["parent"] is not None:
            out.setdefault(s["parent"], {})[s["name"]] = s
    return out


def wall(span: Dict) -> float:
    return span["end"] - span["start"]
