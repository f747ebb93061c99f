"""Drives the program's `ServingEngine` through `submit()` and `step()`
and records, on the host clock, when each request was due, submitted and
when each of its tokens came back.

The driver is one thread: it submits what is due, calls `step()` (which
admits, prefills, decodes and syncs the sampled tokens to the host), and
reads new tokens off the engine's request objects.  A token's time is the
time `step()` returned it.  Its own `jax.profiler.TraceAnnotation` spans
(`bench.submit`, `bench.step`, `bench.idle`) label what the host was doing
in a traced run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

clock = time.monotonic


@dataclasses.dataclass
class Record:
    prompt: List[int]
    max_new: int
    due: Optional[float]          # absolute due time (open loop)
    submit_t: float = 0.0
    uid: int = -1
    token_t: List[float] = dataclasses.field(default_factory=list)
    req: object = None            # the engine's Request, once admitted
    rejected: bool = False


class Driver:
    def __init__(self, eng, mode: str, requests: List[Dict]):
        self.eng = eng
        self.mode = mode
        self.pending = list(requests)      # not yet submitted, in order
        self.recs: List[Record] = []
        self.live: Dict[int, Record] = {}  # submitted, not yet done
        self.done: List[Record] = []
        self.by_uid: Dict[int, Record] = {}
        self.calls: Optional[List] = None  # per-step log while tracing
        self.steps = 0

    # -- submission ------------------------------------------------------
    def submit(self, item: Dict, due: Optional[float]) -> None:
        from repro.serve import AdmissionRejected

        rec = Record(item["prompt"], item["max_new"], due)
        rec.submit_t = clock()
        self.recs.append(rec)
        try:
            rec.uid = self.eng.submit(rec.prompt, max_new_tokens=rec.max_new)
        except AdmissionRejected:
            rec.rejected = True
            return
        self.live[rec.uid] = rec
        self.by_uid[rec.uid] = rec

    def submit_due(self, t0: float, now: float) -> None:
        """Open loop: every request whose due time (seconds from t0) has
        passed."""
        with jax.profiler.TraceAnnotation("bench.submit"):
            while self.pending and t0 + self.pending[0]["due"] <= now:
                item = self.pending.pop(0)
                self.submit(item, t0 + item["due"])

    def refill(self) -> None:
        """Closed loop: one new request for each slot that retired."""
        with jax.profiler.TraceAnnotation("bench.submit"):
            free = self.eng.n_slots - len(self.live)
            for _ in range(min(free, len(self.pending))):
                self.submit(self.pending.pop(0), None)

    # -- stepping --------------------------------------------------------
    def step(self) -> None:
        eng = self.eng
        tel = eng.telemetry
        n_ev = len(tel.events) if self.calls is not None else 0
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        now = clock()
        self.steps += 1
        for uid, rec in list(self.live.items()):
            req = rec.req
            if req is None:
                req = eng.active.get(uid) or eng.finished.get(uid)
                if req is None:
                    continue                      # still queued
                rec.req = req
            n = len(req.tokens)
            if n > len(rec.token_t):
                rec.token_t.extend([now] * (n - len(rec.token_t)))
            if req.done:
                del self.live[uid]
                self.done.append(rec)
        if self.calls is not None:
            self._log_calls(tel.events[n_ev:])

    def _log_calls(self, events) -> None:
        """The device programs this step ran, in order, with what the
        trace reducer needs to count their work."""
        for ev in events:
            if ev["kind"] == "admit":
                lens = [len(self.by_uid[u].prompt) for u in ev["uids"]]
                self.calls.append({"kind": "prefill", "rows": ev["batch"],
                                   "bucket": ev["bucket"], "lens": lens})
            elif ev["kind"] == "step" and ev.get("mode") == "decode":
                fills = [len(r.prompt) + len(r.req.tokens) - 1
                         for r in (self.by_uid[u] for u in ev["uids"])]
                self.calls.append({"kind": "decode", "rows": self.eng.n_slots,
                                   "active": len(ev["uids"]),
                                   "context": fills})

    def busy(self) -> bool:
        return bool(self.eng.active) or bool(len(self.eng.queue))

    # -- phases ----------------------------------------------------------
    def run_until(self, t0: float, t_end: float) -> float:
        """Serve until the clock passes `t_end` (open loop: sending what
        falls due; closed loop: refilling retired slots); returns the time
        the last step ended."""
        now = clock()
        while now < t_end:
            if self.mode == "open":
                self.submit_due(t0, now)
            else:
                self.refill()
            if self.busy():
                self.step()
            elif self.mode == "open" and self.pending:
                nxt = t0 + self.pending[0]["due"]
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, t_end) - clock()))
            else:
                break
            now = clock()
        return now

    def fill_slots(self, per_step: int) -> None:
        """Closed loop set-up: submit the first n_slots requests at most
        `per_step` at a time (a bigger admission group would need a
        prefill shape the window never uses), stepping until every slot
        is taken."""
        while len(self.live) < self.eng.n_slots and self.pending:
            for _ in range(min(per_step, self.eng.n_slots - len(self.live),
                               len(self.pending))):
                self.submit(self.pending.pop(0), None)
            self.step()

    def drain(self, t_open: float, t_close: float, t0: float,
              limit_s: float) -> None:
        """After the window: keep serving (and sending what falls due)
        until every request due in the window has its first token, or
        `limit_s` has passed."""
        waiting = [r for r in self.recs if not r.rejected
                   and t_open <= (r.due if r.due is not None else r.submit_t)
                   < t_close]
        t_lim = clock() + limit_s
        while clock() < t_lim and any(not r.token_t for r in waiting):
            if self.mode == "open":
                self.submit_due(t0, clock())
            if self.busy():
                self.step()
            else:
                time.sleep(0.001)
