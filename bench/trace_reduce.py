"""From a profiler trace (`.xplane.pb`, read with `jax.profiler.ProfileData`)
to what the per-layer metrics need.

On a TPU the profiler writes one plane per chip (`/device:TPU:<n>`) with a
line of program executions (`XLA Modules`) and a line of operations
(`XLA Ops`), and a host plane whose `python` line holds the harness's
`TraceAnnotation` spans.  The reducer finds:

* the traced window: the harness's `bench.window` span;
* device busy time: the union of the operation intervals inside it;
* executions of the engine's jitted steps, by name (`jit__decode_fn`,
  `jit__prefill_fn`, the names `ServingEngine` gives them), each with its
  device interval on the first chip and the summed time of the CLAQ
  kernel's operations inside it on every chip (on a mesh each chip runs
  the same execution on its share of the work; a step execution on
  another chip that overlaps none of the first chip's is refused);
* the longest idle gaps, each labelled by the innermost host span open
  at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

# program executions of the engine's steps
STEP_PROGRAMS = {"decode": re.compile(r"_decode_fn"),
                 "prefill": re.compile(r"_prefill_fn")}
# the CLAQ dequant-GEMM's device operations: the pallas_call carries no
# name of its own; on a v5e its ops are custom-calls named after the
# jitted wrapper, "%_dequant_matmul.<n> = f32[..] custom-call(...)"
KERNEL = re.compile(r"^%?_dequant_matmul(\.\d+)?\b")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Exec:
    kind: str            # decode | prefill | other
    name: str
    start: float         # seconds, on the trace clock
    end: float
    kernel_s: float = 0.0


@dataclasses.dataclass
class Reduced:
    window_s: float
    window_end: float
    busy_s: float
    execs: List[Exec]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    kernel_ops: int


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev


def is_kernel(name: str) -> bool:
    return bool(KERNEL.search(name))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def device_planes(pd) -> List:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and "TPU" in p.name.upper()]


def _line(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def host_spans(pd) -> List[Tuple[float, float, str]]:
    """(start, end, name) of the host events on the thread that ran the
    harness: the host line that holds its `bench.window` span."""
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            spans = [(a, b, n) for n, a, b, _ in _events(ln)]
            if any(n == WINDOW_SPAN for _, _, n in spans):
                return spans
    return []


def _plane_execs(mod_line, ops, w0: float, w1: float) -> List[Exec]:
    """One chip's step executions inside the window, each with the time of
    the kernel operations that ran inside it."""
    kern = sorted((a, b) for a, b, n, _ in ops if is_kernel(n))
    starts = [a for a, _ in kern]
    execs = []
    for n, a, b, _ in _events(mod_line):
        if b <= w0 or a >= w1:
            continue
        kind = next((k for k, rx in STEP_PROGRAMS.items()
                     if rx.search(n)), "other")
        i = bisect.bisect_left(starts, a)
        ks = 0.0
        while i < len(kern) and kern[i][0] < b:
            ks += min(kern[i][1], b) - kern[i][0]
            i += 1
        execs.append(Exec(kind, n, a, b, ks))
    return execs


def _twin(execs: List[Exec], e: Exec) -> Optional[Exec]:
    """The first chip's execution that `e`, on another chip, is part of:
    same program, overlapping, the nearest start."""
    cands = [x for x in execs if x.name == e.name
             and x.start < e.end and e.start < x.end]
    return min(cands, key=lambda x: abs(x.start - e.start), default=None)


def reduce(pd, top_n: int = 10) -> Reduced:
    spans = host_spans(pd)
    win = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0]
    planes = device_planes(pd)
    if not planes:
        raise ValueError("no device plane in the trace")
    busy, execs, op_time = [], [], {}
    kernel_ops = 0
    per_chip_busy = []
    for plane in planes:
        ops_line, mod_line = _line(plane, "XLA Ops"), _line(plane, "XLA Modules")
        ops = [] if ops_line is None else [
            (a, b, n, ev) for n, a, b, ev in _events(ops_line)
            if b > w0 and a < w1]
        chip = _union([(max(a, w0), min(b, w1)) for a, b, _, _ in ops])
        per_chip_busy.append(sum(b - a for a, b in chip))
        busy.extend(chip)
        for a, b, n, _ in ops:
            op_time[n] = op_time.get(n, 0.0) + (b - a)
        if mod_line is None:
            continue
        kernel_ops += sum(1 for _, _, n, _ in ops if is_kernel(n))
        if plane is planes[0]:
            execs = _plane_execs(mod_line, ops, w0, w1)
            continue
        for e in _plane_execs(mod_line, ops, w0, w1):
            twin = _twin(execs, e)
            if twin is not None:
                twin.kernel_s += e.kernel_s
            elif e.kind in STEP_PROGRAMS:
                # its kernel time would go uncounted and the roofline read
                # high: refuse the trace rather than read it
                raise ValueError(
                    f"{plane.name}: {e.kind} execution at {e.start:.6f} s "
                    f"overlaps no {e.name} on {planes[0].name}")
    execs.sort(key=lambda e: e.start)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top_n]
    gaps = idle_gaps(_union(busy), w0, w1, spans, top_n)
    return Reduced(window_s=w1 - w0, window_end=w1,
                   busy_s=sum(per_chip_busy) / len(per_chip_busy),
                   execs=execs, top_ops=top, idle_gaps=gaps,
                   kernel_ops=kernel_ops)


def idle_gaps(busy, w0, w1, spans, top_n) -> List[Tuple[str, float]]:
    """The longest stretches with no device operation, each named after
    the innermost harness or JAX host span open at its middle."""
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for a, b in gaps[:top_n]:
        mid = (a + b) / 2
        inner = [s for s in spans if s[0] <= mid <= s[1]
                 and s[2] != WINDOW_SPAN]
        label = min(inner, key=lambda s: s[1] - s[0])[2] if inner \
            else "no host span"
        out.append((label, b - a))
    return out


def match_calls(execs: List[Exec], calls: List[Dict]) -> List[Tuple[Exec, Optional[Dict]]]:
    """Pair each step-program execution with the harness's log of the
    call that ran it (the n-th decode with the n-th decode call, and so
    for prefill); executions without a logged call get None."""
    by_kind: Dict[str, List[Dict]] = {}
    for c in calls:
        by_kind.setdefault(c["kind"], []).append(c)
    seen: Dict[str, int] = {}
    out = []
    for e in execs:
        if e.kind not in STEP_PROGRAMS:
            continue
        i = seen.get(e.kind, 0)
        seen[e.kind] = i + 1
        lst = by_kind.get(e.kind, [])
        out.append((e, lst[i] if i < len(lst) else None))
    return out


def periods(execs: List[Exec], kind: str, end: float) -> float:
    """Wall seconds of `kind` steps: from each execution's start to the
    start of the next step-program execution (the host work and small
    programs between them included), the last one to `end`."""
    main = [e for e in execs if e.kind in STEP_PROGRAMS]
    total = 0.0
    for e, nxt in zip(main, main[1:] + [None]):
        if e.kind == kind:
            total += (nxt.start if nxt is not None else end) - e.start
    return total
