"""Loop/fusion-aware cost analysis over compiled HLO text.

XLA's own `compiled.cost_analysis()` counts a `while` body **once** (scan
bodies are the bulk of a transformer step, so it undercounts FLOPs by the
layer count) and reports fusion internals unevenly across backends.  This
analyzer walks the HLO call graph instead:

  * `while` bodies are multiplied by their trip count — taken from XLA's
    `known_trip_count` backend_config when present, else derived from the
    canonical `(iv = const; iv < K; iv += step)` cond/body pattern;
  * `fusion` / `call` / `map` / `reduce` sub-computations are charged once
    at each call site;
  * FLOPs count dot/convolution contractions only (2 * out_elems * K), so
    induction-variable arithmetic never pollutes the figure;
  * fused elementwise cost is tracked SEPARATELY (`elementwise_flops`):
    every arithmetic/transcendental elementwise instruction — including
    those inside fusion bodies, which XLA's cost model reports unevenly —
    charges `result_elems x op_weight` (1 for add/mul-class ops, 4 for
    divides/roots, 8 for transcendentals), times the enclosing trip
    counts.  Memory-bound cells (decode attention softmax, dequant
    select-accumulate chains) are VPU-heavy, so roofline fractions need
    this term once the MXU share stops dominating;
  * HBM bytes are a result-bytes proxy per non-trivial instruction;
  * collective bytes are keyed per kind (`coll_all-reduce`, ...).

`analyze_hlo(text)` -> {"flops", "elementwise_flops", "hbm_bytes",
"collective_bytes", "coll_*"}.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# ops whose result bytes are pure bookkeeping, not HBM traffic
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "while", "conditional", "call",
             "partition-id", "replica-id"}

# Elementwise op weights (VPU ops per result element) for the fused
# elementwise cost.  Coarse three-tier model: cheap ALU ops cost 1,
# divides/roots 4, transcendentals 8 — the tiers matter for roofline
# fractions, the exact constants do not.  Data movement (copy, convert,
# broadcast, reshape, slice, ...) is excluded: it is HBM traffic, already
# covered by the result-bytes proxy, not arithmetic.
_ELEMWISE_COST = {}
for _op in ("add", "subtract", "multiply", "negate", "abs", "maximum",
            "minimum", "select", "compare", "and", "or", "xor", "not",
            "clamp", "floor", "ceil", "round-nearest-afz",
            "round-nearest-even", "sign", "shift-left",
            "shift-right-logical", "shift-right-arithmetic"):
    _ELEMWISE_COST[_op] = 1.0
for _op in ("divide", "remainder", "sqrt", "rsqrt", "cbrt"):
    _ELEMWISE_COST[_op] = 4.0
for _op in ("exponential", "exponential-minus-one", "log", "log-plus-one",
            "tanh", "logistic", "sine", "cosine", "tan", "atan2", "power",
            "erf"):
    _ELEMWISE_COST[_op] = 8.0

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
# dims may be dynamic ("<=8"): the bound is the right byte proxy
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,<=]*)\]")
_OP_RE = re.compile(r"^(?:\([^)]*\)|[a-z][a-z0-9]*\[[0-9,<=]*\](?:\{[^}]*\})?)"
                    r"\s+([a-z][a-z0-9\-]*)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*"?n"?[^0-9]*(\d+)')
_CALLEE_RE = re.compile(
    r"(?:condition|body|to_apply|calls|true_computation|false_computation)="
    r"%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((-?\d+)\)")
_GTE_IDX_RE = re.compile(r"index=(\d+)")


class _Instr:
    __slots__ = ("name", "op", "line", "is_root")

    def __init__(self, name: str, op: str, line: str, is_root: bool):
        self.name = name
        self.op = op
        self.line = line
        self.is_root = is_root


def _shape_elems(dims: str) -> int:
    """Element count of one bracketed dim list.  Dynamic dims print as
    `<=N` — the bound is the right proxy for byte accounting.  Malformed
    fragments count as 0 elements rather than raising mid-scan."""
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if not d:
            continue
        if d.startswith("<="):
            d = d[2:]
        if not d.isdigit():
            return 0
    # second pass so a malformed dim voids the whole product
    for d in dims.split(","):
        d = d.strip().lstrip("<=")
        if d:
            n *= int(d)
    return n


def _result_elems(line: str) -> int:
    """Element count of the result type (first shape token after '=')."""
    rhs = line.split("=", 1)[1].lstrip() if "=" in line else line
    m = _SHAPE_RE.search(rhs)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return 0
    return _shape_elems(m.group(2))


def _tuple_region(rhs: str) -> str:
    """The balanced leading tuple-type region of an instruction rhs —
    nested tuples `((f32[2], s32[]), f32[4])` keep every element (the old
    split-at-first-')' dropped everything after the inner close)."""
    depth = 0
    for i, ch in enumerate(rhs):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rhs[:i + 1]
    return rhs


def _result_bytes(line: str) -> int:
    """Bytes of the result type (first type token after '='); a tuple type
    sums its parts (nested tuples included).  `token[]` / `opaque[]` /
    unknown dtypes contribute 0 — bookkeeping types, not HBM traffic."""
    rhs = line.split("=", 1)[1].lstrip() if "=" in line else line
    if rhs.startswith("("):
        region = _tuple_region(rhs)
    else:
        region = rhs
    total = 0
    for dt, dims in _SHAPE_RE.findall(region):
        if dt not in _DTYPE_BYTES:
            continue
        total += _shape_elems(dims) * _DTYPE_BYTES[dt]
        if not rhs.startswith("("):
            break
    return total


def _parse_computations(text: str) -> Dict[str, List[_Instr]]:
    comps: Dict[str, List[_Instr]] = {}
    entry: Optional[str] = None
    current: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if current is None:
            m = _COMP_HEAD_RE.match(line.strip())
            if m:
                current = m.group(1)
                comps[current] = []
                if line.strip().startswith("ENTRY"):
                    entry = current
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        opm = _OP_RE.match(rhs)
        op = opm.group(1) if opm else ""
        comps[current].append(
            _Instr(name, op, line, line.lstrip().startswith("ROOT")))
    comps["__entry__"] = comps.get(entry, [])
    return comps


def _shapes_by_name(comps: Dict[str, List[_Instr]]) -> Dict[str, str]:
    """Instruction name -> dims of its (array) result type.  HLO names are
    unique module-wide, so one map resolves operands in any computation."""
    out: Dict[str, str] = {}
    for comp in comps.values():
        for ins in comp:
            rhs = ins.line.split("=", 1)[1].lstrip()
            m = _SHAPE_RE.match(rhs)
            if m:
                out[ins.name] = m.group(2)
    return out


def _operand_dims(rhs: str, op: str, shapes: Dict[str, str]) -> List[str]:
    """Dims of each operand of `op(...)`, resolved by instruction name.
    Newer XLA prints operands as bare names (`dot(%a, %b)`); older prints
    put the type inline (`dot(f32[8,64]{1,0} %a, ...)`), read as is."""
    start = rhs.find(op + "(")
    if start < 0:
        return []
    region = _tuple_region(rhs[start + len(op):])[1:-1]
    dims = []
    for arg in region.split(", "):
        inline = _SHAPE_RE.search(arg)
        name = arg.split()[-1].lstrip("%") if arg.split() else ""
        dims.append(inline.group(2) if inline else shapes.get(name, ""))
    return dims


def _dot_flops(line: str, shapes: Dict[str, str]) -> float:
    """2 * out_elems * contracted_size for dot/convolution lines."""
    rhs = line.split("=", 1)[1]
    res = _SHAPE_RE.search(rhs)
    if not res:
        return 0.0
    out_elems = _shape_elems(res.group(2))
    conv = "convolution" in rhs
    operands = _operand_dims(rhs, "convolution" if conv else "dot", shapes)
    if conv:
        # rhs operand (the kernel) fully contracts except its output-feature
        # dim; a robust proxy: 2 * out * (kernel_elems / out_features).
        if len(operands) >= 2 and operands[1]:
            out_feat = max(int(d) for d in res.group(2).split(",") if d) \
                if res.group(2) else 1
            k_elems = _shape_elems(operands[1])
            return 2.0 * out_elems * max(k_elems // max(out_feat, 1), 1)
        return 2.0 * out_elems
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
    if not m:
        return 2.0 * out_elems
    lhs_dims = [int(d) for d in operands[0].split(",") if d] \
        if operands else []
    contracted = 1
    for d in (int(x) for x in m.group(1).split(",") if x):
        if d < len(lhs_dims):
            contracted *= lhs_dims[d]
    return 2.0 * out_elems * contracted


def _find_instr(comp: List[_Instr], name: str) -> Optional[_Instr]:
    for ins in comp:
        if ins.name == name:
            return ins
    return None


def _derive_trip_count(comps, parent: List[_Instr], while_line: str,
                       cond_name: str) -> int:
    """Fallback when known_trip_count is absent: match the canonical
    `(iv = c0; iv < K; iv += s)` pattern across cond / init tuple."""
    cond = comps.get(cond_name)
    if not cond:
        return 1
    root = next((i for i in cond if i.is_root), None)
    if root is None or root.op != "compare" or "direction=LT" not in root.line:
        return 1
    # operands of the compare: gte(index=i) and a constant
    ops = re.findall(r"%([\w.\-]+)[,)]", root.line.split("compare(", 1)[1])
    iv_index = bound = None
    for name in ops:
        ins = _find_instr(cond, name)
        if ins is None:
            continue
        if ins.op == "get-tuple-element":
            m = _GTE_IDX_RE.search(ins.line)
            iv_index = int(m.group(1)) if m else None
        elif ins.op == "constant":
            m = _CONST_RE.search(ins.line)
            bound = int(m.group(1)) if m else None
    if iv_index is None or bound is None:
        return 1
    # init: while(%tuple) -> tuple element iv_index in the parent computation
    m = re.search(r"while\([^%]*%([\w.\-]+)\)", while_line)
    start = 0
    if m:
        tup = _find_instr(parent, m.group(1))
        if tup is not None and tup.op == "tuple":
            elems = re.findall(r"%([\w.\-]+)[,)]",
                               tup.line.split("tuple(", 1)[1])
            if iv_index < len(elems):
                src = _find_instr(parent, elems[iv_index])
                # chase one copy
                if src is not None and src.op == "copy":
                    m2 = re.search(r"copy\([^%]*%([\w.\-]+)\)", src.line)
                    src = _find_instr(parent, m2.group(1)) if m2 else src
                if src is not None and src.op == "constant":
                    mc = _CONST_RE.search(src.line)
                    if mc:
                        start = int(mc.group(1))
    return max(bound - start, 0)


def collective_instructions(text: str):
    """Every collective instruction in the module (all computations, loop
    bodies included, each listed ONCE — no trip-count multiplication) as
    ``[(kind, result_bytes), ...]``.

    `analyze_hlo` aggregates collective bytes; this keeps them
    per-instruction so tests can assert *size classes* — e.g. the
    multi-device serving test asserts no single all-gather result is
    weight-sized (decode must move activations between shards, never the
    sharded CLAQ plan payload)."""
    out = []
    for comp, instrs in _parse_computations(text).items():
        if comp == "__entry__":
            continue
        for ins in instrs:
            if ins.op in _COLLECTIVES:
                out.append((ins.op, _result_bytes(ins.line)))
    return out


def gather_instructions(text: str):
    """Every gather / dynamic-slice instruction in the module (all
    computations, fusion and loop bodies included, each listed ONCE) as
    ``[(kind, result_bytes), ...]`` — the indexed-load counterpart of
    `collective_instructions`.

    Tests use this to pin down the decode hot path's indexing cost: the
    fused CLAQ matmul must add ZERO gather instructions over a dense
    model's decode step when its plans are x-aligned (the plan folded the
    stripe permutation away entirely, DESIGN.md §9), and for permuted
    (mixed-precision) plans every added gather must be a VMEM-tile-sized
    in-kernel take — never an activation-sized XLA gather.  `dynamic-slice`
    is reported too (cache reads, in-kernel block fetches) so callers can
    distinguish block fetches from true gathers; note ``all-gather`` is a
    collective, not counted here."""
    out = []
    for comp, instrs in _parse_computations(text).items():
        if comp == "__entry__":
            continue
        for ins in instrs:
            if ins.op in ("gather", "dynamic-slice"):
                out.append((ins.op, _result_bytes(ins.line)))
    return out


def copy_instructions(text: str):
    """Every `copy` / `copy-start` instruction in the module (all
    computations, each listed ONCE) as ``[(op, result_bytes), ...]`` —
    the contract checker's raw material for the whole-cache-copy audit
    (HLO-CP1): a copy whose result is cache-sized inside the decode step
    means the cache round-trips HBM instead of being updated in place."""
    out = []
    for comp, instrs in _parse_computations(text).items():
        if comp == "__entry__":
            continue
        for ins in instrs:
            if ins.op in ("copy", "copy-start"):
                out.append((ins.op, _result_bytes(ins.line)))
    return out


_CONVERT_OPERAND_RE = re.compile(r"convert\(\s*([a-z][a-z0-9]*)\[([0-9,<=]*)\]")


def convert_instructions(text: str):
    """Every `convert` instruction as ``[(src_dtype, dst_dtype, elems),
    ...]`` (each listed once, fusion bodies included) — dtype-discipline
    rules key off widening converts (s8 -> f32 of a pool-sized array means
    an int8 page path silently upcasted, HLO-DT1)."""
    out = []
    for comp, instrs in _parse_computations(text).items():
        if comp == "__entry__":
            continue
        for ins in instrs:
            if ins.op != "convert":
                continue
            rhs = ins.line.split("=", 1)[1].lstrip()
            mdst = _SHAPE_RE.search(rhs)
            msrc = _CONVERT_OPERAND_RE.search(rhs)
            if not mdst or not msrc:
                continue
            out.append((msrc.group(1), mdst.group(1),
                        _shape_elems(mdst.group(2))))
    return out


_HOST_TRANSFER_OPS = ("infeed", "outfeed", "send", "send-done", "recv",
                      "recv-done")
_HOST_CUSTOM_CALL_RE = re.compile(
    r'custom_call_target="[^"]*(?:Host|host_compute|PinToHost|Callback'
    r'|callback)[^"]*"')


def host_transfer_instructions(text: str):
    """Every instruction that moves data between device and host inside
    the module — infeed/outfeed/send/recv plus host custom-calls — as
    ``[(op, result_bytes), ...]``.  The compiled decode/verify step loop
    must contain NONE (HLO-HT1): a host transfer per step serializes the
    loop on PCIe latency."""
    out = []
    for comp, instrs in _parse_computations(text).items():
        if comp == "__entry__":
            continue
        for ins in instrs:
            if ins.op in _HOST_TRANSFER_OPS:
                out.append((ins.op, _result_bytes(ins.line)))
            elif (ins.op == "custom-call"
                  and _HOST_CUSTOM_CALL_RE.search(ins.line)):
                out.append(("custom-call", _result_bytes(ins.line)))
    return out


_ALIAS_PAIR_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([0-9,\s]*)\}")


def donation_aliases(text: str):
    """Input/output aliases from the module header's `input_output_alias`
    attribute as ``[(param_index, output_index_tuple), ...]`` — empty when
    nothing is donated.  The attribute's value nests braces
    (``{ {0}: (1, {}, must-alias) }``), so the region is taken by balanced
    scan, not regex.  The donation audit (HLO-DN1) checks that cache
    buffers are donated into the step jits where the platform supports
    buffer donation (otherwise every step allocates a second cache)."""
    head = text.split("\n\n", 1)[0]
    start = head.find("input_output_alias=")
    if start < 0:
        return []
    region = head[head.index("{", start):]
    depth = 0
    for i, ch in enumerate(region):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                region = region[:i + 1]
                break
    out = []
    for mo in _ALIAS_PAIR_RE.finditer(region):
        out_idx = tuple(int(v) for v in mo.group(1).split(",") if v.strip())
        out.append((int(mo.group(2)), out_idx))
    return out


def analyze_hlo(text: str) -> Dict[str, float]:
    comps = _parse_computations(text)
    entry = comps.get("__entry__", [])
    shapes = _shapes_by_name(comps)

    def walk(comp: List[_Instr]) -> Dict[str, float]:
        acc: Dict[str, float] = {"flops": 0.0, "elementwise_flops": 0.0,
                                 "hbm_bytes": 0.0, "collective_bytes": 0.0}
        for ins in comp:
            mult = 1
            callees = _CALLEE_RE.findall(ins.line)
            if ins.op == "while":
                mtc = _TRIP_RE.search(ins.line)
                if mtc:
                    mult = int(mtc.group(1))
                else:
                    cond = next((c for c in callees if c in comps), None)
                    # condition= is listed first in HLO text
                    cm = re.search(r"condition=%?([\w.\-]+)", ins.line)
                    cond = cm.group(1) if cm else cond
                    mult = _derive_trip_count(comps, comp, ins.line, cond)
            if ins.op in ("dot", "convolution"):
                acc["flops"] += _dot_flops(ins.line, shapes)
            cost = _ELEMWISE_COST.get(ins.op)
            if cost is not None:
                acc["elementwise_flops"] += cost * _result_elems(ins.line)
            if ins.op not in _FREE_OPS:
                acc["hbm_bytes"] += _result_bytes(ins.line)
            if ins.op in _COLLECTIVES:
                b = _result_bytes(ins.line)
                acc[f"coll_{ins.op}"] = acc.get(f"coll_{ins.op}", 0.0) \
                    + b * mult
                acc["collective_bytes"] += b * mult
            for callee in callees:
                sub = comps.get(callee)
                if sub is None:
                    continue
                inner = walk(sub)
                for k, v in inner.items():
                    # fusion internals execute their flops/collectives but
                    # materialize only the fusion root — the root's bytes
                    # were already charged at this call site
                    if ins.op == "fusion" and k == "hbm_bytes":
                        continue
                    acc[k] = acc.get(k, 0.0) + v * mult
        return acc

    return walk(entry)
