"""Builders that turn a live ``ServingEngine`` into the context dict the
compiled-artifact and trace rules consume, plus ``verify_engine`` — the
one-call gate behind ``ServingEngine(verify_contracts=True)``.

The expensive piece is the HLO: ``engine_context`` AOT-lowers the
engine's decode jit under kernel mode (compiled on a TPU, interpreted
elsewhere) and, for the gather-parity rule, builds a *dense twin* — the
same engine over the dequantized weights — whose compiled decode is the
gather baseline.  Everything else (plan stats,
shard thresholds, pool-slice limits, compile budgets) is cheap host-side
tree walking.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantized import QuantizedTensor
from repro.kernels.plan import PreparedQuantizedTensor
from repro.models import modules as nn

from .core import ContractViolation, Report, Rule, run_rules
from .hlo_rules import HLO_RULES
from .trace_rules import TRACE_RULES

_QUANT_TYPES = (QuantizedTensor, PreparedQuantizedTensor)

# Cache-pool leaf names (mirrors serve.engine._POOL_SRC — duplicated here
# to keep analysis importable without pulling in the engine module).
_POOL_LEAVES = ("kp", "vp", "cp", "pp")


def _is_quant(leaf: Any) -> bool:
    return isinstance(leaf, _QUANT_TYPES)


def _quant_leaves(params) -> List[Any]:
    out: List[Any] = []
    jax.tree_util.tree_map(
        lambda l: out.append(l) if _is_quant(l) else None,
        params, is_leaf=_is_quant)
    return out


def plan_stats(params, n_slots: int = 8) -> Dict[str, Any]:
    """Plan-tree stats for the gather-parity rule: how many permuted
    (x-indexed) groups exist across all prepared leaves, and the worst
    in-kernel take size.  ``bm`` is the decode-row tile the take loads
    (>= 8 even for tiny slot counts: the kernel pads rows to its block)."""
    n_permuted = 0
    max_bk = 0
    has_plans = False
    for leaf in _quant_leaves(params):
        if not isinstance(leaf, PreparedQuantizedTensor):
            continue
        has_plans = True
        permuted = [g for g in leaf.groups if g.x_start is None]
        n_permuted += len(permuted)
        if permuted:
            max_bk = max(max_bk, max(g.bk for g in permuted))
    return {"has_plans": has_plans, "n_permuted_groups": n_permuted,
            "max_bk": max_bk, "bm": max(8, n_slots), "itemsize": 4}


def weight_shard_threshold(params, model_parts: int) -> Optional[int]:
    """Largest sharded plan-plane payload in bytes — the all-gather rule's
    threshold.  None when no quantized unit actually shards (replicated
    plans move at load, not per step, so the rule would be vacuous)."""
    if model_parts <= 1:
        return None
    best: Optional[int] = None
    for leaf in _quant_leaves(params):
        if (isinstance(leaf, PreparedQuantizedTensor)
                and leaf.shards_whole_tiles(model_parts)):
            for g in leaf.groups:
                for p in g.planes:
                    b = int(np.prod(p.shape)) * 4
                    best = b if best is None else max(best, b)
    return best


def _dequant_leaf(leaf):
    """Dequantize one (possibly layer-stacked) quantized leaf into the
    dense kernel slot layout (..., in, out)."""
    if isinstance(leaf, PreparedQuantizedTensor):
        stack = leaf.gather_idx.ndim - 1
    elif isinstance(leaf, QuantizedTensor):
        stack = leaf.col_perm.ndim - 1
    else:
        return leaf
    fn = lambda l: l.dequantize()          # noqa: E731 - vmap target
    for _ in range(stack):
        fn = jax.vmap(fn)
    return jnp.swapaxes(fn(leaf), -1, -2)


def dense_twin_params(params):
    """The engine's params with every quantized leaf replaced by its
    dequantized dense form — the baseline the gather-parity rule lowers."""
    return jax.tree_util.tree_map(_dequant_leaf, params, is_leaf=_is_quant)


def _batch_buckets(n_slots: int) -> int:
    """Distinct bucketed admission batch sizes: next-power-of-2 capped at
    n_slots (mirrors the engine's ``Bb`` computation in ``_admit``)."""
    return len({min(1 << (b - 1).bit_length(), n_slots)
                for b in range(1, n_slots + 1)})


def compile_budgets(engine) -> Dict[str, int]:
    """Per-jit upper bounds on distinct abstract signatures (PR 2's
    contract).  Prefill budgets exist only under bucketing — with it off,
    every distinct prompt length legitimately compiles."""
    out: Dict[str, int] = {}
    if engine.bucketing.enabled:
        shapes = engine.bucketing.max_traces() * _batch_buckets(
            engine.n_slots)
        out["prefill"] = shapes
        if engine.spec is not None:
            out["draft_prefill"] = shapes
    if getattr(engine, "chunked", None) is not None:
        # chunk jits have a fixed token axis (chunk_tokens); only the
        # batch bucket varies, and the chunk position is a traced scalar
        out["chunk_prefill"] = _batch_buckets(engine.n_slots)
        if engine.spec is not None:
            out["draft_chunk_prefill"] = _batch_buckets(engine.n_slots)
    # decode: the batched step shape plus the batch-1 resume replay
    out["decode"] = 2
    if engine.spec is not None:
        out["draft_decode"] = 2
        # one verify span shape per distinct γ the engine may run — the
        # degradation ladder's spec_half rung adds ceil(γ/2)
        out["verify"] = max(1, len(getattr(engine, "verify_gammas",
                                           {engine.spec.gamma})))
    return out


def trace_counts(engine) -> Dict[str, int]:
    out = {"prefill": engine.prefill_traces,
           "decode": engine.decode_traces}
    if engine.spec is not None:
        out.update(draft_prefill=engine.draft_prefill_traces,
                   draft_decode=engine.draft_decode_traces,
                   verify=engine.verify_traces)
    if getattr(engine, "chunked", None) is not None:
        out["chunk_prefill"] = engine.chunk_prefill_traces
        if engine.spec is not None:
            out["draft_chunk_prefill"] = engine.draft_chunk_prefill_traces
    return out


def _pool_slice_elems(engine) -> Optional[int]:
    """Element count of one layer's gathered int8 pool view — the widest
    s8->f32 convert legal on the decode path.  None when the engine holds
    no int8 pages (nothing to upcast)."""
    if getattr(engine, "kv_dtype", None) != "int8":
        return None
    best: Optional[int] = None

    def visit(path, leaf):
        nonlocal best
        name = getattr(path[-1], "name", None)
        if name in _POOL_LEAVES and leaf.dtype == jnp.int8:
            feat = int(np.prod(leaf.shape[3:])) if leaf.ndim > 3 else 1
            n = engine.n_slots * engine.max_len * feat
            best = n if best is None else max(best, n)
        return leaf

    jax.tree_util.tree_map_with_path(visit, engine.cache)
    return best


def _cache_leaf_bytes(engine) -> int:
    best = 0
    for leaf in jax.tree_util.tree_leaves(engine.cache):
        best = max(best, int(leaf.size) * leaf.dtype.itemsize)
    return best


def lowered_decode_text(engine) -> str:
    """Compiled HLO of the engine's decode step under kernel mode (the
    deployment path the gather/dtype contracts guard)."""
    with nn.quant_mode("kernel"):
        return engine.lower_decode().compile().as_text()


def _mesh_model_parts(engine) -> int:
    if engine.mesh is None:
        return 1
    return int(dict(engine.mesh.shape).get("model", 1))


def engine_context(engine, dense_engine=None, *,
                   collective_budget_bytes: Optional[int] = None,
                   donation_expected: bool = False) -> Dict[str, Any]:
    """Build the full rule context from a live engine (and, optionally, a
    dense twin engine supplying the gather baseline)."""
    ctx: Dict[str, Any] = {
        "hlo": {"decode": lowered_decode_text(engine)},
        "plan": plan_stats(engine.params, n_slots=engine.n_slots),
        "cache_leaf_bytes": _cache_leaf_bytes(engine),
        "donation_expected": donation_expected,
        "sentinel": getattr(engine, "sentinel", None),
        "compile_budget": compile_budgets(engine),
        "trace_counts": trace_counts(engine),
    }
    thresh = weight_shard_threshold(engine.params, _mesh_model_parts(engine))
    if thresh is not None:
        ctx["weight_shard_bytes"] = thresh
    if collective_budget_bytes is not None:
        ctx["collective_budget_bytes"] = collective_budget_bytes
    pool = _pool_slice_elems(engine)
    if pool is not None:
        ctx["pool_slice_elems"] = pool
    if dense_engine is not None:
        ctx["dense_hlo"] = {
            "decode": lowered_decode_text(dense_engine)}
    return ctx


def dense_twin_engine(engine):
    """A twin engine over the dequantized weights, matched on everything
    that shapes the decode HLO (slots, cache layout, mesh)."""
    from repro.serve.engine import ServingEngine
    kw: Dict[str, Any] = {}
    if engine._paged:
        kw = dict(kv_layout="paged", page_size=engine.page_size,
                  kv_pages=engine.n_pages, kv_dtype=engine.kv_dtype)
    return ServingEngine(
        dense_twin_params(engine.params), engine.cfg,
        n_slots=engine.n_slots, max_len=engine.max_len,
        dtype=engine._cache_dtype, prepare=False, mesh=engine.mesh,
        guards=engine.guards, **kw)


def verify_engine(engine, dense_engine=None, *,
                  rules: Optional[List[Rule]] = None,
                  with_baseline: bool = True,
                  raise_on_error: bool = True,
                  subject: str = "engine") -> Report:
    """Run the compiled-artifact + trace rules against a live engine;
    raises ``ContractViolation`` on ERROR findings (the
    ``verify_contracts=True`` init hook).  ``with_baseline`` builds the
    dense twin for the gather-parity rule when the caller did not pass
    ``dense_engine`` and the params hold plans."""
    if (dense_engine is None and with_baseline
            and plan_stats(engine.params)["has_plans"]):
        dense_engine = dense_twin_engine(engine)
    ctx = engine_context(engine, dense_engine)
    report = run_rules(rules if rules is not None
                       else list(HLO_RULES) + list(TRACE_RULES),
                       ctx, subject=subject)
    if raise_on_error and not report.clean:
        raise ContractViolation(report)
    return report
