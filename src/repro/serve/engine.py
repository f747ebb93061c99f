"""Batched serving engine: slot-based continuous batching over a fixed-size
decode batch, with bucketed prefill admission into free slots.

Admission pads each prompt to its power-of-2 length bucket
(serve/bucketing.py), so N distinct prompt lengths cost at most
``ceil(log2(max_len / min_bucket)) + 1`` prefill traces instead of N at
any fixed admission batch size; batch sizes are bucketed the same way
(next power of 2, capped at n_slots), so the total trace count is
bounded by the product length-buckets x batch-buckets
(<= ``floor(log2(n_slots)) + 1`` of the latter).
The prefill reads logits at the true last-token position (``logits_at =
n - 1``, not ``-1``), and the per-request cache fragment enters the
batched cache through a masked insert: K/V positions ``n..bucket-1``
(the padded tail) are zeroed and the fill counter is set to the true
length ``n``, so decode appends at position ``n`` and the attention mask
never exposes a padding slot.  ``add_requests`` admits prompts sharing a
bucket in one batched prefill call (except moe, whose router couples
rows — it admits one per prefill), with the batch size itself bucketed
so a drifting free-slot count doesn't mint fresh compiles either.

Padding applies to the dense attention family, where causal masking
makes a padded suffix invisible to valid positions.  Recurrent families
(rwkv / hybrid) fold every token into their state, and moe's
capacity-bounded router sees padded tokens (see _PADDED_FAMILIES), so
those are admitted at exact lengths (bucket == n, grouping still batches
equal-length prompts).

Weights may be dense or CLAQ-quantized — QuantizedTensor leaves are
compiled into their ahead-of-time inference plans once at init, and the
model dispatches per leaf, so the same engine serves fp and 2/3/4-bit
models.  ``act_dtype="int8"`` additionally opts every quantized matmul
into per-token dynamic int8 activation quantization (weight-activation
quantized serving, DESIGN.md §9) — opt-in because it changes numerics
(bounded by scale/2 * ||W||_1 per output element), unlike every other
engine knob, which is bit-exact.

Multi-device serving: pass ``mesh=`` (e.g. ``launch.mesh.make_mesh((2,
4), ("data", "model"))``) and the engine device_puts the prepared params with
``dist.sharding`` rules — PreparedQuantizedTensor units split along N over
"model" with whole (bn, bk) tiles per shard, dense leaves by the generic
TP rule — shards the slot cache over "dp" (plus KV heads over "model"),
and runs the hoisted prefill/decode jits under ``dist.context.use_mesh``
so the layer-level sharding constraints activate.  Decode stays
weight-resident: each shard dequantizes only its own N slice, so the step
moves activations, never weights (asserted on compiled HLO in
tests/test_dist_serving.py via ``lower_decode()``).

Request lifecycle (DESIGN.md §10, serve/lifecycle.py): every request
carries an explicit state machine (QUEUED -> RUNNING -> {FINISHED,
TRUNCATED, ABANDONED, FAILED, PREEMPTED}; PREEMPTED -> QUEUED) with an
optional per-request deadline and priority.  ``submit()`` enqueues into
a bounded admission queue and raises typed ``AdmissionRejected``
backpressure when it is full; each ``step()`` first runs lifecycle
housekeeping (``pump()``): deadline-expired work is ABANDONED (queued
or running — partial tokens are kept), cache pressure is applied, and
free slots are filled from the queue (highest priority first, resumed
work ahead of fresh).

Preemption replaces silent truncation: when the effective slot-cache
limit drops below ``max_len`` (fault injection, ``set_cache_pressure``)
or strictly-higher-priority work is queued behind a full engine, the
lowest-priority/youngest victim is PREEMPTED — its slot is cleared by
the jitted masked rollback (``_rollback_tail``, the same leaf
classification as the bucketed masked insert) — and re-queued at the
front.  Resume re-prefills the ORIGINAL prompt through the normal
bucketed prefill, then replays the generated prefix through the decode
jit teacher-forced (bitwise the decode steps the uninterrupted run
executed — prefilling prompt+prefix would NOT be bitwise: the prefill
path uses online softmax, decode does not), so a resumed request's
remaining tokens are bit-identical to an uninterrupted run.  Truncation
survives only where resume is physically impossible (fill reached
``max_len`` itself) or as the opt-in ``on_pressure="truncate"`` policy.
moe cannot preempt (decode rows are router-coupled, so a batch-1 replay
is not bitwise) and falls back to truncation.

Numeric guards: ``guards=True`` folds one ``jnp.isfinite`` all-reduce
over the selected logits into the prefill/decode/verify jits; a
non-finite row quarantines ONLY the offending request (FAILED, with
diagnostics: phase, non-finite count, engine step) while the rest of the
batch proceeds — mid-speculative-window the slot is rolled back, then
quarantined.  ``faults=FaultInjector(...)`` (serve/faults.py) wires a
seeded deterministic fault plan: NaN/Inf injection rides a traced
operand added to the logits inside the jit (so guards see injected
faults exactly like genuine ones), pressure windows drive preemption,
and planned transient ``EngineFault`` raises happen BEFORE any state
mutation so a bounded-retry driver can simply call ``step()`` again.

Flow: add_requests() buckets, pads, and prefills; step() decodes every
active slot in one batched decode_step and emits one token per active
request.  Retirement (``max_new_tokens`` reached or EOS sampled) is
checked wherever a token is appended — including the prefill-sampled
first token, so a one-token budget or an immediate EOS retires the
request at admission without entering the decode loop.  Retired requests
move to ``finished`` (drain with ``take_finished()``).

Self-speculative decoding: pass ``draft_params=`` (the same checkpoint
quantized at a lower bit-width from the same calibration pass — see
``launch.quantize.claq_quantize_with_draft``) and ``spec=SpecConfig(γ)``,
and ``step()`` becomes a propose/verify/rollback window
(serve/speculative.py): γ+1 draft decode steps, ONE target span verify
(``models.api.decode_span``, bitwise γ+1 successive decodes), greedy
acceptance, and a batched per-slot rollback of both caches
(``_rollback_tail``: masked K/V tail zeroing + fill-counter rewind,
the same leaf classification as the bucketed masked insert).  Greedy
speculation is lossless — emitted tokens, retirement points, and the
rolled-back cache are bit-identical to vanilla decode (DESIGN.md §8).
Families that cannot roll back (recurrent state, router-coupled moe,
ring caches) are rejected at construction.

Paged KV cache (``kv_layout="paged"``, DESIGN.md §11): instead of one
contiguous (n_slots, max_len) K/V strip per slot, each layer owns a
global page pool ``(n_pages + 1, page_size, ...)`` (the last row is the
scratch page absorbing masked writes) and each slot a page-table row,
mirrored on the host and broadcast to the device before any consuming
jit (``_sync_tables``).  Pages are allocated on demand — at admission
for the prompt, per step for decode writes (``_ensure_capacity``) — and
freed at retirement/preemption; ``PoolExhausted`` is the typed
backpressure when the pool runs dry (queued work waits, running work
preempts or retires TRUNCATED with diagnostics).  Prefill stays
contiguous: fragments are scattered into pages afterwards
(``_paged_insert``), so the prefill jits are shared with the contiguous
layout.  Paged fp decode is bit-identical to contiguous decode (the
gathered view has the contiguous cache's exact shape, so XLA reduces
identically; fresh pages are zeroed so masked rows contribute exactly
0.0).  ``kv_dtype="int8"`` stores resident pages quantized per token row
(absmax/127 scales in a parallel pool) — bounded error (scale/2 per
element), ~4x the tokens per byte of fp32, and no preemption (an fp
replay cannot reproduce int8 history; pressure truncates, like moe).
Requests sharing a prompt prefix share physical pages (refcounted via
``PrefixRegistry``) and copy-on-write at the first write into a shared
page — prefill right-padding invariance makes the donor's page contents
bitwise what the sharer's own prefill would have produced.

``prefill_traces`` / ``decode_traces`` count actual XLA traces (a Python
side effect inside the jitted function runs once per trace); ``stats()``
reports them next to the bucketing policy's compile-cache accounting.
Speculation adds its own counters (``draft_prefill/draft_decode/verify
_traces``) — all bounded by constants independent of how many windows
run.  Lifecycle adds terminal-state, preemption/resume, and
admission-rejection counters.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np

from repro.analysis.trace_rules import TraceSentinel
from repro.dist import context as dctx
from repro.dist import sharding as shd
from repro.kernels import ops as kops
from repro.kernels.plan import or_slot_share, prepare_tree
from repro.models import api
from repro.models import modules as nn

from . import lifecycle as lc
from . import speculative
from .admission import AdmissionController, StepCostModel
from .bucketing import BucketingPolicy
from .chunked_prefill import ChunkedPrefillConfig, PrefillGroup
from .faults import FaultInjector, nonfinite_rows
from .lifecycle import (AdmissionQueue, AdmissionRejected, DeadlineExceeded,
                        EngineFault, IncompleteRun, RequestState, RetryPolicy,
                        TERMINAL_STATES)
from .paging import PageAllocator, PoolExhausted, PrefixRegistry
from .speculative import SpecConfig
from .telemetry import MetricsRegistry, Telemetry, registry_from_stats

Array = jax.Array

# Families whose caches are position-indexed and masked by a fill counter,
# making right-padding invisible to valid tokens.  moe is excluded even
# though its cache is attention-shaped: capacity-bounded routing sees the
# padded suffix (cap and the group-local cumsum depend on total token
# count), so padded prefill changes which valid tokens are capacity-dropped
# — bucketing moe needs a routing mask first.  The same router coupling
# makes moe prefill rows batch-DEPENDENT, so moe admissions are also never
# batched together (see add_requests); every other family's prefill rows
# are independent.
_PADDED_FAMILIES = ("dense",)

# Cache leaf names with a sequence axis to zero-mask past the true length
# (KVCache.k/v, MLACache.c_kv/k_pe) vs. fill counters to pin to it.
_SEQ_LEAVES = ("k", "v", "c_kv", "k_pe")
_LEN_LEAVES = ("length",)

# Paged-cache leaf names (models/layers.py PagedKVCache, mla.py
# PagedMLACache): pool rows / per-row int8 scales, each mapped to the
# contiguous-fragment leaf that feeds it at admission scatter time.  The
# "table" leaf is owned by the engine's host mirror (see _sync_tables) and
# the rollback/insert machinery never touches it — masking by the fill
# counter is what hides a rolled-back tail, exactly as in the contiguous
# layout.
_POOL_SRC = {"kp": "k", "vp": "v", "cp": "c_kv", "pp": "k_pe"}
_SCALE_SRC = {"k_scale": "k", "v_scale": "v",
              "c_scale": "c_kv", "p_scale": "k_pe"}


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    state: RequestState = RequestState.QUEUED
    priority: int = 0                   # higher preempts lower under load
    deadline: Optional[float] = None    # absolute clock() time, or None
    submitted_at: float = 0.0
    preemptions: int = 0                # times this request was preempted
    diagnostics: Optional[Dict[str, Any]] = None
    kv_int8: bool = False               # admitted under the kv_int8 rung:
                                        # prefill K/V carries int8-page
                                        # numerics, so no fp resume replay
                                        # can reproduce it (non-preemptible)

    @property
    def tokens_out(self) -> int:
        """Tokens actually emitted so far — on a retired request, the
        post-hoc denominator for TPOT (``(last - first) / (tokens_out -
        1)``) and the per-request throughput numerator."""
        return len(self.tokens)

    @property
    def done(self) -> bool:
        return self.state in TERMINAL_STATES

    @property
    def truncated(self) -> bool:
        return self.state is RequestState.TRUNCATED

    def transition(self, new_state: RequestState) -> None:
        lc.transition(self, new_state)


def _rollback_tail(cache, new_lens):
    """Rewind every slot's fill counter to ``new_lens`` ((B,) int32) and
    zero the K/V positions at or past it — the per-slot cache rollback a
    rejected speculation window needs, and (with a victim's length set to
    0) the masked slot CLEAR preemption needs.  Reuses the bucketed-insert
    leaf classification (`_SEQ_LEAVES` / `_LEN_LEAVES` by NamedTuple field
    name in the key path), so the rolled-back cache is bit-identical to one
    that never saw the rejected tail (the tail past a slot's fill is zero
    from init / the masked insert).  Jitted once in the engine — the
    target and the draft cache share the treedef, so one trace serves
    both; lengths arrive traced, so acceptance patterns never retrace."""
    new_lens = jnp.asarray(new_lens, jnp.int32)

    def rb(path, leaf):
        name = getattr(path[-1], "name", None)
        if name in _LEN_LEAVES:
            if leaf.ndim == 1:                   # (B,)
                return new_lens.astype(leaf.dtype)
            return jnp.broadcast_to(              # (layers, B)
                new_lens, leaf.shape).astype(leaf.dtype)
        if name in _SEQ_LEAVES:                  # (layers, B, S, ...)
            pos = jnp.arange(leaf.shape[2])
            keep = (pos[None, :] < new_lens[:, None]).reshape(
                (1,) + leaf.shape[1:3] + (1,) * (leaf.ndim - 3))
            return jnp.where(keep, leaf, jnp.zeros((), leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(rb, cache)


def _masked_group_insert(full, frag, slots: Sequence[int],
                         lens: Sequence[int], masked: bool):
    """Insert the first ``len(slots)`` rows of a prefill cache fragment
    into the batched cache at ``slots``, keeping only each row's first
    ``lens[r]`` sequence positions.  One whole-cache copy per admitted
    GROUP, not per request (the fragment batch may be larger — its tail
    rows are batch-bucketing dummies and are dropped).

    With `masked` (padded admission): fill counters advanced to the bucket
    size by the padded prefill are reset to the true lengths, and the
    padded K/V tail is zeroed — the batched cache ends up bit-identical to
    an unpadded prefill's.  Leaves are classified by their NamedTuple field
    name in the pytree key path.
    """
    B = len(slots)
    slots = jnp.asarray(slots, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)

    def ins(path, fl, fr):
        name = getattr(path[-1], "name", None)
        if masked and name in _LEN_LEAVES:
            if fl.ndim == 1:
                return fl.at[slots].set(lens.astype(fl.dtype))
            return fl.at[:, slots].set(
                jnp.broadcast_to(lens, (fl.shape[0], B)).astype(fl.dtype))
        if fl.ndim == 1:            # per-slot scalars, e.g. enc_len
            return fl.at[slots].set(fr[:B])
        v = fr[:, :B]               # (layers, B, seq?, ...) fragment rows
        if masked and name in _SEQ_LEAVES:
            pos = jnp.arange(v.shape[2])
            keep = (pos[None, :] < lens[:, None]).reshape(
                (1, B, -1) + (1,) * (v.ndim - 3))
            v = jnp.where(keep, v, jnp.zeros((), v.dtype))
        return fl.at[:, slots].set(v)

    return jax.tree_util.tree_map_with_path(ins, full, frag)


class ServingEngine:
    def __init__(self, params, cfg, n_slots: int = 8, max_len: int = 1024,
                 dtype=jnp.float32, prepare: bool = True,
                 min_bucket: int = 16, bucketing: bool = True,
                 mesh=None, plan_bn: Optional[int] = None,
                 plan_bk: Optional[int] = None,
                 draft_params=None, spec: Optional[SpecConfig] = None,
                 draft_plan_bn: Optional[int] = None,
                 draft_plan_bk: Optional[int] = None,
                 act_dtype: Optional[str] = None,
                 guards: bool = False,
                 faults: Optional[FaultInjector] = None,
                 queue_depth: Optional[int] = None,
                 on_pressure: str = "preempt",
                 clock=None,
                 kv_layout: str = "contiguous",
                 page_size: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 share_prefixes: bool = True,
                 verify_contracts: bool = False,
                 telemetry: Optional[Telemetry] = None,
                 chunked_prefill=None,
                 controller: Optional[AdmissionController] = None,
                 cost_model: Optional[StepCostModel] = None):
        if cfg.family == "encdec":
            raise NotImplementedError(
                "ServingEngine serves decoder-only families; encdec "
                "admission needs a frames input and a length-masked encoder")
        act_dtype = kops.normalize_act_dtype(act_dtype)
        if act_dtype is not None and not prepare:
            raise ValueError(
                "act_dtype='int8' needs ahead-of-time plans — drop "
                "prepare=False (the int8 path runs on prepared leaves only)")
        if on_pressure not in ("preempt", "truncate"):
            raise ValueError(
                f"on_pressure must be 'preempt' or 'truncate', got "
                f"{on_pressure!r}")
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'contiguous' or 'paged', got "
                f"{kv_layout!r}")
        self._paged = kv_layout == "paged"
        if not self._paged and (page_size is not None or kv_pages is not None
                                or kv_dtype is not None):
            raise ValueError(
                "page_size / kv_pages / kv_dtype configure the paged cache "
                "— pass kv_layout='paged'")
        self.page_size = None
        self.n_pages = None
        self.kv_dtype = None
        if self._paged:
            api.validate_paged_support(cfg)
            if kv_dtype in (None, "f32"):
                kv_dtype = None
            elif kv_dtype != "int8":
                raise ValueError(
                    f"unsupported kv_dtype {kv_dtype!r} (expected 'f32' or "
                    f"'int8')")
            self.page_size = int(page_size) if page_size is not None else 16
            if self.page_size < 1 or max_len % self.page_size:
                raise ValueError(
                    f"page_size={self.page_size} must be >= 1 and divide "
                    f"max_len={max_len}")
            # capacity-equivalent default: the pool holds exactly what the
            # contiguous layout reserved; pass kv_pages to over/undercommit
            self.n_pages = (int(kv_pages) if kv_pages is not None
                            else n_slots * (max_len // self.page_size))
            self.kv_dtype = kv_dtype
        if draft_plan_bn is not None or draft_plan_bk is not None:
            if spec is None:
                raise ValueError(
                    "draft_plan_bn/draft_plan_bk tune the speculative "
                    "draft's plan tiles — pass spec=SpecConfig(...) and "
                    "draft_params")
            if not prepare:
                raise ValueError(
                    "draft_plan_bn/draft_plan_bk shape the draft's "
                    "ahead-of-time plans — they do nothing with "
                    "prepare=False, so that combination is rejected")
        if spec is not None:
            speculative.validate_spec_support(cfg)
            if draft_params is None:
                raise ValueError(
                    "speculative decoding needs draft_params (the same "
                    "checkpoint quantized at SpecConfig.draft_bits — see "
                    "launch.quantize.claq_quantize_with_draft)")
        elif draft_params is not None:
            raise ValueError("draft_params given without spec=SpecConfig(...)")
        self._clock = clock if clock is not None else time.monotonic
        # Per-request event and phase-span recorder (serve/telemetry.py):
        # every hook call below is guarded by `is not None`, so a disabled
        # engine pays one predicate per lifecycle edge or span site and
        # NOTHING inside the jits — telemetry is host-side by construction
        # (AST/trace contract rules stay green with it attached).  The
        # recorder binds THIS engine's injectable clock, so StepClock runs
        # record deterministic timestamps.  Attached before the weights'
        # plans are built, so its compile counter sees every program the
        # engine obtains.
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.attach(n_slots=n_slots, clock=self._clock)
        # Compile every QuantizedTensor leaf into its ahead-of-time
        # inference plan ONCE; the prepared leaves then flow through the
        # jitted steps with zero per-trace layout work and one kernel
        # launch per distinct stripe bit-width.  plan_bn / plan_bk cap the
        # kernel block sizes (deployment tuning knob; smaller bn also
        # lowers the whole-tile granularity at which plans shard over
        # "model").
        prep_kw = {}
        if plan_bn is not None:
            prep_kw["bn"] = plan_bn
        if plan_bk is not None:
            prep_kw["bk"] = plan_bk
        self.params = prepare_tree(params, **prep_kw) if prepare else params
        if telemetry is not None:
            share = or_slot_share(self.params)
            if share is not None:
                telemetry.registry.gauge("kernel.or_slot_share").set(share)
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.mesh = mesh
        self.act_dtype = act_dtype
        # ---- lifecycle / robustness knobs --------------------------------
        self.guards = bool(guards)
        self.faults = faults
        self.on_pressure = on_pressure
        self._pressure_limit: Optional[int] = None
        # moe decode rows are router-coupled: a batch-1 resume replay is
        # not bitwise the batched decode, so moe cannot preempt and falls
        # back to truncation under pressure.  int8 resident pages cannot
        # preempt either: resume replays history through the fp decode jit,
        # which cannot reproduce the quantized K/V the uninterrupted run
        # accumulated — truncation under pressure, same as moe.
        self._preemptible = (cfg.family != "moe"
                             and self.kv_dtype != "int8")
        self.queue = AdmissionQueue(
            queue_depth if queue_depth is not None else max(2 * n_slots, 1))
        # Padding additionally requires linear (non-ring) caches: a
        # sliding-window ring keeps the LAST W keys, so a padded suffix
        # would evict valid ones and the masked insert's linear-position
        # zeroing would be meaningless in ring-slot space.
        self.bucketing = BucketingPolicy(
            min_bucket=min_bucket, max_len=max_len,
            enabled=(bucketing and cfg.family in _PADDED_FAMILIES
                     and cfg.attn_window is None))
        # ---- chunked prefill (serve/chunked_prefill.py) ------------------
        # The gate mirrors the bucketing/padding gate, but hard: chunking
        # rides the model layers' uniform-fill prefill branch, which only
        # the linear-cache padded families implement (moe's router couples
        # rows; ring caches have no linear chunk positions).
        self.chunked: Optional[ChunkedPrefillConfig] = None
        if chunked_prefill is not None:
            cpc = (chunked_prefill
                   if isinstance(chunked_prefill, ChunkedPrefillConfig)
                   else ChunkedPrefillConfig(chunk_tokens=int(chunked_prefill)))
            if (cfg.family not in _PADDED_FAMILIES
                    or cfg.attn_window is not None):
                raise NotImplementedError(
                    f"chunked prefill supports the padded linear-cache "
                    f"families {_PADDED_FAMILIES} (family={cfg.family!r}, "
                    f"attn_window={cfg.attn_window!r})")
            if max_len % cpc.chunk_tokens:
                raise ValueError(
                    f"chunk_tokens={cpc.chunk_tokens} must divide max_len="
                    f"{max_len}: the final chunk's dynamic_update_slice "
                    f"would clamp past the cache end and shift real rows")
            self.chunked = cpc
        self._prefill_groups: List[PrefillGroup] = []
        self.chunk_prefill_traces = 0
        self.draft_chunk_prefill_traces = 0
        self.chunks_processed = 0
        # ---- overload control plane (serve/admission.py) -----------------
        self.controller = controller
        self.cost_model = cost_model
        self.last_step_cost_ms: Optional[float] = None
        self._step_prefill_tokens = 0
        self._step_decode_calls = 0
        self._step_draft_calls = 0
        self._step_verify_tokens = 0
        # What the root ``engine.step`` span reports (recorder attached):
        # fresh uids admitted and their prompt tokens, and the step's
        # event (mode, tokens per uid, slots, step index, extras), emitted
        # once the span has closed so its ``dur`` is the span's.
        self._step_admitted: List[int] = []
        self._step_prompt_tokens = 0
        self._step_event = None
        # Degradation-ladder knobs the controller drives; nominal values
        # make an uncontrolled engine behave exactly as before.
        self._gamma_eff = spec.gamma if spec is not None else 0
        self._spec_enabled = spec is not None
        self._kv_int8_admission = False
        # Distinct speculative window sizes this engine may verify at —
        # the verify compile budget (controller.attach adds γ//2 when the
        # spec_half rung exists).
        self.verify_gammas = {spec.gamma} if spec is not None else set()
        self._cache_kw: Dict[str, Any] = {}
        if self._paged:
            self._cache_kw = dict(page_size=self.page_size,
                                  n_pages=self.n_pages,
                                  kv_dtype=self.kv_dtype)
        self.cache = api.make_cache(cfg, n_slots, max_len, dtype=dtype,
                                    **self._cache_kw)
        self._cache_shardings = None
        if mesh is not None:
            # Shard params by the serve TP rule (quantized units split
            # along N as whole tile groups, dense leaves by largest
            # model-divisible dim) and the slot cache over "dp" (+ KV
            # heads over "model").  The cache shardings are kept: eager
            # admission inserts produce mixed placements, so the cache is
            # re-pinned after every insert (see add_requests).
            self.params = jax.device_put(
                self.params, shd.tree_shardings(
                    self.params, shd.spec_for_param_serve, cfg, mesh))
            self._cache_shardings = shd.tree_shardings(
                self.cache, shd.spec_for_cache, cfg, mesh)
            self.cache = jax.device_put(self.cache, self._cache_shardings)
        # fp fragment dtype: prefill fragments are ALWAYS contiguous fp
        # caches (paged admission scatters them into pool pages afterwards),
        # so derive the dtype from the request, canonicalized exactly as
        # make_cache would — the first cache leaf may be int8/int32 paged.
        self._cache_dtype = jnp.zeros((), dtype).dtype
        # ---- paged-cache host state: allocator, tables, counters ---------
        self.allocator: Optional[PageAllocator] = None
        self.prefix_registry: Optional[PrefixRegistry] = None
        if self._paged:
            self.allocator = PageAllocator(self.n_pages, self.page_size)
            if share_prefixes:
                self.prefix_registry = PrefixRegistry(self.allocator)
            # host mirror of every slot's table row; the engine is the sole
            # mutator — _sync_tables broadcasts it into the device cache(s)
            # before any jit that consumes them
            self._tables = np.full((n_slots, max_len // self.page_size),
                                   self.allocator.scratch, np.int32)
            self._tables_dirty = False
            self._req_pages: Dict[int, List[int]] = {}
            self.cow_copies = 0
            self.prefix_hits = 0
            self.prefix_shared_tokens = 0
            self.page_evictions = 0
            self.peak_pages_in_use = 0
            self.peak_pages_per_request = 0
        self.free = list(range(n_slots))
        self.active: Dict[int, Request] = {}
        self.finished: Dict[int, Request] = {}
        self.last_token = np.zeros((n_slots,), np.int32)
        self._uid = 0

        # Trace counters: a Python side effect inside a jitted function
        # runs once per trace, so these count compiles, not calls.
        self.prefill_traces = 0
        self.decode_traces = 0
        self.draft_prefill_traces = 0
        self.draft_decode_traces = 0
        self.verify_traces = 0
        # Retrace sentinel: records the abstract signature of every jit
        # call so the trace rules (repro.analysis) can cross-check the
        # counters above against distinct-signature counts and the
        # bucketing compile budget.
        self.sentinel = TraceSentinel()

        # Emission counters (all modes): tokens actually appended to
        # requests, and the engine steps that produced them (decode steps
        # vanilla, verify windows speculative) — stats() derives
        # tokens-per-step from these.  Speculation adds drafted/accepted.
        self.emitted_tokens = 0
        self.engine_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0

        # Lifecycle counters: terminal states, preemption/resume traffic,
        # and typed admission rejections (backpressure observed).
        self.state_counts: collections.Counter = collections.Counter()
        self.preemptions = 0
        self.resumes = 0
        self.admission_rejections = 0

        # act_dtype scopes the per-token int8 activation quantization of
        # every quantized matmul inside the jitted steps; QuantMode.mode
        # stays whatever the ambient context set (the wrap runs at trace
        # time — QuantMode is read inside dense()).
        #
        # The jitted steps return (logits, cache, nonfinite) — the third
        # output is the per-row non-finite count when guards are on, None
        # (an empty pytree node, zero cost) otherwise.  ``iv`` is the
        # fault injector's additive per-slot vector, applied INSIDE the
        # jit so the guard sees injected faults exactly like genuine
        # ones; engines without an injector pass None.
        def _decode_fn(p, t, c, iv):
            self.decode_traces += 1
            with nn.activation_quant(self.act_dtype):
                logits, cache = api.decode_step(p, cfg, t, c)
            if iv is not None:
                logits = logits + iv[:, None]
            nf = nonfinite_rows(logits) if self.guards else None
            return logits, cache, nf

        # One stable jitted prefill keyed on the (batch, bucket) operand
        # shape: admissions at a previously seen shape hit the compile
        # cache.  True lengths arrive as a traced operand (logits_at), so
        # they never force a retrace.
        def _prefill_fn(p, t, c, lens):
            self.prefill_traces += 1
            with nn.activation_quant(self.act_dtype):
                logits, cache = api.prefill_step(p, cfg, {"tokens": t}, c,
                                                 logits_at=lens - 1)
            nf = nonfinite_rows(logits) if self.guards else None
            return logits, cache, nf

        self._decode = jax.jit(_decode_fn)
        self._prefill = jax.jit(_prefill_fn)

        # Chunked prefill: the operand shape is FIXED at (batch_bucket,
        # chunk_tokens) and the chunk position rides the fragment cache's
        # fill counter (the model's uniform-fill branch reads
        # cache.length[0] as the append offset), so every chunk of every
        # prompt at a given batch bucket shares ONE trace.  Logits are
        # read per row at the position of the row's true last token IF it
        # falls in this chunk (clipped otherwise; the host discards those
        # rows) — same traced-logits_at idea as the monolithic prefill.
        if self.chunked is not None:
            C = self.chunked.chunk_tokens

            def _chunk_prefill_fn(p, t, c, lens, start):
                self.chunk_prefill_traces += 1
                with nn.activation_quant(self.act_dtype):
                    logits, cache = api.prefill_step(
                        p, cfg, {"tokens": t}, c,
                        logits_at=jnp.clip(lens - 1 - start, 0, C - 1))
                nf = nonfinite_rows(logits) if self.guards else None
                return logits, cache, nf

            self._chunk_prefill = jax.jit(_chunk_prefill_fn)
            if spec is not None:
                def _draft_chunk_prefill_fn(p, t, c):
                    self.draft_chunk_prefill_traces += 1
                    # cache only, like the monolithic draft prefill
                    with nn.activation_quant(self.act_dtype):
                        _, cache = api.prefill_step(p, cfg, {"tokens": t}, c)
                    return cache

                self._draft_chunk_prefill = jax.jit(_draft_chunk_prefill_fn)
        # One rollback trace serves every cache with the engine's treedef
        # (target and draft alike) and doubles as the preemption slot
        # clear; per-slot lengths are traced, so acceptance/eviction
        # patterns never mint compiles.
        self._rollback = jax.jit(_rollback_tail)

        # -------- speculative decoding: draft model + verify + rollback --
        self.spec = spec
        self.draft_params = None
        self.draft_cache = None
        if spec is not None:
            # The draft rides the same machinery as the target: prepared
            # CLAQ plans, the same sharding rules, its own slot cache.
            # Its jits are SEPARATE (draft params have their own pytree
            # structure — fewer stripes at 2-bit — so they could never
            # share a compile cache entry with the target anyway) and
            # carry their own trace counters.
            # Draft-specific plan tiles: the 2-bit draft's groups span
            # skinnier K stripes and smaller matrices benefit from smaller
            # output tiles, so its bn/bk caps are tunable independently of
            # the target's (ROADMAP spec item b); they default to the
            # target's caps.
            dprep_kw = dict(prep_kw)
            if draft_plan_bn is not None:
                dprep_kw["bn"] = draft_plan_bn
            if draft_plan_bk is not None:
                dprep_kw["bk"] = draft_plan_bk
            self.draft_params = (prepare_tree(draft_params, **dprep_kw)
                                 if prepare else draft_params)
            self.draft_cache = api.make_cache(cfg, n_slots, max_len,
                                              dtype=dtype, **self._cache_kw)
            if mesh is not None:
                self.draft_params = jax.device_put(
                    self.draft_params, shd.tree_shardings(
                        self.draft_params, shd.spec_for_param_serve, cfg,
                        mesh))
                self.draft_cache = jax.device_put(self.draft_cache,
                                                  self._cache_shardings)

            def _draft_decode_fn(p, t, c):
                self.draft_decode_traces += 1
                with nn.activation_quant(self.act_dtype):
                    return api.decode_step(p, cfg, t, c)

            def _draft_prefill_fn(p, t, c):
                self.draft_prefill_traces += 1
                # cache only: the draft's prefill logits are never read,
                # and not returning them lets XLA drop the whole-bucket
                # unembedding matmul from the compiled draft prefill
                with nn.activation_quant(self.act_dtype):
                    _, cache = api.prefill_step(p, cfg, {"tokens": t}, c)
                return cache

            def _verify_fn(p, t, c, iv):
                self.verify_traces += 1
                with nn.activation_quant(self.act_dtype):
                    logits, cache = api.decode_span(p, cfg, t, c)
                if iv is not None:
                    logits = logits + iv[:, None, None]
                nf = nonfinite_rows(logits) if self.guards else None
                return logits, cache, nf

            self._draft_decode = jax.jit(_draft_decode_fn)
            self._draft_prefill = jax.jit(_draft_prefill_fn)
            self._verify = jax.jit(_verify_fn)

        # Attach the SLO controller last: it reads the engine's realized
        # capabilities (spec, kv_dtype) to build its degradation ladder,
        # and may extend verify_gammas — so this must precede the contract
        # gate below, whose compile budgets read that set.
        if controller is not None:
            controller.attach(self)

        # Opt-in contract gate: lower+compile the decode path NOW and run
        # the compiled-artifact rules against it (plus a dense dequantized
        # twin as the gather baseline), raising ContractViolation before
        # the engine serves a single token from a non-conforming artifact.
        self.contract_report = None
        if verify_contracts:
            from repro.analysis.artifacts import verify_engine
            self.contract_report = verify_engine(self)

    def _span(self, name: str, **attrs):
        """A host span around one phase of the engine's work: recorded by
        the telemetry when one is attached, else only the bare profiler
        annotation (no record, ~1 us when no trace is running)."""
        tel = self.telemetry
        if tel is not None:
            return tel.span(name, self.engine_steps, **attrs)
        return jax.profiler.TraceAnnotation(name)

    @property
    def clock(self):
        """The engine's injectable monotonic clock (``StepClock`` in
        deterministic runs) — drivers and the replayer read time through
        this, never through the wall clock directly (AST-DT1)."""
        return self._clock

    @contextlib.contextmanager
    def _mesh_scope(self):
        """Activate the engine's mesh around jit calls so the layer-level
        `dist.context.constrain` hints apply inside the traces; a no-op
        for single-device engines."""
        if self.mesh is None:
            yield
            return
        with self.mesh, dctx.use_mesh(self.mesh):
            yield

    def _repin_cache(self):
        """Re-pin the slot cache(s) after an eager host-side update (masked
        insert, preemption clear) so the decode jit keeps one stable input
        sharding; a no-op for single-device engines."""
        if self._cache_shardings is None:
            return
        self.cache = jax.device_put(self.cache, self._cache_shardings)
        if self.spec is not None:
            self.draft_cache = jax.device_put(self.draft_cache,
                                              self._cache_shardings)

    # ------------------------------------------------------------- paged sync
    def _sync_tables(self) -> None:
        """Broadcast the host page-table mirror into every cache's
        (L, n_slots, max_pages) table leaves before a jit consumes them.
        The engine is the SOLE table mutator (model code only reads
        tables), so one broadcast per dirty step keeps host and device in
        lockstep; clean steps cost nothing."""
        if not self._paged or not self._tables_dirty:
            return
        tbl = jnp.asarray(self._tables)

        def st(path, leaf):
            if getattr(path[-1], "name", None) == "table":
                return jnp.broadcast_to(tbl, leaf.shape).astype(leaf.dtype)
            return leaf

        self.cache = jax.tree_util.tree_map_with_path(st, self.cache)
        if self.spec is not None:
            self.draft_cache = jax.tree_util.tree_map_with_path(
                st, self.draft_cache)
        self._repin_cache()
        self._tables_dirty = False

    def _map_pools(self, fn) -> None:
        """Apply ``fn`` to every pool/scale leaf of the target (and draft)
        cache — the shared plumbing of page zeroing and COW copies."""
        def go(path, leaf):
            name = getattr(path[-1], "name", None)
            if name in _POOL_SRC or name in _SCALE_SRC:
                return fn(leaf)
            return leaf

        self.cache = jax.tree_util.tree_map_with_path(go, self.cache)
        if self.spec is not None:
            self.draft_cache = jax.tree_util.tree_map_with_path(
                go, self.draft_cache)
        self._repin_cache()

    def _zero_pages(self, pages: Sequence[int]) -> None:
        """Zero freshly allocated pages in both caches: preserves the
        contiguous invariant that every masked/unwritten cache row is
        exactly zero, so a recycled page can never leak its previous
        holder's rows into another request's (zero-weight) gather — and
        the zero-weight contribution itself stays exactly 0.0, keeping
        paged decode bitwise."""
        idx = jnp.asarray(sorted(set(int(p) for p in pages)), jnp.int32)
        self._map_pools(lambda l: l.at[:, idx].set(jnp.zeros((), l.dtype)))

    def _copy_pages(self, pairs: Sequence) -> None:
        """Copy-on-write: duplicate the pool (and scale) rows of shared
        pages into fresh private ones, target and draft cache alike."""
        olds = jnp.asarray([int(o) for o, _ in pairs], jnp.int32)
        news = jnp.asarray([int(n) for _, n in pairs], jnp.int32)
        self._map_pools(lambda l: l.at[:, news].set(l[:, olds]))

    def lower_decode(self):
        """AOT-lower the decode step against the engine's CURRENT
        params/cache (sharded when a mesh is wired) — for HLO inspection:
        tests assert the compiled step contains no weight-sized all-gather
        (decode stays weight-resident per shard).  Note: lowering traces,
        so it bumps `decode_traces`."""
        self._sync_tables()
        self.sentinel.observe_lowering("decode")
        toks = jnp.asarray(self.last_token, jnp.int32)
        with self._mesh_scope():
            return self._decode.lower(self.params, toks, self.cache, None)

    # ------------------------------------------------------------------ admit
    @staticmethod
    def _fill(req: Request) -> int:
        """Slot-cache positions this request occupies: the prompt plus one
        K/V write per decode step so far (the pending last_token's write
        belongs to the NEXT step)."""
        return len(req.prompt) + len(req.tokens) - 1

    # ------------------------------------------------------------- page plans
    def _note_page_peaks(self, req: Optional[Request] = None) -> None:
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.allocator.pages_in_use)
        if req is not None:
            self.peak_pages_per_request = max(
                self.peak_pages_per_request,
                len(self._req_pages.get(req.uid, ())))

    def _alloc_evicting(self, n: int) -> List[int]:
        """Allocate n pages, evicting prefix-registry entries (oldest
        first) under exhaustion; ``PoolExhausted`` propagates only once
        even an empty registry cannot satisfy the request."""
        while True:
            try:
                return self.allocator.alloc(n)
            except PoolExhausted:
                if (self.prefix_registry is None
                        or not self.prefix_registry.evict_one()):
                    raise
                self.page_evictions += 1

    def _plan_pages(self, req: Request, n_tokens: int,
                    exact_ok: bool = True):
        """Reserve the pages covering ``n_tokens`` resident positions for
        one request: shared prefix pages first (retained read-only from
        the registry), fresh private pages for the rest.  Returns
        ``(pages, table_row, write_row)`` — ``write_row`` marks the blocks
        the admission scatter may write (scratch everywhere else: shared
        pages stay read-only until COW).  All-or-nothing: on
        ``PoolExhausted`` no reference survives.  ``exact_ok=False``
        (resume) shares only whole pages, since the resumed request's
        tokens diverge inside the first partial page."""
        ps, scratch = self.page_size, self.allocator.scratch
        mp = self.max_len // ps
        nb = -(-n_tokens // ps)
        shared: List[int] = []
        if self.prefix_registry is not None:
            _, shared = self.prefix_registry.lookup(req.prompt,
                                                    exact_ok=exact_ok)
            shared = shared[:nb]
        if shared:
            # retain BEFORE allocating: allocation may evict the donor's
            # registry entry, and only our reference keeps its pages alive
            self.allocator.retain(shared)
        try:
            priv = self._alloc_evicting(nb - len(shared))
        except PoolExhausted:
            if shared:
                self.allocator.free(shared)
            raise
        if shared:
            self.prefix_hits += 1
            self.prefix_shared_tokens += min(len(shared) * ps, n_tokens)
        pages = shared + priv
        row = np.full((mp,), scratch, np.int32)
        row[:nb] = pages
        wrow = np.full((mp,), scratch, np.int32)
        wrow[len(shared):nb] = priv
        return pages, row, wrow

    def _release_pages(self, req: Request) -> None:
        """Drop a retiring/preempted request's page references and point
        its table row back at scratch.  The stale device rows stay until
        the pages are reallocated (and zeroed) — masking already hides
        them, exactly as a contiguous slot's stale tail is hidden."""
        if not self._paged:
            return
        pages = self._req_pages.pop(req.uid, None)
        if pages:
            self.allocator.free(pages)
        if req.slot >= 0:
            self._tables[req.slot, :] = self.allocator.scratch
            self._tables_dirty = True

    def _paged_insert(self, cache, frag, slots: Sequence[int],
                      lens: Sequence[int], wrows) -> Any:
        """Scatter a prefilled CONTIGUOUS fp cache fragment into pool
        pages — the paged counterpart of `_masked_group_insert`.
        ``wrows`` ((B, max_pages) int32) names the page each max_len block
        of each fragment row lands in; scratch marks blocks that are not
        this group's to write (shared prefix pages, unallocated tail) —
        their rows land in the pool's scratch page.  Rows past each true
        length are zeroed first (the bucketed-padding fix), so resident
        pages never hold padding garbage; int8 pools quantize each token
        row on the way in.  Device tables are NOT touched here — the host
        mirror was updated by the caller and `_sync_tables` broadcasts it
        before the next consuming jit."""
        B = len(slots)
        ps = self.page_size
        mp = self.max_len // ps
        lens_j = jnp.asarray(lens, jnp.int32)
        wt = jnp.asarray(np.asarray(wrows, np.int32).reshape(-1))
        slots_j = jnp.asarray(slots, jnp.int32)

        frag_leaves: Dict[Any, Array] = {}

        def collect(path, leaf):
            frag_leaves[getattr(path[-1], "name", None)] = leaf
            return leaf

        jax.tree_util.tree_map_with_path(collect, frag)

        def rows_for(src):
            v = frag_leaves[src][:, :B]          # (L, B, max_len, feat...)
            pos = jnp.arange(v.shape[2])
            keep = (pos[None, :] < lens_j[:, None]).reshape(
                (1, B, -1) + (1,) * (v.ndim - 3))
            v = jnp.where(keep, v, jnp.zeros((), v.dtype))
            v = v.reshape((v.shape[0], B, mp, ps) + v.shape[3:])
            return v.reshape((v.shape[0], B * mp, ps) + v.shape[4:])

        def ins(path, fl):
            name = getattr(path[-1], "name", None)
            if name in _LEN_LEAVES:
                return fl.at[:, slots_j].set(
                    jnp.broadcast_to(lens_j, (fl.shape[0], B)).astype(
                        fl.dtype))
            if name in _POOL_SRC:
                v = rows_for(_POOL_SRC[name])
                if self.kv_dtype == "int8":
                    flat = v.reshape(v.shape[:3] + (-1,))
                    xq, _ = kops.quantize_activations(
                        flat.astype(jnp.float32))
                    v = xq.reshape(v.shape)
                return fl.at[:, wt].set(v.astype(fl.dtype))
            if name in _SCALE_SRC:
                v = rows_for(_SCALE_SRC[name])
                flat = v.reshape(v.shape[:3] + (-1,))
                _, sc = kops.quantize_activations(flat.astype(jnp.float32))
                return fl.at[:, wt].set(sc[..., 0])
            return fl

        return jax.tree_util.tree_map_with_path(ins, cache)

    def _make_request(self, prompt: Sequence[int], max_new_tokens: int,
                      eos_id: Optional[int], priority: int,
                      deadline_ms: Optional[float]) -> Request:
        prompt = list(prompt)
        if len(prompt) == 0:
            raise AdmissionRejected("empty prompt")
        if len(prompt) + max_new_tokens > self.max_len:
            # The slot cache must hold the prompt plus every generated
            # token fed back through decode; past max_len the K/V
            # update clamps/drops, silently corrupting the last cache
            # position — reject at admission instead.
            raise AdmissionRejected(
                f"request does not fit its slot cache: {len(prompt)} "
                f"prompt + {max_new_tokens} new tokens > max_len="
                f"{self.max_len}; shorten the prompt, lower "
                f"max_new_tokens, or build the engine with a larger "
                f"max_len")
        now = self._clock()
        deadline = None
        if deadline_ms is not None:
            if deadline_ms <= 0:
                raise DeadlineExceeded(
                    f"deadline_ms={deadline_ms} is already expired at "
                    f"submission")
            deadline = now + deadline_ms / 1e3
        req = Request(self._uid, prompt, max_new_tokens, eos_id,
                      priority=priority, deadline=deadline, submitted_at=now)
        self._uid += 1
        return req

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None, priority: int = 0,
               deadline_ms: Optional[float] = None) -> int:
        """Enqueue one request into the bounded admission queue (the
        backpressure path — ``AdmissionRejected`` when the queue is full
        or the request can never fit, ``DeadlineExceeded`` when its SLO
        is already blown).  Admission into a free slot happens at the
        next ``step()``/``pump()``; returns the uid."""
        try:
            req = self._make_request(prompt, max_new_tokens, eos_id,
                                     priority, deadline_ms)
            self.queue.push(req)
        except AdmissionRejected:
            self.admission_rejections += 1
            raise
        if self.telemetry is not None:
            self.telemetry.on_submit(req, self.engine_steps)
        return req.uid

    def add_request(self, prompt: Sequence[int], max_new_tokens: int = 16,
                    eos_id: Optional[int] = None) -> int:
        return self.add_requests([prompt], max_new_tokens, eos_id)[0]

    def add_requests(self, prompts: Sequence[Sequence[int]],
                     max_new_tokens: int = 16,
                     eos_id: Optional[int] = None, priority: int = 0,
                     deadline_ms: Optional[float] = None) -> List[int]:
        """Admit several prompts directly into free slots (bypassing the
        queue); those sharing a length bucket are padded to it and
        prefilled in ONE batched call.  Returns uids in prompt order
        (look in `active`/`finished` for the Request objects — an
        immediate EOS or a one-token budget retires at admission).
        Raises typed ``AdmissionRejected`` when the slots don't exist —
        use ``submit()`` for queued, backpressured admission."""
        if len(prompts) > len(self.free):
            raise AdmissionRejected(
                f"need {len(prompts)} free slots, have {len(self.free)}")
        reqs = [self._make_request(p, max_new_tokens, eos_id, priority,
                                   deadline_ms) for p in prompts]
        if self.telemetry is not None:
            for req in reqs:
                self.telemetry.on_submit(req, self.engine_steps)
        self._admit(reqs)
        return [r.uid for r in reqs]

    def _admit(self, reqs: List[Request]) -> None:
        """Prefill-admit fresh requests into free slots, grouped by length
        bucket (one batched prefill per group; moe one per prefill).
        Chunked engines route to ``_admit_chunked``: slots are reserved
        now, prefill happens chunk by chunk across subsequent steps."""
        if self.chunked is not None:
            self._admit_chunked(reqs)
            return
        # moe prefill rows are coupled through router capacity (a row's
        # tokens change which of another row's tokens are dropped), so moe
        # admissions run one per prefill to match per-request admission;
        # all other families' rows are independent and share a call.
        batch_safe = self.cfg.family != "moe"
        groups: Dict[Any, List[int]] = {}
        for i, req in enumerate(reqs):
            bucket = self.bucketing.bucket_for(len(req.prompt))
            groups.setdefault(bucket if batch_safe else (bucket, i),
                              []).append(i)

        # Paged: reserve every request's pages up front (shared prefix
        # pages from the registry, fresh ones from the pool), so a late
        # PoolExhausted cannot leave half the batch admitted — unwind and
        # re-raise with no reference leaked.
        plans: Dict[int, Any] = {}
        if self._paged:
            try:
                for req in reqs:
                    plans[req.uid] = self._plan_pages(req, len(req.prompt))
            except PoolExhausted:
                for pages, _, _ in plans.values():
                    self.allocator.free(pages)
                raise

        tel = self.telemetry
        for key, idxs in groups.items():
            bucket = key if batch_safe else key[0]
            uids = [reqs[i].uid for i in idxs]
            n_tokens = sum(len(reqs[i].prompt) for i in idxs)
            B = len(idxs)
            # The batch size is bucketed too (next power of 2, capped at
            # n_slots): the jit cache is keyed on the (batch, bucket)
            # operand shape, so a drifting free-slot count must not mint
            # fresh compiles.  Dummy tail rows prefill garbage that is
            # never inserted.
            Bb = min(1 << (B - 1).bit_length(), self.n_slots)
            with self._span("engine.admit", uids=uids, bucket=bucket,
                            rows=Bb, prompt_tokens=n_tokens) as span:
                toks = np.zeros((Bb, bucket), np.int32)
                lens = np.ones((Bb,), np.int32)
                for r, i in enumerate(idxs):
                    toks[r, :len(reqs[i].prompt)] = reqs[i].prompt
                    lens[r] = len(reqs[i].prompt)
                self.bucketing.record(Bb, bucket)
                with self._span("engine.admit.fragment"):
                    cache_b = api.make_cache(self.cfg, Bb, self.max_len,
                                             dtype=self._cache_dtype)
                self.sentinel.observe("prefill", (Bb, bucket))
                with self._span("engine.admit.prefill"), self._mesh_scope():
                    logits, cache_b, nf = self._prefill(
                        self.params, jnp.asarray(toks), cache_b,
                        jnp.asarray(lens))
                    if self.spec is not None:
                        # the draft needs the prompt in ITS cache too (its
                        # first proposal continues from the target-sampled
                        # first token); the draft prefill's logits are
                        # unused
                        dcache_b = api.make_cache(self.cfg, Bb, self.max_len,
                                                  dtype=self._cache_dtype)
                        self.sentinel.observe("draft_prefill", (Bb, bucket))
                        dcache_b = self._draft_prefill(
                            self.draft_params, jnp.asarray(toks), dcache_b)
                self._step_prefill_tokens += Bb * bucket * (
                    2 if self.spec is not None else 1)
                if self._kv_int8_admission:
                    # kv_int8 degradation rung: admit through int8
                    # resident-page numerics.  Skip the fake-quant when the
                    # pool is already int8 (insertion quantizes anyway);
                    # always mark the request so preemption treats it as
                    # non-resumable.
                    for i in idxs:
                        reqs[i].kv_int8 = True
                    if self.kv_dtype != "int8":
                        cache_b = self._fake_quant_frag(cache_b)
                        if self.spec is not None:
                            dcache_b = self._fake_quant_frag(dcache_b)
                with self._span("engine.admit.first_token"):
                    firsts = np.asarray(jnp.argmax(logits, axis=-1),
                                        np.int32)
                    nf_h = np.asarray(nf) if nf is not None else None
                slots = [self.free.pop(0) for _ in idxs]
                with self._span("engine.admit.insert"):
                    self._insert_group(reqs, idxs, plans, slots, lens[:B],
                                       cache_b,
                                       dcache_b if self.spec is not None
                                       else None)
            if tel is not None:
                tel.on_admit(uids, slots, bucket, Bb,
                             span["end"] - span["start"], self.engine_steps)
                self._step_admitted.extend(uids)
                self._step_prompt_tokens += n_tokens
            for r, i in enumerate(idxs):
                req = reqs[i]
                req.slot = slots[r]
                req.transition(RequestState.RUNNING)
                self.active[req.uid] = req
                if nf_h is not None and nf_h[r] > 0:
                    # genuine non-finite prompt logits: quarantine at
                    # admission — no first token is sampled from garbage
                    self._quarantine(req, "prefill", int(nf_h[r]))
                else:
                    self._append_token(req, int(firsts[r]))

    def _insert_group(self, reqs: List[Request], idxs: List[int],
                      plans: Dict[int, Any], slots: List[int], lens,
                      cache_b, dcache_b) -> None:
        """Write an admitted group's prefill fragment(s) into the batched
        cache(s) at ``slots`` — paged: scatter into the reserved pages,
        record the table rows and register the prefixes; contiguous: the
        masked group insert — and re-pin the cache."""
        if self._paged:
            true_lens = lens.tolist()
            wrows = np.stack([plans[reqs[i].uid][2] for i in idxs])
            self.cache = self._paged_insert(self.cache, cache_b, slots,
                                            true_lens, wrows)
            if dcache_b is not None:
                self.draft_cache = self._paged_insert(
                    self.draft_cache, dcache_b, slots, true_lens, wrows)
            for r, i in enumerate(idxs):
                req = reqs[i]
                pages, row, _ = plans[req.uid]
                self._tables[slots[r]] = row
                self._req_pages[req.uid] = list(pages)
                # fake-quantized prefixes must never be shared with
                # nominal admissions (on an int8 pool every insert is
                # quantized identically, so sharing stays sound there)
                if (self.prefix_registry is not None
                        and not (req.kv_int8
                                 and self.kv_dtype != "int8")):
                    self.prefix_registry.register(req.prompt, pages)
                self._note_page_peaks(req)
            self._tables_dirty = True
        else:
            self.cache = _masked_group_insert(
                self.cache, cache_b, slots, lens.tolist(),
                self.bucketing.enabled)
            if dcache_b is not None:
                self.draft_cache = _masked_group_insert(
                    self.draft_cache, dcache_b, slots, lens.tolist(),
                    self.bucketing.enabled)
        self._repin_cache()

    # ------------------------------------------------------- chunked prefill
    def _admit_chunked(self, reqs: List[Request]) -> None:
        """Reserve slots and open a ``PrefillGroup``: the prompts prefill
        chunk by chunk across subsequent steps (``_process_chunks``) and
        the batched cache is only written at completion, so a
        mid-``PREFILLING`` preempt needs no rollback.  Paged pools reserve
        every page up front with the same all-or-nothing unwind as
        ``_admit``."""
        plans: Dict[int, Any] = {}
        if self._paged:
            try:
                for req in reqs:
                    plans[req.uid] = self._plan_pages(req, len(req.prompt))
            except PoolExhausted:
                for pages, _, _ in plans.values():
                    self.allocator.free(pages)
                raise
        tel = self.telemetry
        B = len(reqs)
        Bb = min(1 << (B - 1).bit_length(), self.n_slots)
        frag = api.make_cache(self.cfg, Bb, self.max_len,
                              dtype=self._cache_dtype)
        draft_frag = None
        if self.spec is not None:
            draft_frag = api.make_cache(self.cfg, Bb, self.max_len,
                                        dtype=self._cache_dtype)
        slots = [self.free.pop(0) for _ in reqs]
        group = PrefillGroup(
            reqs=list(reqs), slots=slots,
            lens=[len(r.prompt) for r in reqs], bb=Bb, frag=frag,
            draft_frag=draft_frag, plans=plans,
            t0=tel.now() if tel is not None else 0.0)
        for req, slot in zip(reqs, slots):
            req.slot = slot
            req.transition(RequestState.PREFILLING)
            if self._paged:
                # pages live in _req_pages from reservation on, so the
                # one release path covers cancel mid-prefill and retire
                self._req_pages[req.uid] = list(plans[req.uid][0])
        self._prefill_groups.append(group)

    @property
    def pending_prefills(self) -> int:
        """Live requests currently mid-chunked-prefill."""
        return sum(len(g.live_rows()) for g in self._prefill_groups)

    @property
    def prefill_backlog_tokens(self) -> int:
        """Padded prefill tokens still owed to pending groups — the
        controller's defer signal."""
        if self.chunked is None:
            return 0
        C = self.chunked.chunk_tokens
        return sum(g.bb * C * g.chunks_remaining(C)
                   for g in self._prefill_groups)

    def _process_chunks(self) -> None:
        """Advance pending prefill groups by whole chunks, head group
        first, under the per-step padded-token budget (controller budget
        when attached, else the config's).  At least one chunk runs per
        step — progress is unconditional — and a group that finishes is
        completed immediately so its first tokens land this step."""
        if not self._prefill_groups:
            return
        C = self.chunked.chunk_tokens
        if self.controller is not None:
            budget = self.controller.prefill_budget()
        else:
            budget = self.chunked.budget_tokens
        spent = 0
        progressed = False
        while self._prefill_groups:
            g = self._prefill_groups[0]
            if not g.live_rows() or g.done:
                self._prefill_groups.pop(0)
                self._finish_group(g)
                continue
            cost = g.bb * C
            if budget is not None and progressed and spent + cost > budget:
                break
            self._run_chunk(g, C)
            spent += cost
            progressed = True
            if g.done:
                self._prefill_groups.pop(0)
                self._finish_group(g)

    def _run_chunk(self, g: PrefillGroup, C: int) -> None:
        """Run one ``(bb, C)`` chunk for a group: every live row advances
        C positions in the fragment cache.  Rows whose TRUE last prompt
        token falls inside this chunk stash their first-token argmax (and
        guard verdict) for completion; other rows' chunk logits are
        bucketing garbage and are ignored, exactly as monolithic prefill
        ignores all but the last position."""
        start = g.progress
        toks = np.zeros((g.bb, C), np.int32)
        for i in g.live_rows():
            seg = g.reqs[i].prompt[start:start + C]
            toks[i, :len(seg)] = seg
        lens = np.ones((g.bb,), np.int32)
        for i, n in enumerate(g.lens):
            lens[i] = n
        self.sentinel.observe("chunk_prefill", (g.bb, C))
        with self._mesh_scope():
            logits, g.frag, nf = self._chunk_prefill(
                self.params, jnp.asarray(toks), g.frag, jnp.asarray(lens),
                jnp.asarray(start, jnp.int32))
            if self.spec is not None:
                self.sentinel.observe("draft_chunk_prefill", (g.bb, C))
                g.draft_frag = self._draft_chunk_prefill(
                    self.draft_params, jnp.asarray(toks), g.draft_frag)
        g.progress = start + C
        self.chunks_processed += 1
        self._step_prefill_tokens += g.bb * C * (
            2 if self.spec is not None else 1)
        fin = [i for i in g.live_rows() if start <= g.lens[i] - 1 < start + C]
        if fin:
            firsts = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            nf_h = np.asarray(nf) if nf is not None else None
            for i in fin:
                g.firsts[i] = int(firsts[i])
                if nf_h is not None:
                    g.nf[i] = int(nf_h[i])
        if self.telemetry is not None:
            live = g.live_rows()
            self.telemetry.on_chunk(
                [g.reqs[i].uid for i in live],
                [g.slots[i] for i in live], start, C, g.bb,
                self.engine_steps)

    def _finish_group(self, g: PrefillGroup) -> None:
        """Complete a finished group: insert the fragment rows of every
        surviving member into the batched cache (the same masked insert /
        paged scatter as monolithic admission), transition them
        PREFILLING -> RUNNING, and release their stashed first tokens."""
        rows = g.live_rows()
        if not rows:
            return
        tel = self.telemetry
        reqs = [g.reqs[i] for i in rows]
        slots = [g.slots[i] for i in rows]
        lens = [g.lens[i] for i in rows]
        idx = jnp.asarray(rows, jnp.int32)

        def take(path, leaf):
            del path
            return leaf[idx] if leaf.ndim == 1 else leaf[:, idx]

        sel = jax.tree_util.tree_map_with_path(take, g.frag)
        dsel = None
        if g.draft_frag is not None:
            dsel = jax.tree_util.tree_map_with_path(take, g.draft_frag)
        if self._kv_int8_admission:
            for req in reqs:
                req.kv_int8 = True
            if self.kv_dtype != "int8":
                sel = self._fake_quant_frag(sel)
                if dsel is not None:
                    dsel = self._fake_quant_frag(dsel)
        if self._paged:
            wrows = np.stack([g.plans[r.uid][2] for r in reqs])
            self.cache = self._paged_insert(self.cache, sel, slots, lens,
                                            wrows)
            if dsel is not None:
                self.draft_cache = self._paged_insert(
                    self.draft_cache, dsel, slots, lens, wrows)
            for req, slot in zip(reqs, slots):
                pages, row, _ = g.plans[req.uid]
                self._tables[slot] = row
                # real data only lands in the pages NOW — registering the
                # prefix any earlier would let a sharer read garbage; a
                # fake-quantized prefix is never registered (sharing it
                # would leak kv_int8 numerics into nominal admissions)
                if (self.prefix_registry is not None
                        and not (req.kv_int8
                                 and self.kv_dtype != "int8")):
                    self.prefix_registry.register(req.prompt, pages)
                self._note_page_peaks(req)
            self._tables_dirty = True
        else:
            # always masked: the fragment fill is chunk-padded past each
            # row's true length, so the tail must be zeroed on insert
            self.cache = _masked_group_insert(self.cache, sel, slots, lens,
                                              True)
            if dsel is not None:
                self.draft_cache = _masked_group_insert(
                    self.draft_cache, dsel, slots, lens, True)
        self._repin_cache()
        if tel is not None:
            tel.on_admit([r.uid for r in reqs], slots, g.progress, g.bb,
                         tel.now() - g.t0, self.engine_steps)
        for req, i in zip(reqs, rows):
            req.transition(RequestState.RUNNING)
            self.active[req.uid] = req
            nfc = g.nf.get(i, 0)
            if nfc > 0:
                self._quarantine(req, "prefill", nfc)
            else:
                self._append_token(req, g.firsts[i])

    def _fake_quant_frag(self, frag):
        """Round-trip a fragment cache's sequence leaves through the int8
        resident-page numerics (per-token-row absmax/127, the same
        quantizer the paged pool applies on write) — the kv_int8
        degradation rung's cheaper operating point for fp pools."""
        def fq(path, leaf):
            if getattr(path[-1], "name", None) in _SEQ_LEAVES:
                flat = leaf.reshape(leaf.shape[:3] + (-1,)).astype(
                    jnp.float32)
                xq, sc = kops.quantize_activations(flat)
                deq = (xq.astype(jnp.float32) * sc).reshape(leaf.shape)
                return deq.astype(leaf.dtype)
            return leaf
        return jax.tree_util.tree_map_with_path(fq, frag)

    def _preempt_prefilling(self, req: Request, reason: str) -> None:
        """Preempt a mid-``PREFILLING`` request: drop its fragment
        progress and re-queue at the front.  No cache rollback — the
        batched slot row was never written (insertion happens only at
        completion), so the only state to unwind is the reservation.
        Caller must have cancelled it from its group first."""
        if self.telemetry is not None:
            self.telemetry.on_preempt([(req.uid, req.slot)], reason,
                                      self.engine_steps)
        req.transition(RequestState.PREEMPTED)
        req.preemptions += 1
        self.preemptions += 1
        self._release_pages(req)
        self.free.append(req.slot)
        req.slot = -1
        req.transition(RequestState.QUEUED)
        self.queue.push_front(req)

    def _admit_resume(self, req: Request) -> None:
        """Resume a preempted request into a free slot, bit-identically to
        an uninterrupted run: bucketed prefill of the ORIGINAL prompt
        (same op as its first admission), then a teacher-forced decode
        replay of the generated prefix at batch 1 — exactly the decode
        steps the uninterrupted run already executed, so the rebuilt slot
        cache and every subsequent token are bitwise reproductions.
        (Prefilling prompt+prefix instead would NOT be bitwise: the
        prefill path reduces attention with online softmax, decode does
        not.)  The replay reuses the engine's decode jit at a (1,) batch
        shape — one extra trace for the engine lifetime, independent of
        how many resumes run."""
        P, toks = req.prompt, req.tokens
        n = len(P)
        fill = n + len(toks) - 1
        tel = self.telemetry
        t0 = tel.now() if tel is not None else 0.0
        # Paged: reserve the resumed fill's pages BEFORE any replay work —
        # PoolExhausted must leave the request untouched (still QUEUED) so
        # _pump_queue can park it at the queue front.  Only whole prefix
        # pages are shared (exact_ok=False): the replayed decode writes
        # land strictly past them.
        plan = None
        if self._paged:
            plan = self._plan_pages(req, fill, exact_ok=False)
        bucket = self.bucketing.bucket_for(n)
        ta = np.zeros((1, bucket), np.int32)
        ta[0, :n] = P
        self.bucketing.record(1, bucket)
        n_j = jnp.asarray([n], jnp.int32)
        # replay must stay fault-free: injection targets engine steps, and
        # catch-up work re-executes history that already happened cleanly
        riv = None if self.faults is None else jnp.zeros((1,), jnp.float32)
        cache_b = api.make_cache(self.cfg, 1, self.max_len,
                                 dtype=self._cache_dtype)
        dcache_b = None
        self.sentinel.observe("prefill", (1, bucket))
        with self._mesh_scope():
            _, cache_b, _ = self._prefill(self.params, jnp.asarray(ta),
                                          cache_b, n_j)
            if bucket != n:
                # in-place equivalent of the masked insert's padding fix:
                # zero the padded K/V tail, pin the fill counter to n
                cache_b = self._rollback(cache_b, n_j)
            if self.spec is not None:
                dcache_b = api.make_cache(self.cfg, 1, self.max_len,
                                          dtype=self._cache_dtype)
                self.sentinel.observe("draft_prefill", (1, bucket))
                dcache_b = self._draft_prefill(self.draft_params,
                                               jnp.asarray(ta), dcache_b)
                if bucket != n:
                    dcache_b = self._rollback(dcache_b, n_j)
            for t in toks[:-1]:
                tok = jnp.asarray([t], jnp.int32)
                self.sentinel.observe("decode", (1, riv is not None))
                _, cache_b, _ = self._decode(self.params, tok, cache_b, riv)
                self._step_decode_calls += 1
                if self.spec is not None:
                    self.sentinel.observe("draft_decode", (1,))
                    _, dcache_b = self._draft_decode(self.draft_params, tok,
                                                     dcache_b)
                    self._step_draft_calls += 1
        self._step_prefill_tokens += bucket * (
            2 if self.spec is not None else 1)
        slot = self.free.pop(0)
        if self._paged:
            pages, row, wrow = plan
            self.cache = self._paged_insert(self.cache, cache_b, [slot],
                                            [fill], wrow[None])
            if self.spec is not None:
                self.draft_cache = self._paged_insert(
                    self.draft_cache, dcache_b, [slot], [fill], wrow[None])
            self._tables[slot] = row
            self._req_pages[req.uid] = list(pages)
            self._tables_dirty = True
            self._note_page_peaks(req)
        else:
            self.cache = _masked_group_insert(self.cache, cache_b, [slot],
                                              [fill], False)
            if self.spec is not None:
                self.draft_cache = _masked_group_insert(
                    self.draft_cache, dcache_b, [slot], [fill], False)
        self._repin_cache()
        req.slot = slot
        req.transition(RequestState.RUNNING)
        self.active[req.uid] = req
        self.last_token[slot] = toks[-1]
        self.resumes += 1
        if tel is not None:
            tel.on_resume(req.uid, slot, max(len(toks) - 1, 0),
                          tel.now() - t0, self.engine_steps)

    # -------------------------------------------------------------- lifecycle
    def _retire(self, req: Request, state: RequestState = RequestState.FINISHED,
                diagnostics: Optional[Dict[str, Any]] = None) -> None:
        """Move a request (active or queued) to `finished` in a terminal
        state and recycle its slot — the single retirement bookkeeping for
        budget/EOS, truncation, abandonment, and quarantine."""
        if diagnostics is not None:
            req.diagnostics = diagnostics
        if self.telemetry is not None:
            # before the transition/slot recycle: the event carries the
            # slot the request retired from (or -1 for queued work)
            self.telemetry.on_retire(req, state, self.engine_steps)
        req.transition(state)
        self._release_pages(req)
        if req.slot >= 0:
            self.free.append(req.slot)
            req.slot = -1
        self.active.pop(req.uid, None)
        self.finished[req.uid] = req
        self.state_counts[state.value] += 1

    def _quarantine(self, req: Request, phase: str, count: int) -> None:
        """Numeric-guard quarantine: FAIL only the offending request, with
        diagnostics, while the rest of the batch proceeds."""
        self._retire(req, RequestState.FAILED, diagnostics={
            "kind": "nonfinite_logits", "phase": phase,
            "nonfinite": count, "engine_step": self.engine_steps})

    def _append_token(self, req: Request, t: int) -> None:
        """Append a sampled token and apply retirement — the single place
        the max_new_tokens / EOS check lives, so the prefill-sampled first
        token is held to the same budget as decode-step tokens."""
        req.tokens.append(t)
        self.last_token[req.slot] = t
        if self.telemetry is not None:
            self.telemetry.on_token(req, self.engine_steps)
        if (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and t == req.eos_id)):
            self._retire(req, RequestState.FINISHED)

    def set_cache_pressure(self, limit: Optional[int]) -> None:
        """Manually force an effective slot-cache limit below ``max_len``
        (None releases it).  Requests whose fill reaches the limit are
        preempted (or truncated under ``on_pressure="truncate"``) at the
        next step; the fault injector applies the same mechanism from its
        seeded pressure windows."""
        if limit is not None and limit < 2:
            raise ValueError(f"pressure limit must be >= 2, got {limit}")
        self._pressure_limit = limit

    def _effective_limit(self, step_idx: int) -> int:
        limit = self.max_len
        if self._pressure_limit is not None:
            limit = min(limit, self._pressure_limit)
        if self.faults is not None:
            fp = self.faults.pressure(step_idx, self.max_len)
            if fp is not None:
                limit = min(limit, fp)
        return limit

    def _victim_order(self) -> List[Request]:
        """Preemption order: lowest priority first, youngest (largest uid)
        within a priority — the cheapest work to redo."""
        return sorted(self.active.values(),
                      key=lambda r: (r.priority, -r.uid))

    def _preempt(self, victims: List[Request], reason: str) -> None:
        """Evict ``victims`` from their slots and re-queue them (front,
        bound-exempt) for a bit-identical resume.  The freed slots are
        cleared with ONE jitted masked rollback — victim lengths pinned to
        0 (K/V zeroed, fill rewound), surviving slots pinned at their true
        fill (a no-op for them) — reusing `_rollback_tail`'s leaf
        classification, so a recycled slot is indistinguishable from a
        never-used one."""
        if self.telemetry is not None:
            self.telemetry.on_preempt([(r.uid, r.slot) for r in victims],
                                      reason, self.engine_steps)
        for req in victims:
            req.transition(RequestState.PREEMPTED)
            req.preemptions += 1
            self.preemptions += 1
            del self.active[req.uid]
            self._release_pages(req)
            self.free.append(req.slot)
            req.slot = -1
        lens = np.zeros((self.n_slots,), np.int32)
        for r in self.active.values():
            lens[r.slot] = self._fill(r)
        lens_j = jnp.asarray(lens)
        with self._mesh_scope():
            self.cache = self._rollback(self.cache, lens_j)
            if self.spec is not None:
                self.draft_cache = self._rollback(self.draft_cache, lens_j)
        self._repin_cache()
        for req in victims:
            req.transition(RequestState.QUEUED)
            self.queue.push_front(req)

    def _alloc_decode_page(self, req: Request) -> int:
        """One fresh page for a running request's next K/V write: evict
        registry entries first, then preempt victims (policy and family
        permitting) until the pool yields a page; ``PoolExhausted``
        propagates when nothing preemptible remains."""
        while True:
            try:
                page = self._alloc_evicting(1)[0]
                self._note_page_peaks()
                return page
            except PoolExhausted:
                # kv_int8 admissions are excluded: preempting one would
                # force an fp prefix replay at resume, which cannot
                # reproduce the quantized cache history
                victims = [r for r in self._victim_order()
                           if r.uid != req.uid and not r.kv_int8]
                if (victims and self._preemptible
                        and self.on_pressure == "preempt"):
                    self._preempt(victims[:1], reason="pool_exhausted")
                    continue
                raise

    def _reserve_blocks(self, req: Request, horizon: int,
                        cow: List, fresh: List) -> None:
        """Make every table block the next ``horizon`` K/V writes of this
        request touch PRIVATE and allocated: scratch blocks get fresh
        pages (queued in ``fresh`` for zeroing), shared blocks (refcount >
        1) are replaced by private copies (queued in ``cow``) with the
        shared reference dropped — copy-on-write at the first write into
        shared territory.  Entries are uid-tagged: a preemption triggered
        by a LATER allocation may free and recycle pages queued earlier,
        and the caller filters stale entries by current ownership."""
        ps, scratch = self.page_size, self.allocator.scratch
        fill = self._fill(req)
        lo = fill // ps
        hi = min(fill + horizon - 1, self.max_len - 1) // ps
        s = req.slot
        pages = self._req_pages.setdefault(req.uid, [])
        for b in range(lo, hi + 1):
            pid = int(self._tables[s, b])
            if pid == scratch:
                new = self._alloc_decode_page(req)
                self._tables[s, b] = new
                pages.append(new)
                fresh.append((req.uid, new))
                self._tables_dirty = True
            elif self.allocator.refcount(pid) > 1:
                new = self._alloc_decode_page(req)
                self.allocator.free([pid])
                self._tables[s, b] = new
                pages[pages.index(pid)] = new
                cow.append((req.uid, pid, new))
                self.cow_copies += 1
                self._tables_dirty = True
        self._note_page_peaks(req)

    def _ensure_capacity(self, horizon: int) -> None:
        """Pre-step page reservation: every block the next ``horizon`` K/V
        writes touch must be private and allocated BEFORE the jit runs (the
        jit routes out-of-table writes to the scratch page — data loss, not
        corruption, but still loss).  Under exhaustion the starved request
        is retired TRUNCATED with diagnostics — typed, observable
        backpressure, never a silent clamp."""
        if not self._paged or not self.active:
            return
        cow: List = []
        fresh: List = []
        for uid in sorted(self.active):
            req = self.active.get(uid)
            if req is None:      # preempted by an earlier iteration's alloc
                continue
            try:
                self._reserve_blocks(req, horizon, cow, fresh)
            except PoolExhausted:
                self._retire(req, RequestState.TRUNCATED, diagnostics={
                    "kind": "pool_exhausted",
                    "pages_in_use": self.allocator.pages_in_use,
                    "n_pages": self.allocator.n_pages,
                    "engine_step": self.engine_steps})
        # a preemption mid-loop may have freed (and recycled) queued pages;
        # only zero/copy pages their planner still owns
        own = {u: set(p) for u, p in self._req_pages.items()}
        zs = [p for u, p in fresh if p in own.get(u, ())]
        pairs = [(o, n) for u, o, n in cow if n in own.get(u, ())]
        if zs:
            self._zero_pages(zs)
        if pairs:
            self._copy_pages(pairs)

    def _admissible(self, req: Request, limit: int) -> bool:
        """A queued request may take a slot only if its (prospective) fill
        sits below the effective cache limit — admitting it under pressure
        would just preempt it right back (admission churn)."""
        fill = len(req.prompt) + max(len(req.tokens), 1) - 1
        return fill < limit

    def pump(self) -> None:
        """Lifecycle housekeeping without decoding: abandon deadline-expired
        work (queued AND running — partial tokens are kept), apply cache
        pressure (preempt, or truncate under the opt-in policy), then fill
        free slots from the queue — resumed work first, then fresh work,
        highest priority first; strictly-higher-priority queued work may
        preempt the lowest-priority/youngest running victim.  ``step()``
        calls this first, so a driver that only ever calls ``step()``
        still drives every request to a terminal state."""
        with self._span("engine.pump"):
            self._pump()

    def _pump(self) -> None:
        step_idx = self.engine_steps
        now = self._clock()
        for req in list(self.active.values()):
            if req.deadline is not None and now >= req.deadline:
                self._retire(req, RequestState.ABANDONED, diagnostics={
                    "kind": "deadline", "where": "running",
                    "engine_step": step_idx})
        for g in self._prefill_groups:
            for i in g.live_rows():
                req = g.reqs[i]
                if req.deadline is not None and now >= req.deadline:
                    g.cancel(req.uid)
                    self._retire(req, RequestState.ABANDONED, diagnostics={
                        "kind": "deadline", "where": "prefilling",
                        "engine_step": step_idx})
        limit = self._effective_limit(step_idx)
        # cache pressure reaches mid-PREFILLING work too: a prompt that no
        # longer fits under the effective limit is cancelled from its group
        # (preempt-to-queue when policy and numerics allow — free, since
        # the slot row was never written — else typed truncation)
        for g in self._prefill_groups:
            for i in g.live_rows():
                req = g.reqs[i]
                if len(req.prompt) >= limit:
                    g.cancel(req.uid)
                    if (self.on_pressure == "preempt" and self._preemptible
                            and not req.kv_int8):
                        self._preempt_prefilling(req, "cache_pressure")
                    else:
                        self._retire(req, RequestState.TRUNCATED,
                                     diagnostics={
                                         "kind": "cache_pressure",
                                         "limit": limit,
                                         "engine_step": step_idx})
        victims: List[Request] = []
        for req in self._victim_order():
            fill = self._fill(req)
            if fill >= self.max_len:
                # the slot cache is genuinely full before the budget
                # (mutated mid-flight): resume is physically impossible
                # (the replayed prefix itself would not fit), so this is
                # terminal truncation regardless of policy
                self._retire(req, RequestState.TRUNCATED)
            elif fill >= limit:
                if (self.on_pressure == "preempt" and self._preemptible
                        and not req.kv_int8):
                    victims.append(req)
                else:
                    # kv_int8 admissions are non-resumable (an fp prefix
                    # replay cannot reproduce the quantized cache history),
                    # so pressure retires them like the truncate policy
                    self._retire(req, RequestState.TRUNCATED, diagnostics={
                        "kind": "cache_pressure", "limit": limit,
                        "engine_step": step_idx})
        if victims:
            self._preempt(victims, reason="cache_pressure")
        if self.controller is not None:
            # the controller decides BEFORE admission: rung moves and
            # shedding apply to the queue this pump is about to drain
            self.controller.on_step(self)
        self._pump_queue(now, limit)

    def _pump_queue(self, now: float, limit: int) -> None:
        # deadline-based abandonment of queued work
        for req in self.queue.expire(now):
            self._retire(req, RequestState.ABANDONED, diagnostics={
                "kind": "deadline", "where": "queued",
                "engine_step": self.engine_steps})
        # strictly-higher-priority queued work evicts the lowest-priority/
        # youngest running request when no slot is free
        while (len(self.queue) and not self.free and self._preemptible
               and self.on_pressure == "preempt"):
            best = self.queue.peek_best(lambda r: self._admissible(r, limit))
            victims = [r for r in self._victim_order() if not r.kv_int8]
            if (best is None or not victims
                    or best.priority <= victims[0].priority):
                break
            self._preempt([victims[0]], reason="priority")
        # admit: resumed requests one by one (each replays its own prefix),
        # fresh requests collected and admitted in one bucketed batch.
        # Under controller deferral only resumed work passes (its slot
        # debt already exists; deferring it would strand generated tokens).
        allow_fresh = (self.controller.allow_fresh(self)
                       if self.controller is not None else True)
        fresh: List[Request] = []
        while len(self.free) - len(fresh) > 0:
            req = self.queue.pop_best(
                lambda r: self._admissible(r, limit)
                and (allow_fresh or r.tokens))
            if req is None:
                break
            if req.tokens:
                try:
                    self._admit_resume(req)
                except PoolExhausted:
                    # page-pool backpressure: the resume waits its turn at
                    # the queue front; pages drain as running work retires
                    self.queue.push_front(req)
                    break
            else:
                fresh.append(req)
        if fresh:
            try:
                self._admit(fresh)
            except PoolExhausted:
                for r in reversed(fresh):
                    self.queue.push_front(r)
        if self.controller is not None and not allow_fresh and self.free:
            blocked = sum(1 for r in self.queue.requests()
                          if not r.tokens and self._admissible(r, limit))
            if blocked:
                self.controller.note_defer(self, blocked)

    def _tick(self) -> None:
        """Per-step lifecycle prologue.  A planned transient fault raises
        BEFORE any state mutation, so a driver's retry of ``step()`` is
        idempotent."""
        if (self.faults is not None
                and self.faults.should_fail_step(self.engine_steps)):
            raise EngineFault(
                f"injected transient step failure at engine step "
                f"{self.engine_steps}", transient=True, diagnostics={
                    "kind": "transient_step_failure",
                    "engine_step": self.engine_steps})
        self.pump()

    def _inject_vec(self):
        """The fault injector's additive per-slot logit vector for this
        step (zeros outside planned faults), or None when no injector is
        wired — the jit signature is stable per engine configuration."""
        if self.faults is None:
            return None
        occupied = sorted(r.slot for r in self.active.values())
        return jnp.asarray(self.faults.inject_vector(
            self.engine_steps, self.n_slots, occupied))

    # ------------------------------------------------------------------- step
    def step(self) -> Dict[int, Any]:
        """One engine step for all active slots.

        Runs the lifecycle prologue first (deadlines, cache pressure,
        queue admission — see ``pump()``); a planned transient fault
        raises ``EngineFault(transient=True)`` before any mutation.

        Vanilla: one batched decode, returns ``{uid: new_token}``.  With
        speculation (``spec=``): one propose/verify/rollback window,
        returns ``{uid: [tokens]}`` — between 1 and gamma+1 tokens per
        still-active request, every one of them exactly what vanilla
        greedy decode would have emitted (greedy speculation is
        lossless).  Quarantined (guard-failed) requests emit nothing and
        are absent from the returned dict — drain them via
        ``take_finished()``.

        With a ``cost_model``, the step's deterministic virtual cost is
        published as ``last_step_cost_ms`` (the replayer advances its
        ``StepClock`` by it, so chunking actually buys tail latency
        under virtual time instead of being free)."""
        self._step_prefill_tokens = 0
        self._step_decode_calls = 0
        self._step_draft_calls = 0
        self._step_verify_tokens = 0
        tel = self.telemetry
        root = jax.profiler.StepTraceAnnotation("engine.step",
                                                step_num=self.engine_steps)
        if tel is None:
            with root:
                out = self._step_inner()
        else:
            self._step_admitted = []
            self._step_prompt_tokens = 0
            self._step_event = None
            with tel.span("engine.step", self.engine_steps,
                          annotation=root) as span:
                out = self._step_inner()
                ev = self._step_event
                span["attrs"].update(
                    mode=ev[0] if ev is not None else "idle",
                    active=len(ev[2]) if ev is not None else 0,
                    admitted=self._step_admitted,
                    prompt_tokens=self._step_prompt_tokens)
            if ev is not None:
                mode, counts, slot_of, step_idx, extra = ev
                tel.on_step(mode, counts, slot_of,
                            span["end"] - span["start"], step_idx, **extra)
        if self.cost_model is not None:
            self.last_step_cost_ms = self.cost_model.cost_ms(
                prefill_tokens=self._step_prefill_tokens,
                decode_calls=self._step_decode_calls,
                draft_calls=self._step_draft_calls,
                verify_tokens=self._step_verify_tokens)
        return out

    def _step_inner(self) -> Dict[int, Any]:
        self._tick()
        if self.chunked is not None:
            # interleave pending prefill chunks BEFORE decode: completed
            # groups join `active` and decode this very step
            self._process_chunks()
        tel = self.telemetry
        if tel is not None:
            # per-step occupancy gauges (same-step samples overwrite, so
            # an idle driver loop cannot grow the series)
            tel.sample("queue_depth", self.engine_steps, len(self.queue))
            tel.sample("active_slots", self.engine_steps, len(self.active))
            if self._paged:
                tel.sample("pages_in_use", self.engine_steps,
                           self.allocator.pages_in_use)
        if not self.active:
            if len(self.queue) or self._prefill_groups:
                # idle step with pending work: step-indexed fault plans
                # (pressure windows, planned failures) must still elapse,
                # or queued-but-inadmissible work would livelock
                self.engine_steps += 1
            return {}
        if self._paged:
            # reserve (zeroed, private) pages for every K/V write this
            # step will issue — one for vanilla decode, the whole window
            # for speculation — then push the dirty table mirror
            self._ensure_capacity(
                self._gamma_eff + 1
                if self.spec is not None and self._spec_enabled else 1)
            if not self.active:
                if len(self.queue) or self._prefill_groups:
                    self.engine_steps += 1
                return {}
            self._sync_tables()
        if self.spec is not None and self._spec_enabled:
            return self._spec_step()
        step_idx = self.engine_steps
        slot_of = {uid: r.slot for uid, r in self.active.items()}
        with self._span("engine.decode.dispatch"):
            toks = jnp.asarray(self.last_token, jnp.int32)
            iv = self._inject_vec()
            self.sentinel.observe("decode", (self.n_slots, iv is not None))
            with self._mesh_scope():
                logits, self.cache, nf = self._decode(self.params, toks,
                                                      self.cache, iv)
                if self.spec is not None:
                    # spec-off degradation rung keep-warm: advance the
                    # draft cache with the SAME token so both caches stay
                    # uniformly filled and re-enabling speculation is
                    # seamless
                    self.sentinel.observe("draft_decode", (self.n_slots,))
                    _, self.draft_cache = self._draft_decode(
                        self.draft_params, toks, self.draft_cache)
                    self._step_draft_calls += 1
            self._step_decode_calls += 1
        # the host copy waits for the decode: the engine's one sync a step
        with self._span("engine.decode.sync"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            nf_h = np.asarray(nf) if nf is not None else None
        with self._span("engine.decode.emit"):
            emitted = {}
            for uid, req in list(self.active.items()):
                s = req.slot
                if nf_h is not None and nf_h[s] > 0:
                    # non-finite row: quarantine ONLY this request; the
                    # other rows of the same batched decode are unaffected
                    self._quarantine(req, "decode", int(nf_h[s]))
                    continue
                t = int(nxt[s])
                emitted[uid] = t
                self._append_token(req, t)
            self.engine_steps += 1
            self.emitted_tokens += len(emitted)
        if tel is not None:
            self._step_event = ("decode", {u: 1 for u in emitted}, slot_of,
                                step_idx, {})
        return emitted

    def _spec_step(self) -> Dict[int, List[int]]:
        """One speculation window: γ+1 draft decode steps (the last one
        write-only, so both caches advance uniformly to fill+γ+1), ONE
        target span verify, greedy acceptance, then a batched per-slot
        rollback of both caches to fill+accepted.  Retirement (EOS /
        max_new_tokens / cache-full) applies token by token in emission
        order, so a request retires at exactly the token vanilla decode
        would have retired it at.  A non-finite verify row (guards on)
        emits nothing: the slot rolls back to empty and the request is
        quarantined — rollback, then quarantine."""
        if not self.active:
            return {}
        # γ_eff: the controller's spec_half rung halves the window without
        # re-tracing (verify is compiled per distinct γ at build time)
        gamma = self._gamma_eff
        tel = self.telemetry
        step_idx = self.engine_steps
        slot_of = {uid: r.slot for uid, r in self.active.items()}
        accepted_ks: List[int] = []
        # per-slot fill BEFORE the window: prompt + appended tokens minus
        # the pending last_token (whose K/V the window itself writes)
        base_fill = {uid: self._fill(r) for uid, r in self.active.items()}

        # the chain feeds each draft decode's argmax back in, and on a
        # mesh that output is typed as replicated over it: start from the
        # same placement, or the second call retraces
        cur = jnp.asarray(self.last_token, jnp.int32)
        if self.mesh is not None:
            cur = jax.device_put(cur, NamedSharding(self.mesh, P()))
        d_cols = []                                     # device-resident
        iv = self._inject_vec()
        with self._mesh_scope():
            for j in range(gamma):
                self.sentinel.observe("draft_decode", (self.n_slots,))
                dlogits, self.draft_cache = self._draft_decode(
                    self.draft_params, cur, self.draft_cache)
                cur = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                d_cols.append(cur)
            # write-only catch-up: feed d_γ so the draft cache holds it if
            # the whole window is accepted (logits discarded).  The whole
            # propose chain stays on device — no host sync until the
            # verify logits are read below.
            self.sentinel.observe("draft_decode", (self.n_slots,))
            _, self.draft_cache = self._draft_decode(
                self.draft_params, cur, self.draft_cache)
            drafts_j = jnp.stack(d_cols, axis=1)        # (n_slots, γ)
            span = jnp.concatenate(
                [jnp.asarray(self.last_token, jnp.int32)[:, None],
                 drafts_j], axis=1)                     # (n_slots, γ+1)
            self.sentinel.observe(
                "verify", (self.n_slots, gamma + 1, iv is not None))
            vlogits, self.cache, nf = self._verify(self.params, span,
                                                   self.cache, iv)
        self._step_draft_calls += gamma + 1
        self._step_verify_tokens += gamma + 1
        drafts = np.asarray(drafts_j)
        greedy = np.asarray(jnp.argmax(vlogits, axis=-1), np.int32)
        nf_h = np.asarray(nf) if nf is not None else None

        emitted: Dict[int, List[int]] = {}
        lens = np.zeros((self.n_slots,), np.int32)   # 0 = free/retired slot
        for uid, req in list(self.active.items()):
            s = req.slot
            if nf_h is not None and nf_h[s] > 0:
                # mid-window quarantine: no token from this window can be
                # trusted, so emit nothing; lens[s]=0 makes the rollback
                # below clear the slot entirely before it is recycled
                self._quarantine(req, "verify", int(nf_h[s]))
                lens[s] = 0
                continue
            k, toks = speculative.accept_greedy(drafts[s], greedy[s])
            appended: List[int] = []
            for t in toks:
                if self._fill(req) >= self.max_len:
                    # the slot cache is full before the budget (mutated
                    # mid-flight) — later span rows fall past the cache
                    # end, so stop at exactly the token vanilla would
                    self._retire(req, RequestState.TRUNCATED)
                    break
                self._append_token(req, t)
                appended.append(t)
                if req.done:
                    break
            emitted[uid] = appended
            self.spec_drafted += gamma
            self.spec_accepted += k
            self.emitted_tokens += len(appended)
            accepted_ks.append(k)
            lens[s] = 0 if req.done else base_fill[uid] + len(appended)
        self.engine_steps += 1

        lens_j = jnp.asarray(lens)
        with self._mesh_scope():
            self.cache = self._rollback(self.cache, lens_j)
            self.draft_cache = self._rollback(self.draft_cache, lens_j)
        self._repin_cache()
        if tel is not None:
            self._step_event = (
                "spec", {u: len(v) for u, v in emitted.items()}, slot_of,
                step_idx,
                {"window": speculative.window_summary(gamma, accepted_ks)})
            for k in accepted_ks:
                tel.registry.histogram(
                    "spec_accepted_per_window", lo=0.5,
                    hi=float(max(gamma + 1, 2)), per_decade=16).observe(k)
        return emitted

    def run_to_completion(self, max_steps: int = 256, strict: bool = True,
                          retry: Optional[RetryPolicy] = None) -> List[int]:
        """Step until every submitted request reaches a terminal state
        (the queue drains through ``pump()`` inside ``step()``).  Returns
        the uids still in flight when max_steps runs out ([] == all
        finished); with strict=True (default) exhausting max_steps raises
        ``IncompleteRun`` carrying the partial outputs and lifecycle
        states of every unfinished request, so a truncated run cannot be
        mistaken for completion AND already-generated work survives the
        error.  ``retry=RetryPolicy(...)`` absorbs transient
        ``EngineFault``s (bounded attempts, backoff); without it they
        propagate."""
        consecutive_faults = 0
        steps = 0
        while steps < max_steps:
            if (not self.active and not len(self.queue)
                    and not self._prefill_groups):
                return []
            try:
                self.step()
            except EngineFault as e:
                if retry is None or not e.transient:
                    raise
                consecutive_faults += 1
                if consecutive_faults >= retry.max_attempts:
                    raise
                backoff = (retry.backoff_s
                           * retry.multiplier ** (consecutive_faults - 1))
                if backoff > 0:
                    retry.sleep(backoff)
                continue
            consecutive_faults = 0
            steps += 1
        prefilling = [r for g in self._prefill_groups for r in g.live()]
        unfinished = sorted(set(self.active) | set(self.queue.uids())
                            | {r.uid for r in prefilling})
        if unfinished and strict:
            reqs = dict(self.active)
            reqs.update({r.uid: r for r in self.queue.requests()})
            reqs.update({r.uid: r for r in prefilling})
            raise IncompleteRun(
                f"run_to_completion: max_steps={max_steps} exhausted with "
                f"{len(unfinished)} requests not terminal (uids "
                f"{unfinished}); partial outputs and lifecycle states "
                f"attached to this error",
                partial={u: list(reqs[u].tokens) for u in unfinished},
                states={u: reqs[u].state for u in unfinished})
        return unfinished

    # ------------------------------------------------------------------ stats
    def take_finished(self) -> Dict[int, Request]:
        """Drain and return retired requests (bounds engine memory)."""
        out, self.finished = self.finished, {}
        return out

    def stats(self) -> Dict[str, Any]:
        s = self.bucketing.stats
        out = {
            "prefill_traces": self.prefill_traces,
            "decode_traces": self.decode_traces,
            "buckets": list(self.bucketing.buckets()),
            "bucket_hits": s.hits,
            "bucket_misses": s.misses,
            "bucket_hit_rate": s.hit_rate,
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "act_dtype": self.act_dtype or "f32",
            # decode-loop emission: tokens appended by step() over engine
            # steps (decode steps vanilla; speculation windows with spec)
            "emitted_tokens": self.emitted_tokens,
            "engine_steps": self.engine_steps,
            "tokens_per_step": (self.emitted_tokens / self.engine_steps
                                if self.engine_steps else 0.0),
            # lifecycle: queue + terminal-state + preemption accounting
            "queued": len(self.queue),
            "queue_depth": self.queue.depth,
            "queue_peak_depth": self.queue.peak_depth,
            "guards": self.guards,
            "on_pressure": self.on_pressure,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "admission_rejections": self.admission_rejections,
            "lifecycle": {st.value: self.state_counts.get(st.value, 0)
                          for st in sorted(TERMINAL_STATES,
                                           key=lambda s: s.value)},
        }
        if self._paged:
            # HBM accounting straight off the live pool leaves: bytes per
            # page (all layers, pools + scales) x pool occupancy, next to
            # what the contiguous fp layout would have pinned per slot.
            ps = self.page_size
            per_page = 0
            per_tok_fp = 0
            fp_size = jnp.zeros((), self._cache_dtype).dtype.itemsize
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    self.cache)[0]:
                name = getattr(path[-1], "name", None)
                if name in _POOL_SRC:
                    feat = int(np.prod(leaf.shape[3:])) if leaf.ndim > 3 else 1
                    per_page += (leaf.shape[0] * ps * feat
                                 * leaf.dtype.itemsize)
                    per_tok_fp += leaf.shape[0] * feat * fp_size
                elif name in _SCALE_SRC:
                    per_page += leaf.shape[0] * ps * leaf.dtype.itemsize
            out["paged"] = {
                "page_size": ps,
                "n_pages": self.allocator.n_pages,
                "pages_in_use": self.allocator.pages_in_use,
                "pages_free": self.allocator.n_free,
                "pool_utilization": (self.allocator.pages_in_use
                                     / self.allocator.n_pages),
                "peak_pages_in_use": self.peak_pages_in_use,
                "peak_pages_per_request": self.peak_pages_per_request,
                "kv_dtype": self.kv_dtype or str(self._cache_dtype),
                "bytes_per_page": per_page,
                "bytes_resident": self.allocator.pages_in_use * per_page,
                "bytes_pool": self.allocator.n_pages * per_page,
                "bytes_contiguous_fp": (self.n_slots * self.max_len
                                        * per_tok_fp),
                "prefix_hits": self.prefix_hits,
                "prefix_shared_tokens": self.prefix_shared_tokens,
                "cow_copies": self.cow_copies,
                "page_evictions": self.page_evictions,
                "pages_allocated_total": self.allocator.pages_allocated_total,
                "pages_freed_total": self.allocator.pages_freed_total,
                "registry_entries": (len(self.prefix_registry)
                                     if self.prefix_registry is not None
                                     else 0),
            }
        if self.spec is not None:
            out.update({
                "spec_gamma": self.spec.gamma,
                "spec_gamma_eff": self._gamma_eff,
                "spec_enabled": self._spec_enabled,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                # fraction of proposed draft tokens the target kept
                "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                    if self.spec_drafted else 0.0),
                "draft_prefill_traces": self.draft_prefill_traces,
                "draft_decode_traces": self.draft_decode_traces,
                "verify_traces": self.verify_traces,
            })
        if self.chunked is not None:
            out["chunked"] = {
                "chunk_tokens": self.chunked.chunk_tokens,
                "budget_tokens": self.chunked.budget_tokens,
                "chunk_prefill_traces": self.chunk_prefill_traces,
                "draft_chunk_prefill_traces": self.draft_chunk_prefill_traces,
                "chunks_processed": self.chunks_processed,
                "groups_pending": len(self._prefill_groups),
                "prefilling": self.pending_prefills,
            }
        if self.last_step_cost_ms is not None:
            out["last_step_cost_ms"] = self.last_step_cost_ms
        if self.controller is not None:
            out["controller"] = self.controller.stats()
        return out

    def reset_peaks(self) -> None:
        """Drop high-water marks (queue depth, page peaks) to CURRENT
        occupancy.  Back-to-back A/B replays share one process; without
        this the second run's report inherits the first run's peaks."""
        self.queue.reset_peaks()
        if self._paged:
            self.peak_pages_in_use = self.allocator.pages_in_use
            self.peak_pages_per_request = max(
                (len(p) for p in self._req_pages.values()), default=0)

    def metrics(self) -> MetricsRegistry:
        """The ONE uniform metrics surface: every ``stats()`` number —
        spec counters, paged byte ladder, lifecycle tallies — projected
        onto the telemetry registry as ``serve.*`` gauges, joining, when a
        recorder is attached, its histograms/timelines and its phase
        totals and compile/GC counters as ``telemetry.*`` gauges.
        ``launch/serve.py --stats`` renders this."""
        tel = self.telemetry
        if tel is None:
            return registry_from_stats(self.stats(), MetricsRegistry())
        registry_from_stats(self.stats(), tel.registry)
        return registry_from_stats(tel.summary(), tel.registry,
                                   prefix="telemetry")
