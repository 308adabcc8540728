"""Continuous-batching LLM serving engine over the paged KV cache.

Reference role: the serving loop the reference's block-cache op exists
for — admit requests into a fixed decode batch as slots free up,
prefill newcomers, decode everyone in lockstep, evict on finish
(PaddleNLP's dynamic-batching inference server over
block_multihead_attention; fleet_executor dist_model serving).

TPU-native shape: the decode batch is FIXED SIZE (one compiled step
serves forever — no retracing as requests come and go); per-row block
tables + lengths make rows independent, so a slot is just (table row,
lens entry).  Admission packs every waiting prompt — mixed lengths,
prefix-cache suffixes — into ONE token stream with segment ids and
prefills it as a single segmented-flash program (the packed varlen
lane, single-device and TP alike — the sharded form composes through
the same shard_map seam as the decode step; the per-bucket batched
and per-chunk lanes remain as explicit fallbacks); the shared
per-token step then advances every active slot.  Inactive slots
carry ``lens = 0`` and attend nothing (the kernel visits zero
pages).

With a HOST PAGE TIER on the cache (``PagedKVCache(host_pages=N)``,
models/kv_offload.py) preemption swaps the victim's pages to host RAM
and re-admission restores them with ZERO prefill tokens, guarded by a
bytes-vs-FLOPs cost model; without one (or when the model prices the
re-prefill below the DMA) preemption stays recompute-style.

The engine is deliberately host-simple: a queue, a free-slot list, and
numpy bookkeeping — the device work is the two jitted programs.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import (EngineMetrics, MetricsRegistry,
                             advance_phase, bind_engine_gauges,
                             finalize_request_trace)
from ..profiler.utils import RecordEvent
from ..testing import faults
from .llama_pretrain import LlamaPretrainConfig, _mm, _rms_norm
from .paged_decode import (PagedKVCache, _prefill, _prefill_chunk,
                           _prefill_packed, _prefill_packed_tp,
                           _pick_token, make_mixed_step,
                           make_paged_decode_step,
                           make_paged_decode_step_async,
                           make_paged_decode_step_multi,
                           make_paged_decode_step_tp, make_spec_step,
                           tp_collective_bytes_per_step)

__all__ = ["ContinuousBatchingEngine", "EngineDeadError",
           "EngineSupervisor", "PRIORITIES", "QueueFullError",
           "QuotaExceededError", "Request", "SchedulerPolicy",
           "SpecConfig", "TenantQuotas", "priority_rank"]

# Priority classes, best first.  The admission queue orders by
# (class, arrival), preemption-victim selection prefers the lowest
# class, and overload shedding is class-aware (reject low, degrade
# normal, protect high) — see SchedulerPolicy.
PRIORITIES = ("high", "normal", "low")
_PRIO_RANK = {"high": 0, "normal": 1, "low": 2}


def priority_rank(priority: str) -> int:
    """Sort key for a priority class: 0 is best ("high").  Unknown
    strings rank as "normal" — rank is an ORDERING helper; validation
    happens once, at ``submit()``."""
    return _PRIO_RANK.get(priority, 1)


class QueueFullError(RuntimeError):
    """``submit()`` refused by the bounded admission queue
    (``max_queue_len`` / ``max_queued_tokens``).  Carries a finite
    ``retry_after`` hint (seconds) priced off the engine's observed
    throughput — the HTTP front maps this to ``429`` +
    ``Retry-After``."""

    def __init__(self, why: str, retry_after: float = 1.0):
        super().__init__(why)
        self.retry_after = float(retry_after)


class QuotaExceededError(QueueFullError):
    """``submit()`` refused because the request's TENANT is over its
    token-rate budget (:class:`TenantQuotas`) — distinct from pool
    backpressure so clients and dashboards can tell "you are over
    YOUR budget" from "the engine is full".  Subclasses
    :class:`QueueFullError` so every HTTP front maps it to ``429`` +
    ``Retry-After`` for free; ``retry_after`` is derived from the
    bucket refill rate (how long until the bucket holds this
    request's cost again), not from engine throughput."""

    def __init__(self, why: str, retry_after: float = 1.0,
                 tenant: Optional[str] = None):
        super().__init__(why, retry_after=retry_after)
        self.tenant = tenant


class TenantQuotas:
    """Per-tenant token-rate buckets enforced at admission: each
    tenant accrues ``rate_tokens_per_s`` up to ``burst_tokens`` and a
    submission charges its WORST-CASE token cost (prompt +
    max_new_tokens) up front, so one tenant's burst can never consume
    another tenant's capacity — isolation holds even when the pool
    itself still has room.  ``overrides`` maps tenant name ->
    ``(rate_tokens_per_s, burst_tokens)`` for per-tenant contracts;
    requests with ``tenant=None`` are UNMETERED (quota is an opt-in
    contract, not a default tax).

    Thread safety: ``external-lock``, like ``submit()`` — the engine
    and the fleet router both consult it behind their own serving
    lock (see ``analysis/annotations.py THREAD_SAFETY``)."""

    def __init__(self, rate_tokens_per_s: float,
                 burst_tokens: Optional[float] = None,
                 overrides: Optional[Dict[str, tuple]] = None):
        if rate_tokens_per_s <= 0:
            raise ValueError("rate_tokens_per_s must be > 0, got "
                             f"{rate_tokens_per_s}")
        self.rate = float(rate_tokens_per_s)
        self.burst = float(burst_tokens if burst_tokens is not None
                           else rate_tokens_per_s)
        self.overrides = dict(overrides or {})
        # tenant -> [level, last_refill_t]; buckets start FULL so a
        # cold tenant gets its burst immediately
        self._buckets: Dict[str, list] = {}

    def _limits(self, tenant: str) -> tuple:
        if tenant in self.overrides:
            rate, burst = self.overrides[tenant]
            return float(rate), float(burst)
        return self.rate, self.burst

    def charge(self, tenant: Optional[str], cost: float,
               now: float) -> None:
        """Deduct ``cost`` tokens from ``tenant``'s bucket or raise
        :class:`QuotaExceededError` with a refill-derived
        ``Retry-After``.  All-or-nothing: a refused charge leaves the
        bucket untouched (the rejected request must not erode the
        tenant's budget)."""
        if tenant is None:
            return
        rate, burst = self._limits(tenant)
        bucket = self._buckets.get(tenant)
        if bucket is None:
            bucket = self._buckets[tenant] = [burst, now]
        level, last = bucket
        level = min(burst, level + (now - last) * rate)
        bucket[1] = now
        if cost > level:
            # a cost the bucket can NEVER hold (> burst) still answers
            # finitely: time to refill the whole burst — the client's
            # real fix is a smaller request, and the hint says so
            deficit = min(cost, burst) - level
            bucket[0] = level
            raise QuotaExceededError(
                f"tenant {tenant!r} over token-rate quota: cost "
                f"{cost:.0f} > bucket {level:.0f} (rate {rate:.0f} "
                f"tok/s, burst {burst:.0f})",
                retry_after=float(min(max(deficit / rate, 0.1), 60.0)),
                tenant=tenant)
        bucket[0] = level - cost


class SchedulerPolicy:
    """The scheduler-policy seam extracted from the engine's
    admission/preemption paths: WHICH queued request admits next,
    WHICH active request is evicted under pool pressure, and HOW
    overload sheds by class.  The default implements the SLO
    guardrails contract — admission orders by (class, arrival),
    preemption evicts the lowest class first (LIFO by ``admit_seq``
    within a class), and ``queue_capacity_reason()`` tripping sheds
    class-aware: reject low with 429, degrade normal (halve
    ``max_new_tokens``, disable spec), protect high up to
    ``overload_factor`` times the configured bounds.  Subclass and
    pass ``ContinuousBatchingEngine(policy=...)`` to change any of
    the three decisions without touching the admission machinery."""

    # hard-bound multiplier protected classes may overflow the soft
    # queue bounds by under overload (beyond it even "high" rejects:
    # truly unbounded admission is a worse failure than a 429)
    overload_factor = 2.0

    def order_queue(self, queue: deque) -> deque:
        """Class-order the admission queue.  The sort is STABLE by
        rank only, so arrival order — including a preempted request's
        requeue-at-the-head position — is preserved within a class."""
        return deque(sorted(queue,
                            key=lambda r: priority_rank(r.priority)))

    def select_victim(self, victims: List[int],
                      active: Dict[int, "Request"]) -> int:
        """Preemption victim among ``victims`` (slot ids): lowest
        class first, most recently admitted within a class —
        high-priority work survives pool pressure at the expense of
        low, and within a class the old LIFO-by-``admit_seq`` rule
        still minimizes wasted prefill."""
        return max(victims,
                   key=lambda s: (priority_rank(active[s].priority),
                                  active[s].admit_seq))

    def preemptable_for(self, head: "Request",
                        active: Dict[int, "Request"]) -> List[int]:
        """Slots the queue head may evict to get a seat: every active
        request of a STRICTLY lower class.  Empty list = no priority
        preemption (equal-class work is never churned)."""
        hr = priority_rank(head.priority)
        return [s for s, r in active.items()
                if priority_rank(r.priority) > hr]

    def shed(self, priority: str) -> str:
        """Overload verdict for a class when the soft capacity bound
        trips: ``"reject"`` (429 now), ``"degrade"`` (admit with
        halved ``max_new_tokens`` + spec off, up to the hard bound)
        or ``"admit"`` (untouched, up to the hard bound)."""
        if priority == "low":
            return "reject"
        if priority == "normal":
            return "degrade"
        return "admit"


class EngineDeadError(RuntimeError):
    """:class:`EngineSupervisor`'s restart budget is exhausted: the
    engine is genuinely unrecoverable and the serving front should
    fail pending requests loudly instead of retrying forever."""


def _release_engine_claims(engine) -> None:
    """Best-effort release of EVERY page/swap claim a dead engine
    holds — each slot off the free list (active rows AND rows
    stranded mid-admission by a fatal step) and every parked swap
    record — so a cache that outlives the engine starts from clean
    page accounting (verified by ``PagedKVCache.audit()`` in tests).
    Shared by :class:`EngineSupervisor`'s restart and the fleet
    router's replica-death path: the claim-release rules must never
    diverge between them."""
    for slot in range(engine.B):
        if slot in engine._free_slots:
            continue
        try:
            engine.cache.release_row(slot)
        except Exception:
            pass
    for handle in list(engine._swap_handles.values()):
        try:
            engine.cache.discard_swap(handle)
        except Exception:
            pass
    engine._swap_handles.clear()
    # engines with claims beyond rows + swap records (the disagg
    # PrefillEngine's staged handoff exports) release them through
    # this seam so orphaned handoff records are reclaimed, not leaked
    extra = getattr(engine, "release_extra_claims", None)
    if extra is not None:
        try:
            extra()
        except Exception:
            pass


def _tid(req: "Request") -> Optional[str]:
    """Exemplar handle: the trace id behind a histogram observation
    (None with tracing off — the observe() call is unchanged)."""
    return req.trace.trace_id if req.trace is not None else None


def _finalize_trace(req: "Request") -> None:
    """Retirement-time trace materialization: close the request's
    open phase interval at ``t_finish`` and report the accrued
    intervals as synthetic spans — the ONE place per-request phase
    clocks become trace spans (never per decode step, so the overlap
    pipeline's zero-added-host-syncs discipline holds).  Engine-owned
    (unmanaged) contexts also CLOSE the trace here with the request's
    final status; router/coordinator-managed ones close at their
    finished-merge, after the fleet rid is restored.  Never raises:
    tracing must not be able to kill retirement."""
    try:
        ctx = req.trace
        if ctx is None:
            # clocks close even with tracing off — the span-
            # accounting consistency contract is on the Request
            if req.t_phase and req.phase != "done":
                advance_phase(req, "done",
                              now=req.t_finish if req.t_finish
                              else None)
            return
        req.trace = None              # report + close exactly once
        finalize_request_trace(ctx, req, close=not ctx.managed,
                               tokens=len(req.generated),
                               preemptions=req.preempted)
    except Exception:
        pass


def _chip_flops_default() -> float:
    """Chip compute rate for the bytes-vs-FLOPs cost models
    (preemption swap-vs-recompute, disagg handoff-vs-stall), read from
    the one peaks table (``device/peaks.py``); an unknown device
    raises there.  ONE definition — the models must never disagree
    about the chip."""
    from ..device.peaks import chip_peaks
    return chip_peaks().flops


def _count_params(params) -> int:
    """Total parameter count (the 2*N*L FLOPs-per-token estimate's
    N); engines cache it in ``_n_params``."""
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params))


def _drive_to_completion(driver, max_steps: int):
    """Step ``driver`` (an engine or a supervisor) until its queue
    drains; returns all finished requests in completion order."""
    out = []
    steps = 0
    while driver.has_work():
        driver.step()
        out.extend(driver.finished())
        steps += 1
        if steps > max_steps:
            raise RuntimeError("serving loop exceeded max_steps")
    return out


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # [len] int64
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    stop_sequences: Optional[List[List[int]]] = None
    # per-request speculative toggle: True/False overrides the
    # engine SpecConfig's default_on; None inherits it.  Rows with
    # spec off ride the SAME fused round (their accept window
    # collapses to one plain greedy token) — on/off mixes in one
    # batch with zero extra dispatches.
    spec: Optional[bool] = None
    admit_seq: int = -1                   # admission order (preemption)
    preempted: int = 0                    # times evicted + requeued
    # QoS: priority class ("high"/"normal"/"low") orders admission and
    # picks preemption victims (SchedulerPolicy); ``tenant`` keys the
    # token-rate quota buckets; ``degraded`` marks a request admitted
    # under overload with a halved budget + spec off — surfaced in the
    # done message so the client knows it got the degraded tier
    priority: str = "normal"
    tenant: Optional[str] = None
    degraded: bool = False
    # lifecycle timestamps (time.monotonic; 0.0 = not reached).
    # t_admit/t_first_token survive preemption — a re-admission must
    # not re-observe queue-wait/TTFT.
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0
    # fault tolerance: absolute monotonic deadline (0.0 = none) and
    # how the request ended — "ok" (eos/stop/budget), "cancelled",
    # "expired" (deadline), or "error" (its decode wave faulted);
    # ``error`` carries the fault text for non-"ok" endings
    deadline: float = 0.0
    status: str = "ok"
    error: Optional[str] = None
    # -- distributed tracing (observability/tracing.py) -----------------
    # current lifecycle phase + the monotonic instant it began; every
    # transition appends one closed (phase, t0, t1) interval to
    # phase_log — O(1) work at scheduler mutation points only, NEVER
    # per decode token.  ``trace`` is the propagated TraceContext
    # (None with tracing off); the intervals materialize as synthetic
    # spans once, at retirement (_finalize_trace).
    phase: str = "queued"
    t_phase: float = 0.0
    phase_log: List = field(default_factory=list)
    trace: Optional[object] = None


@dataclass
class SpecConfig:
    """Speculative decoding as a first-class engine lane:
    ``ContinuousBatchingEngine(spec=SpecConfig(...))`` replaces the
    old SpeculativeEngine subclass — every decode round becomes ONE
    fused draft+verify dispatch (:func:`make_spec_step`) committing
    up to ``gamma + 1`` tokens per active row, token-exact vs plain
    greedy decode (exact verification), composed with the sync and
    overlap lanes, int8-KV pools, TP meshes, preemption and
    prefix caching.

    ``source``:

    * ``"draft"`` — a small DRAFT MODEL proposes: ``draft_cfg`` /
      ``draft_params`` / ``draft_cache`` are required; the
      gamma-iteration draft scan runs inside the same dispatch as
      the verify.  On a TP mesh the draft cache must be built on the
      ENGINE's mesh (kv-head-sharded like the target pool).
    * ``"prompt_lookup"`` — MODEL-FREE n-gram drafting: the host
      matches the last ``ngram`` committed tokens against each
      request's own history and proposes the continuation of the
      previous occurrence (great for extractive/repetitive outputs;
      zero extra model plumbing, so fleet and disagg decode
      replicas get spec through a single knob).  Proposals feed the
      verify-only fused form; a miss simply costs acceptance.

    ``adaptive_gamma`` retunes gamma each round from the acceptance
    EMA in ``[1, max_gamma]``; each distinct gamma compiles one
    fused program (memoised — a bounded, one-time cost per value).

    ``default_on`` is the per-request default; ``submit(spec=...)``
    overrides per request."""
    gamma: int = 4
    source: str = "draft"
    draft_cfg: Optional[LlamaPretrainConfig] = None
    draft_params: object = None
    draft_cache: Optional[PagedKVCache] = None
    adaptive_gamma: bool = False
    max_gamma: int = 8
    ngram: int = 3
    default_on: bool = True


class ContinuousBatchingEngine:
    """``submit()`` requests, call ``step()`` in a loop; finished
    requests appear in ``finished()``.

    ``eos_id``: generation stops at this token (or at the request's
    ``max_new_tokens``).  The decode step compiles ONCE for the engine's
    batch size; prefill compiles once per prompt-length bucket
    (lengths are padded up to ``prefill_bucket``).
    """

    def __init__(self, cfg: LlamaPretrainConfig, params,
                 cache: PagedKVCache, eos_id: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_bucket: int = 64,
                 prefill_chunk: Optional[int] = None,
                 mesh=None, top_k: int = 0, top_p: float = 1.0,
                 enable_prefix_caching: bool = False,
                 metrics_registry=None, metrics_ring=None,
                 overlap: bool = False, lookahead: int = 1,
                 packed: bool = True,
                 max_queue_len: Optional[int] = None,
                 max_queued_tokens: Optional[int] = None,
                 quarantine_faults: bool = True,
                 max_consecutive_faults: int = 3,
                 tp_allreduce: str = "fp32",
                 mixed: bool = False,
                 mixed_token_budget: int = 256,
                 mixed_ctx_cap: Optional[int] = None,
                 decode_horizon: int = 1,
                 spec: Optional[SpecConfig] = None,
                 policy: Optional[SchedulerPolicy] = None,
                 tenant_quotas: Optional[TenantQuotas] = None,
                 tracer=None):
        """``mesh`` (an mp>1 device mesh, with ``params`` initialised
        on it and ``cache`` built with the same mesh) serves a
        TENSOR-PARALLEL model: the decode step is one sharded jitted
        shard_map program (make_paged_decode_step_tp); prefill rides
        GSPMD over the same sharded params.  A model wider than one
        chip serves through the identical engine API — every lane:
        packed admission stays one dispatch per wave (the packed
        program composes through the same shard_map seam), the
        dispatch-ahead overlap pipeline wraps the sharded step, and a
        host page tier offloads the sharded pool per shard.

        ``tp_allreduce="int8"`` (TP engines only, opt-in) swaps each
        decode layer's two output all-reduces for a quantized ring
        reduce-scatter/all-gather (int8 wire + per-block f32 scales,
        EQuARX-style — ~25-31% of a 4-byte fp32 wire's bytes; vs a
        bf16 compute dtype's 2-byte wire the saving halves) whose
        ppermute hops are chunk-interleaved with the producing
        matmuls (T3/FLUX latency hiding).  Greedy outputs then carry
        quantization noise: held to a pinned statistical bar, not
        token-exactness.  Prefill and the speculative verify always
        reduce exact.

        ``overlap=True`` switches the decode hot loop to the
        DISPATCH-AHEAD pipeline: loop state (next token, lens, active
        mask, remaining budget, per-slot done) lives on the device and
        advances functionally inside the jitted step; step k's
        on-device outputs feed step k+1's dispatch directly, and the
        host drains tokens/done masks one step behind (double-buffered
        fetch), so admission/streaming/retirement bookkeeping overlaps
        device compute.  Greedy output is token-exact vs the
        synchronous loop; the pipeline flushes at every scheduler
        mutation point (admission, preemption, stop-sequence
        retirement).  ``lookahead`` is the number of dispatches the
        device may run ahead of the host (1 = classic double
        buffering).

        ``decode_horizon=H`` (H > 1) fuses H micro-steps of the
        decode loop into ONE jitted ``lax.scan`` program per tick
        (sync and overlap lanes alike): one dispatch, one blocking
        fetch and one host-bookkeeping pass per H tokens.  Tables
        stay constant across the block (the tick pre-claims H tokens
        of pages per slot in one batched claim), per-slot eos/budget
        stops fold on-device so rows halt mid-horizon, and
        host-detected stop sequences trim the device's
        over-generated tail (at most H-1 tokens, counted in
        ``horizon_trimmed_tokens``) before emission — streams stay
        token-exact vs ``decode_horizon=1``.  Helps
        dispatch-overhead-bound regimes; hurts under aggressive
        stop-sequence traffic (trim waste).  Does not compose with
        ``mixed=True`` (raises — the mixed tick re-plans its prefill
        stream on the host between dispatches); speculative engines
        reject it in favour of their own gamma cadence.

        ``packed=True`` (default) admits through the PACKED VARLEN
        prefill lane: every waiting context — any length mix,
        prefix-cache suffixes included — packs into one ``[T_bucket]``
        token stream with segment ids and prefills as exactly ONE
        jitted segmented-flash program per admission wave (compile
        count O(log total-token-buckets), padded-token waste only the
        sub-bucket remainder), single-device and TP alike;
        ``packed=False`` forces the batched/chunked lanes
        everywhere."""
        self.cfg = cfg
        self.params = params
        self.cache = cache
        self.mesh = mesh
        # per-request distributed tracing (observability/tracing.py):
        # with a Tracer attached, submit() mints a TraceContext per
        # request (trace id = rid); fleet routers / disagg
        # coordinators pass their own fleet-level context instead and
        # this attribute stays unused.  Phase clocks accrue either way
        # — they are plain host floats on the Request.
        self.tracer = tracer
        self.eos_id = eos_id
        self.temperature = temperature
        self.top_k, self.top_p = top_k, top_p
        # bucket lengths must be page-aligned or the page write would
        # slice/reshape inconsistently (loud here, confusing there)
        page = cache.page
        self.prefill_bucket = ((max(prefill_bucket, page) + page - 1)
                               // page) * page
        # prompts longer than prefill_chunk prefill in CHUNKS (bounded
        # per-dispatch cost; one compile serves every chunk index)
        if prefill_chunk is not None:
            prefill_chunk = ((max(prefill_chunk, page) + page - 1)
                             // page) * page
        self.prefill_chunk = prefill_chunk
        # PREFIX CACHING: admissions share cached full pages of equal
        # prompt prefixes and prefill only the suffix (through the
        # prefill-with-history program); every admission routes through
        # the chunked path so rows can start at a reused offset
        self.enable_prefix_caching = enable_prefix_caching
        # program dispatches for admission, observable for the
        # sublinearity contract (K same-bucket admits = ONE dispatch;
        # packed lane: ANY-mix wave = ONE dispatch)
        self.prefill_calls = 0
        # PACKED VARLEN admission — every mesh: the TP lane composes
        # the packed program through the _build_tp_inner shard_map
        # seam (_prefill_packed_tp), so an admission wave is ONE
        # dispatch single-device and sharded alike
        self._packed = bool(packed)
        self._tp = mesh is not None and mesh.shape.get("mp", 1) > 1
        # -- TP collectives (tp_allreduce="int8": quantized ring
        # RS/AG on the decode layers' output reductions) -------------
        if tp_allreduce not in ("fp32", "int8"):
            raise ValueError("tp_allreduce must be 'fp32' or 'int8', "
                             f"got {tp_allreduce!r}")
        if tp_allreduce == "int8" and not self._tp:
            raise ValueError(
                "tp_allreduce='int8' quantizes the TP decode "
                "collectives — it needs an mp>1 mesh (single-device "
                "engines have no collectives to quantize)")
        self.tp_allreduce = tp_allreduce
        # analytic bytes one device sends in the per-layer output
        # collectives of ONE decode dispatch (the
        # tp_allreduce_bytes_total counter's increment; 0 off-mesh)
        self._tp_bytes_step = tp_collective_bytes_per_step(
            cfg, mesh.shape["mp"], tp_allreduce,
            cache.tables.shape[0]) if self._tp else 0
        self.tp_allreduce_bytes = 0
        # -- MIXED prefill+decode steps (Sarathi-style chunked-prefill
        # piggybacking): mixed=True fuses up to mixed_token_budget
        # prefill-stream tokens into every decode dispatch, so a
        # colocated engine never stops decoding to admit (the
        # admission stall serving_disagg_ab measures is deleted
        # without a second engine).  The budget is page-aligned;
        # budget 0 (or an idle batch) degrades to the sequential
        # admission lanes, as does any context longer than
        # mixed_ctx_cap (the wave shape no longer fits the mixed
        # stream; counted in mixed_degraded).
        budget_pages = 0
        if mixed and int(mixed_token_budget) > 0:
            budget_pages = -(-int(mixed_token_budget) // page)
        self.mixed_token_budget = budget_pages * page
        self._mixed = bool(mixed) and budget_pages > 0
        cap = (mixed_ctx_cap if mixed_ctx_cap is not None
               else 4 * max(self.mixed_token_budget,
                            self.prefill_bucket))
        self.mixed_ctx_cap = max(int(cap) // page, 1) * page
        self._mixed_pref: Dict[int, dict] = {}    # slot -> chunk state
        self.mixed_ticks = 0              # dispatches that piggybacked
        self.mixed_prefill_tokens = 0     # fresh tokens piggybacked
        self.mixed_degraded = 0           # shape-forced sequential waves
        self._step_mixed = None
        if self._mixed:
            self._step_mixed = make_mixed_step(
                cfg, temperature, kv_quant=cache.kv_quant,
                top_k=top_k, top_p=top_p, mesh=mesh,
                tp_allreduce=tp_allreduce)
        # -- MULTI-TOKEN DECODE HORIZON (decode_horizon=H > 1): every
        # decode tick is ONE jitted H-micro-step lax.scan program —
        # one dispatch, one blocking fetch and one host-bookkeeping
        # pass per H tokens instead of per token.  Tables stay
        # constant across the horizon (H-token page pre-claim per
        # slot); per-slot eos/budget stops fold on-device; host-only
        # stop sequences trim the row's over-generated tail at the
        # drain (at most H-1 tokens, counted).
        if int(decode_horizon) < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {decode_horizon}")
        self.decode_horizon = int(decode_horizon)
        if self.decode_horizon > 1 and self._mixed:
            # The real constraint: the mixed tick's admission cadence
            # is host-scheduled BETWEEN dispatches — chunk carving,
            # progressive prefix registration and activation
            # bookkeeping are per-tick host decisions an H-deep
            # on-device scan would have to replay blind (its prefill
            # stream/scatter layout is fixed at dispatch).  The mixed
            # fusion already amortizes dispatch overhead across the
            # prefill budget; run one knob or the other.
            raise ValueError(
                "decode_horizon > 1 does not compose with mixed=True: "
                "the mixed tick re-plans its prefill stream on the "
                "host between consecutive dispatches, which an "
                "on-device multi-step scan cannot replay — use "
                "mixed=True (fused admission) OR decode_horizon "
                "(fused decode cadence), not both")
        self._step_multi = None
        if self.decode_horizon > 1:
            self._step_multi = make_paged_decode_step_multi(
                cfg, self.decode_horizon, temperature,
                kv_quant=cache.kv_quant, top_k=top_k, top_p=top_p,
                mesh=mesh, tp_allreduce=tp_allreduce)
        # host-detected stop sequences fire mid-horizon: tokens the
        # device over-generated past the stop point are discarded
        # before emission (streams stay token-exact vs horizon=1)
        self.horizon_trimmed_tokens = 0
        # padding-waste accounting across ALL prefill lanes: dispatched
        # token slots vs slots that carried no real context token
        # (bucket/page padding) — the benchmark's counters read these
        self.prefill_token_slots = 0
        self.prefill_padded_tokens = 0
        # serving counters (surfaced by GenerationServer /health)
        self.decode_steps = 0
        self.tokens_generated = 0
        self.preemptions = 0
        self.requests_finished = 0
        self.decode_wall_s = 0.0          # decode dispatch wall accum
        # -- fault tolerance (docs/FAULT_TOLERANCE.md) ----------------
        # bounded admission queue: submit() past either bound raises
        # QueueFullError (backpressure — the HTTP front answers 429)
        # instead of growing host memory without limit
        self.max_queue_len = max_queue_len
        self.max_queued_tokens = max_queued_tokens
        # -- QoS (SLO guardrails, docs/FAULT_TOLERANCE.md) ------------
        # scheduler policy seam: class-ordered admission, class-aware
        # preemption victims and overload shedding; tenant token-rate
        # buckets charged at submit().  _has_priorities stays False on
        # all-default traffic so the legacy FIFO path pays zero cost.
        self.policy = policy if policy is not None else SchedulerPolicy()
        self.quotas = tenant_quotas
        self._has_priorities = False
        self.requests_degraded = 0
        self.quota_rejected = 0
        # per-step exception handling: quarantine the poisoned wave
        # (retire its slots with an error done-message, stay alive) up
        # to max_consecutive_faults faults in a row, then escalate —
        # a persistent fault means the engine itself is broken and
        # only an EngineSupervisor rebuild can help
        self.quarantine_faults = bool(quarantine_faults)
        self.max_consecutive_faults = int(max_consecutive_faults)
        self._consecutive_faults = 0
        self._cancelled: set = set()      # rids awaiting cancellation
        self._admitting: List[Request] = []   # popped, not yet active
        self._has_deadlines = False       # any deadline ever submitted
        self._now = time.monotonic        # seam: tests pin the clock
        self.requests_cancelled = 0
        self.requests_expired = 0
        self.requests_rejected = 0
        self.requests_faulted = 0
        self.step_faults = 0              # quarantined wave faults
        self.last_fault: Optional[str] = None
        # -- two-tier KV cache (host-RAM page offload) ----------------
        # with a host tier attached to the cache, preemption SWAPS the
        # victim's pages to host RAM instead of releasing them, and
        # re-admission is a page restore + table rebuild with ZERO
        # prefill tokens — guarded by the bytes-vs-FLOPs cost model
        # below (recompute remains the fallback: host tier full, or a
        # context cheap enough that re-prefilling beats the DMA).
        # TP meshes included: the host tier stages per shard
        # (kv_offload.py) and restores through the sharded scatter.
        self._offload = cache.host is not None
        self._swap_handles: Dict[int, int] = {}   # rid -> swap handle
        self.prefill_tokens_avoided = 0
        self.resumes_swapped = 0
        self.resumes_recompute = 0
        self.resume_wall_s = 0.0          # resume-admission wall accum
        self.resume_events = 0
        # cost-model knobs (overridable): assumed swap DMA bandwidth
        # and chip compute rate; None chip_flops = platform default
        # (v5e bf16 peak on TPU, a conservative CPU figure otherwise)
        self.offload_swap_gbps = 10.0
        self.offload_chip_flops = None
        self._n_params = None             # lazily counted for FLOPs
        self.B = cache.tables.shape[0]
        self._free_slots = list(range(self.B))
        self._queue: deque = deque()
        self._active: Dict[int, Request] = {}       # slot -> request
        self._finished: List[Request] = []
        self._next_rid = 0
        self._admit_seq = 0
        self._stream: List = []     # (rid, token) in emission order
        self._key = jax.random.PRNGKey(seed)
        # OBSERVABILITY (docs/OBSERVABILITY.md): host-side instruments
        # only — recorded from values already materialized on host,
        # zero new jitted programs.  Default is a registry private to
        # this engine (exact per-engine /metrics) and a private event
        # ring; pass a shared MetricsRegistry / EventRing (e.g.
        # observability.default_registry() / default_ring()) to
        # aggregate, or metrics_registry=False to disable
        # instrumentation entirely.
        if metrics_registry is False:
            self.metrics = None
            cache.metrics = None     # a reused cache must not keep
            #                          feeding a prior engine's counters
        else:
            self.metrics = EngineMetrics(
                metrics_registry if metrics_registry is not None
                else MetricsRegistry(), ring=metrics_ring)
            bind_engine_gauges(self.metrics, self)
            cache.metrics = self.metrics
        if mesh is not None and mesh.shape.get("mp", 1) > 1:
            self._step = make_paged_decode_step_tp(
                cfg, mesh, temperature, kv_quant=cache.kv_quant,
                top_k=top_k, top_p=top_p, tp_allreduce=tp_allreduce)
        else:
            self._step = make_paged_decode_step(
                cfg, temperature, kv_quant=cache.kv_quant,
                top_k=top_k, top_p=top_p)
        # -- SPECULATIVE LANE (spec=SpecConfig(...)) ------------------
        # every decode round is ONE fused draft+verify dispatch
        # (make_spec_step) committing up to gamma+1 tokens per row —
        # token-exact vs plain greedy (exact verification), one
        # _fetch per round, sync and overlap cadence alike.
        self._spec = spec
        if spec is not None:
            if temperature != 0.0:
                raise ValueError(
                    "speculative serving is greedy-only (exact "
                    "verification); temperature must be 0")
            if self._mixed:
                # the real constraint: the mixed tick re-plans its
                # prefill stream on the host between dispatches,
                # which the fused draft+verify scan cannot replay —
                # the same reason decode_horizon rejects mixed
                raise ValueError(
                    "spec does not compose with mixed=True: the "
                    "mixed tick re-plans its prefill stream on the "
                    "host between consecutive dispatches, which the "
                    "fused draft+verify program cannot replay — use "
                    "mixed=True (fused admission) OR spec (fused "
                    "speculative decode), not both")
            if self.decode_horizon > 1:
                # the real constraint: both knobs are the SAME fused
                # multi-token-program pattern over the chained loop
                # state — a speculative round already advances up to
                # gamma+1 tokens per dispatch, so stacking an H-deep
                # scan of rounds multiplies the worst-case page
                # pre-claim (H*(gamma+1)) and the stop-sequence trim
                # window for no additional dispatch amortization
                raise ValueError(
                    "decode_horizon > 1 does not compose with spec: "
                    "a speculative round IS the multi-token fused "
                    "program (up to gamma+1 committed tokens per "
                    "dispatch) — tune spec.gamma instead of stacking "
                    "a second horizon scan on top")
            if spec.source not in ("draft", "prompt_lookup"):
                raise ValueError(
                    "SpecConfig.source must be 'draft' or "
                    f"'prompt_lookup', got {spec.source!r}")
            if int(spec.gamma) < 1:
                raise ValueError(
                    f"spec.gamma must be >= 1, got {spec.gamma}")
            if spec.source == "draft":
                if spec.draft_cfg is None or spec.draft_params is None \
                        or spec.draft_cache is None:
                    raise ValueError(
                        "SpecConfig(source='draft') needs draft_cfg, "
                        "draft_params and draft_cache (use "
                        "source='prompt_lookup' for model-free "
                        "n-gram drafting)")
                if spec.draft_cache.tables.shape[0] != self.B:
                    raise ValueError(
                        "draft_cache batch "
                        f"{spec.draft_cache.tables.shape[0]} != "
                        f"target cache batch {self.B}")
                if self._tp and spec.draft_cache.mesh != mesh:
                    # the one REAL constraint of TP speculative
                    # serving: draft and verify run the same mesh, so
                    # the draft pool must be kv-head-sharded over it
                    # exactly like the target pool (a single-device
                    # draft pool would make every fused dispatch
                    # reshard the pools across chips)
                    raise ValueError(
                        "TP speculative serving runs draft and "
                        "verify on the SAME mesh: build the draft "
                        "PagedKVCache with mesh=<the engine's mesh> "
                        "(and init draft_params on it).  Workaround "
                        "if the draft model cannot shard (e.g. "
                        "indivisible heads): serve with "
                        "SpecConfig(source='prompt_lookup') — "
                        "model-free drafting needs no draft pool — "
                        "or through the plain "
                        "ContinuousBatchingEngine(mesh=...) without "
                        "a draft.")
            self.gamma = int(spec.gamma)
            self.adaptive_gamma = bool(spec.adaptive_gamma)
            self.max_gamma = max(int(spec.max_gamma), self.gamma)
            self._accept_ema = float(self.gamma)
            self.spec_rounds = 0
            self.spec_accepted = 0
            self.spec_drafted = 0      # draft tokens proposed
            self._spec_dcfg = spec.draft_cfg
            self._spec_dparams = spec.draft_params
            self._spec_dcache = spec.draft_cache   # None for lookup
            self._spec_on = np.zeros((self.B,), bool)
            self._prev_tok = np.zeros((self.B,), np.int64)
            self._spec_seq: Dict[int, list] = {}   # lookup history
            self._spec_ngrams: Dict[int, dict] = {}
            self._dev_dtables_version = -1
            if self._tp:
                # analytic per-round collective bytes: C verify
                # tokens reduce exact-fp, C draft micro-steps reduce
                # in the engine's tp_allreduce mode (int8 drafts only
                # cost acceptance, never correctness)
                mp_ = mesh.shape["mp"]
                self._tp_bytes_spec_verify = \
                    tp_collective_bytes_per_step(
                        cfg, mp_, "fp32", self.B)
                self._tp_bytes_spec_draft = \
                    tp_collective_bytes_per_step(
                        spec.draft_cfg, mp_, tp_allreduce, self.B) \
                    if spec.source == "draft" else 0
            if self.metrics is not None:
                self.metrics.spec_gamma.set(self.gamma)
        self._next_tok = np.zeros((self.B,), np.int64)
        self._remaining = np.zeros((self.B,), np.int64)
        # incremental ACTIVE-SLOT mask: maintained at admit / retire /
        # preempt — the decode hot loop must never rebuild it per token
        self._active_mask = np.zeros((self.B,), np.int32)
        # -- dispatch-ahead pipeline (overlap=True) ---------------------
        self.overlap = bool(overlap)
        self.lookahead = max(1, int(lookahead))
        self._step_async = None
        if self.overlap:
            self._step_async = make_paged_decode_step_async(
                cfg, temperature, kv_quant=cache.kv_quant,
                top_k=top_k, top_p=top_p, mesh=mesh,
                tp_allreduce=tp_allreduce)
        self._inflight: List[Dict] = []   # oldest-first undrained steps
        # active mask AT DISPATCH of the oldest undrained step (host
        # attributes drained tokens against it, then chains done masks)
        self._drain_active = np.zeros((self.B,), bool)
        self._dev = None                  # chained device loop state
        self._dev_tables_version = -1
        self._needs_flush = False
        self._eos_dev = jnp.asarray(
            -1 if eos_id is None else int(eos_id), jnp.int32)
        self.pipeline_flushes = 0         # mutation-point drains
        self.host_syncs = 0               # blocking device->host fetches

    # -- client side ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 64,
               stop_sequences=None,
               deadline_s: Optional[float] = None,
               trace=None, spec: Optional[bool] = None,
               priority: str = "normal",
               tenant: Optional[str] = None) -> int:
        """Queue a request.  Oversized requests fail HERE with
        ``ValueError`` — one bad request must never surface mid
        ``step()`` and kill every in-flight generation (a row's
        worst-case footprint is bounded by its table width).  A full
        admission queue (``max_queue_len`` / ``max_queued_tokens``)
        fails here too, with :class:`QueueFullError` carrying a finite
        ``retry_after`` — backpressure, not unbounded memory growth.

        ``stop_sequences``: token-id lists; generation retires as soon
        as the generated tail equals one of them (multi-token stop
        strings — the eos_id generalisation every serving product
        needs; checked on the host, costs nothing compiled).

        ``deadline_s``: seconds from now after which the request is
        EXPIRED — retired at the next flush point whether queued or
        mid-decode, resources freed, surfaced in ``finished()`` with
        ``status == "expired"`` (a request whose client stopped
        waiting must stop burning decode slots).

        ``spec``: per-request speculative toggle — ``True``/``False``
        override the engine ``SpecConfig``'s ``default_on``;
        ``None`` inherits it.  Spec-off rows ride the same fused
        round (their accept window collapses to one plain greedy
        token), so on/off requests mix in one batch with zero extra
        dispatches.  ``spec=True`` on an engine built without
        ``spec=SpecConfig(...)`` raises — the fused draft+verify
        program is compiled at engine construction.

        ``trace``: an externally-minted
        :class:`~paddle_tpu.observability.TraceContext` (fleet
        routers / disagg coordinators propagate their fleet-rid
        trace this way); ``None`` mints one from the engine's own
        ``tracer`` when attached.

        ``priority``: QoS class (``"high"``/``"normal"``/``"low"``).
        The admission queue orders by (class, arrival), preemption
        evicts the lowest class first, and overload sheds class-aware
        — when ``queue_capacity_reason()`` trips, low rejects with
        :class:`QueueFullError`, normal admits DEGRADED (halved
        ``max_new_tokens``, spec off, ``degraded`` flagged in the
        done message) and high admits untouched, both up to
        ``policy.overload_factor`` times the configured bounds.

        ``tenant``: token-rate quota key.  With
        ``tenant_quotas=TenantQuotas(...)`` configured, the request's
        worst-case token cost charges the tenant's bucket here;
        over-budget raises :class:`QuotaExceededError` (a 429 with a
        refill-derived ``Retry-After``).  ``tenant=None`` is
        unmetered.

        Thread safety: ``external-lock`` — NOT internally
        synchronized; safe from non-engine threads only when every
        engine touch serializes behind one shared lock
        (``GenerationServer`` does this with ``_lock``).  The full
        per-API contract lives in ``paddle_tpu/analysis/
        annotations.py`` ``THREAD_SAFETY`` and docs/FAULT_TOLERANCE.md
        (consistency-checked by tests/test_analysis.py); the
        ``lock-discipline`` analysis rule enforces it at the serving
        front."""
        prompt = np.asarray(prompt, np.int64)
        if prompt.size == 0:
            # an empty prompt has no last-position logits to sample a
            # first token from: admitted, it would corrupt page 0 K/V
            # (batched path) or kill the engine thread mid-step —
            # reject HERE so one bad client request costs only itself
            raise ValueError(
                "prompt must contain at least one token (empty "
                "prompts cannot be admitted)")
        # bound by BOTH the row's table width and the whole pool (page
        # 0 is reserved): a request the pool can never hold even alone
        # would wedge the engine — preemption has no victim to free
        row_cap = min(self.cache.pages_max,
                      self.cache.num_pages - 1) * self.cache.page
        worst = len(prompt) + max_new_tokens
        if worst > row_cap:
            raise ValueError(
                f"request needs up to {worst} cache slots "
                f"(prompt {len(prompt)} + max_new_tokens "
                f"{max_new_tokens}) > row capacity {row_cap} "
                f"(min(pages_max {self.cache.pages_max}, usable pages "
                f"{self.cache.num_pages - 1}) x page "
                f"{self.cache.page})")
        stops = None
        if stop_sequences is not None:
            if not isinstance(stop_sequences, (list, tuple)):
                raise ValueError(
                    "stop_sequences must be a list of token-id "
                    f"sequences, got {type(stop_sequences).__name__}")
            stops = []
            for q in stop_sequences:
                if not isinstance(q, (list, tuple, np.ndarray)) \
                        or len(q) == 0:
                    raise ValueError(
                        "each stop sequence must be a NON-EMPTY list "
                        f"of token ids, got {q!r}")
                stops.append([int(t) for t in q])
        if spec and self._spec is None:
            raise ValueError(
                "spec=True needs an engine built with "
                "spec=SpecConfig(...): the fused draft+verify "
                "program is compiled at engine construction")
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {PRIORITIES}, got "
                f"{priority!r}")
        degraded = False
        why = self.queue_capacity_reason(len(prompt))
        if why is not None:
            # CLASS-AWARE SHEDDING: the soft bound tripped.  Low
            # rejects (429 absorbs the burst); normal degrades (halved
            # budget, spec off) and high admits untouched — both only
            # up to the HARD bound (overload_factor x the soft bounds:
            # protecting a class must not mean unbounded host memory).
            # A pure default-class workload (no request ever carried a
            # non-normal priority) keeps the legacy FIFO refusal: the
            # soft bound stays the one clients were tuned against, and
            # degradation only buys anything when there is a class
            # hierarchy to protect.
            if self._has_priorities or priority != "normal":
                verdict = self.policy.shed(priority)
            else:
                verdict = "reject"
            if verdict == "reject":
                self._reject(why)
            hard = self.queue_capacity_reason(
                len(prompt), factor=self.policy.overload_factor)
            if hard is not None:
                self._reject(f"{hard} [hard bound, class "
                             f"{priority!r}]")
            if verdict == "degrade":
                max_new_tokens = max(1, int(max_new_tokens) // 2)
                spec = False if self._spec is not None else spec
                degraded = True
                self.requests_degraded += 1
                if self.metrics is not None:
                    self.metrics.requests_degraded.inc()
                    self.metrics.ring.emit(
                        "request_degraded", reason=why,
                        priority=priority, tenant=tenant,
                        max_new_tokens=int(max_new_tokens))
        if self.quotas is not None:
            # worst-case token cost (prompt + remaining budget), so an
            # aggressive tenant is priced for the capacity it can
            # consume, not just what it happened to generate.  Charged
            # AFTER the shed decision: a rejected request must not
            # erode the tenant's budget, and a degraded one charges
            # its halved budget.
            try:
                self.quotas.charge(
                    tenant, len(prompt) + int(max_new_tokens),
                    now=self._now())
            except QuotaExceededError:
                self.quota_rejected += 1
                if self.metrics is not None:
                    self.metrics.quota_rejected.inc()
                    self.metrics.ring.emit("quota_rejected",
                                           tenant=tenant,
                                           priority=priority)
                raise
        deadline = 0.0
        if deadline_s is not None:
            deadline = self._now() + float(deadline_s)
            self._has_deadlines = True
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens,
                      stop_sequences=stops,
                      t_submit=time.monotonic(),
                      deadline=deadline, spec=spec,
                      priority=priority, tenant=tenant,
                      degraded=degraded)
        if priority != "normal":
            self._has_priorities = True
        # phase accounting starts at the queue; ``trace`` (a
        # TraceContext a fleet router / disagg coordinator minted
        # under ITS rid space) wins over the engine's own tracer
        req.t_phase = req.t_submit
        if trace is None and self.tracer is not None:
            trace = self.tracer.begin_trace(
                str(rid), prompt_len=len(prompt),
                max_new_tokens=int(max_new_tokens))
        req.trace = trace
        self._queue.append(req)
        if self.metrics is not None:
            self.metrics.requests_submitted.inc()
            self.metrics.ring.emit("request_submitted", rid=rid,
                                   prompt_len=len(prompt),
                                   max_new_tokens=max_new_tokens,
                                   priority=priority, tenant=tenant)
        return rid

    def cancel(self, rid: int) -> bool:
        """Mark a queued or active request for cancellation; the
        engine retires it at the next flush point (start of
        ``step()``), freeing its device pages, host-tier swap record,
        and prefix refs through the same seams normal retirement uses
        (``PagedKVCache.audit()`` stays clean).  The request surfaces
        in ``finished()`` with ``status == "cancelled"``.  Returns
        False when the rid is unknown or already finished — cancelling
        a completed request is a harmless no-op.

        Thread safety: ``external-lock`` — like :meth:`submit`, safe
        from HTTP handler threads only behind the serving front's
        shared lock (see ``analysis/annotations.py THREAD_SAFETY``
        and docs/FAULT_TOLERANCE.md)."""
        if any(r.rid == rid for r in self._queue) or \
                any(r.rid == rid for r in self._active.values()) or \
                any(e["req"].rid == rid
                    for e in self._mixed_pref.values()):
            self._cancelled.add(rid)
            return True
        return False

    def queued_tokens(self) -> int:
        """Context tokens of PENDING prefill work: the admission
        queue (preempted requests count their regenerated context
        too) PLUS the not-yet-prefilled remainder of rows parked
        mid-prefill in the mixed lane — they left the queue but their
        prefill is still owed, so the ``max_queued_tokens``
        backpressure bound must keep counting them.

        Thread safety: ``any-thread`` — sums over atomic ``tuple()``
        snapshots of the queue and the parked-row map (one C-level
        copy each under the GIL), so metrics scrape threads read it
        lock-free; a racing submit/step makes the answer at most one
        admission stale, never a ``mutated during iteration`` error.
        Exact when serialized behind the serving front's ``_lock``,
        which is how the backpressure path consults it (see
        ``analysis/annotations.py THREAD_SAFETY``)."""
        parked = getattr(self, "_mixed_pref", None)
        owed = sum(len(e["ctx"]) - e["pos"]
                   for e in tuple(parked.values())) if parked else 0
        return owed + sum(len(r.prompt) + len(r.generated)
                          for r in tuple(self._queue))

    def queue_capacity_reason(
            self, prompt_len: int = 0,
            factor: float = 1.0,
            priority: Optional[str] = None) -> Optional[str]:
        """Why the bounded admission queue would refuse a submission
        right now, or ``None`` while capacity remains — the ONE
        predicate behind ``submit()``'s backpressure, the serving
        front's ``/health/ready``, and the fleet router's
        ``accepting()``, so readiness can never disagree with what
        ``submit()`` actually accepts.  ``prompt_len=0`` asks the
        readiness form: would a minimal (1-token) prompt risk
        refusal.

        ``factor`` scales both bounds (the class-aware shed path asks
        the HARD bound with ``policy.overload_factor``); ``priority``
        asks the class-aware form directly — "would ``submit()``
        REJECT this class right now" (None for a protected/degraded
        class while the soft bound trips but the hard bound holds) —
        which is what the fleet router's placement probe needs to stay
        side-effect-free without guessing the shed verdict.

        Thread safety: ``external-lock``, like
        :meth:`submit` (see ``analysis/annotations.py
        THREAD_SAFETY``)."""
        if priority is not None and \
                (self._has_priorities or priority != "normal") and \
                self.policy.shed(priority) != "reject":
            # mirror submit(): a pure default-class workload keeps the
            # legacy soft-bound refusal, so the probe must not promise
            # hard-bound capacity submit() would then reject
            factor = max(factor, self.policy.overload_factor)
        if self.max_queue_len is not None:
            bound = int(self.max_queue_len * factor)
            if len(self._queue) >= bound:
                return (f"admission queue full: {len(self._queue)} "
                        f"waiting >= max_queue_len {bound}")
        if self.max_queued_tokens is not None:
            bound = int(self.max_queued_tokens * factor)
            waiting = self.queued_tokens()
            need = max(int(prompt_len), 1)
            if waiting + need > bound:
                return (f"queued tokens {waiting} + prompt {need} "
                        f"> max_queued_tokens {bound}")
        return None

    def queued_by_class(self) -> Dict[str, int]:
        """Waiting requests per priority class (mixed-lane parked rows
        included — their prefill is still owed).  Thread safety:
        ``any-thread``, like :meth:`queued_tokens` — iterates atomic
        ``tuple()`` snapshots, so the per-class gauges scrape
        lock-free."""
        out = {p: 0 for p in PRIORITIES}
        for r in tuple(self._queue):
            out[r.priority if r.priority in out else "normal"] += 1
        parked = getattr(self, "_mixed_pref", None)
        if parked:
            for e in tuple(parked.values()):
                p = e["req"].priority
                out[p if p in out else "normal"] += 1
        return out

    def retry_after_s(self) -> float:
        """Finite back-off hint for a rejected client: the queue's
        waiting tokens priced at the engine's observed decode
        throughput, clamped to [0.1, 60] s (a cold engine answers 1 s
        — a finite guess beats an honest infinity)."""
        if self.decode_wall_s > 0 and self.tokens_generated > 0:
            rate = self.tokens_generated / self.decode_wall_s
            est = self.queued_tokens() / max(rate, 1e-6)
        else:
            est = 1.0
        return float(min(max(est, 0.1), 60.0))

    def _reject(self, why: str) -> None:
        self.requests_rejected += 1
        if self.metrics is not None:
            self.metrics.requests_rejected.inc()
            self.metrics.ring.emit("request_rejected", reason=why)
        raise QueueFullError(why, retry_after=self.retry_after_s())

    def finished(self) -> List[Request]:
        out, self._finished = self._finished, []
        return out

    def drain_stream(self) -> List:
        """Per-token STREAMING: all ``(rid, token)`` pairs emitted since
        the last drain, in emission order.  Tokens appear here the step
        they are produced — callers forward them to clients without
        waiting for the request to finish."""
        out, self._stream = self._stream, []
        return out

    def has_work(self) -> bool:
        return bool(self._queue or self._active or self._mixed_pref)

    # -- engine side ------------------------------------------------------
    @staticmethod
    def _ctx_of(req: Request) -> np.ndarray:
        """The tokens a (re-)prefill must cache: the prompt, plus — for
        a PREEMPTED request — everything generated except the last
        token (generated[-1] is the not-yet-fed next input)."""
        if req.generated:
            return np.concatenate(
                [req.prompt, np.asarray(req.generated[:-1], np.int64)])
        return req.prompt

    def _release_slot(self, slot: int) -> None:
        """Free a slot's cache rows, main and auxiliary."""
        self.cache.release_row(slot)
        self._release_aux(slot)

    def _release_aux(self, slot: int) -> None:
        """Release a slot's auxiliary state: the speculative lane's
        draft cache row and prompt-lookup history.  Split from
        :meth:`_release_slot` because a swap-out preemption keeps the
        MAIN cache row (parked in the host tier) while auxiliary state
        is always rebuilt at re-admission."""
        if self._spec is None:
            return
        if self._spec_dcache is not None and self._spec_on[slot]:
            self._spec_dcache.release_row(slot)
        self._spec_on[slot] = False
        self._spec_seq.pop(slot, None)
        self._spec_ngrams.pop(slot, None)

    def _hit_stop(self, req: Request, t: int) -> bool:
        """eos or a completed stop sequence at the generated tail."""
        if self.eos_id is not None and t == self.eos_id:
            return True
        for seq in req.stop_sequences or ():
            if len(req.generated) >= len(seq) and \
                    req.generated[-len(seq):] == seq:
                return True
        return False

    def _note_first_token(self, req: Request) -> None:
        """TTFT sample, once per request (the first token lands at
        admission; preemption resumes must not re-observe)."""
        if req.t_first_token == 0.0 and req.generated:
            req.t_first_token = time.monotonic()
            if self.metrics is not None:
                self.metrics.ttft.observe(
                    req.t_first_token - req.t_submit,
                    exemplar=_tid(req))

    def _spec_admit(self, req: Request, slot: int, tok: int) -> None:
        """Speculative admission tail: resolve the row's on/off
        toggle, seed the prev-token mirror, and build the row's draft
        source — a dense draft-model prefill of the committed context
        (``source='draft'``) or the per-request n-gram table
        (``source='prompt_lookup'``).  Runs for fresh admissions,
        recompute resumes and swap-ins alike (every lane ends in
        :meth:`_finish_admit`)."""
        on = req.spec if req.spec is not None \
            else self._spec.default_on
        self._spec_on[slot] = bool(on)
        ctx = self._ctx_of(req)
        self._prev_tok[slot] = int(ctx[-1])
        if not on:
            return
        if self._spec.source == "draft":
            dcache = self._spec_dcache
            L = len(ctx)
            # analysis: ignore[claim-lifecycle] reason=draft-row transfer: a draft prefill fault quarantines, and _retire_abnormal releases the slot through _release_slot -> _release_aux -> dcache.release_row (audit-clean)
            dcache.alloc_row(slot, L)
            page = dcache.page
            Lp = ((L + page - 1) // page) * page
            padded = np.zeros((1, Lp), np.int64)
            padded[0, :L] = ctx
            x, ks, vs = _prefill(self._spec_dcfg)(
                self._spec_dparams, jnp.asarray(padded))
            dcache.write_row_pages(slot, ks[:, 0], vs[:, 0], L)
        else:
            seq = [int(t) for t in ctx] + [int(tok)]
            self._spec_seq[slot] = seq
            n = self._spec.ngram
            tab: dict = {}
            # first occurrence wins (setdefault): a proposal should
            # continue the EARLIEST prior match, not the tail itself
            for i in range(n, len(seq)):
                tab.setdefault(tuple(seq[i - n:i]), i)
            self._spec_ngrams[slot] = tab

    def _finish_admit(self, req: Request, slot: int, tok: int) -> None:
        """Shared bookkeeping tail of every admission path."""
        if self._spec is not None:
            self._spec_admit(req, slot, tok)
        if req.t_admit == 0.0:
            req.t_admit = time.monotonic()
            if self.metrics is not None:
                self.metrics.queue_wait.observe(
                    req.t_admit - req.t_submit, exemplar=_tid(req))
        # phase-clock transition: whatever came before (queued /
        # prefill wave / swapped restore / handoff restore) closes
        # here and decoding begins
        advance_phase(req, "decode_active")
        self._note_first_token(req)
        req.slot = slot
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        self._active[slot] = req
        self._next_tok[slot] = tok
        self._remaining[slot] = req.max_new_tokens - len(req.generated)
        self._active_mask[slot] = 1
        if self._hit_stop(req, tok) or self._remaining[slot] <= 0:
            self._retire(slot)

    def _admit_batch(self, group: List) -> None:
        """BATCHED admission: K same-bucket requests prefill as ONE
        jitted program of shape [K_pow2, bucket] — admission cost is
        sublinear in arrivals (one dispatch instead of K).  A fresh
        request samples its first token from its last real position's
        logits (batched); a preempted one resumes at its saved token
        (recompute-style preemption, the vLLM scheduler's recovery
        path).  ``group`` carries (request, context) pairs — the
        context was already built during reservation."""
        reqs = [r for r, _ in group]
        ctxs = [c for _, c in group]
        K = len(reqs)
        Ls = [len(c) for c in ctxs]
        Lp = ((max(Ls) + self.prefill_bucket - 1) //
              self.prefill_bucket) * self.prefill_bucket
        # pad the batch to a power of two: compile count stays
        # O(log B x buckets), padding rows are ignored
        Kp = 1 << (K - 1).bit_length()
        slots = []
        for req, ctx, L in zip(reqs, ctxs, Ls):
            slot = self._free_slots.pop()
            # analysis: ignore[claim-lifecycle] reason=admission-phase fault transfer: the slot left _free_slots, so _quarantine reclaims its rows via release_row (audit-clean, pinned by test_serving_faults)
            self.cache.alloc_row(slot, L)
            slots.append(slot)
        padded = np.zeros((Kp, Lp), np.int64)
        for i, ctx in enumerate(ctxs):
            padded[i, :Ls[i]] = ctx
        faults.fire("prefill_dispatch")
        x, ks, vs = _prefill(self.cfg)(self.params, jnp.asarray(padded))
        self.prefill_calls += 1
        waste = Kp * Lp - sum(Ls)
        self.prefill_token_slots += Kp * Lp
        self.prefill_padded_tokens += waste
        if self.metrics is not None:
            self.metrics.prefill_dispatches.inc()
            self.metrics.prefill_padded_tokens.inc(waste)
        # one coalesced scatter dispatch for the whole group (the same
        # write_pages_batch economy the packed lane gets)
        self.cache.write_pages_batch(
            [(slot, ks[:, i], vs[:, i], L, 0)
             for i, (slot, L) in enumerate(zip(slots, Ls))])
        toks = None
        if any(not r.generated for r in reqs):
            # batched first tokens from each row's LAST REAL position —
            # skipped for an all-resume group (their next token is
            # saved; sampling would also burn a PRNG split for nothing)
            last = jnp.asarray(np.asarray(Ls, np.int64) - 1)
            h = _rms_norm(x[jnp.arange(K), last],
                          self.params["final_norm"],
                          self.cfg.rms_norm_eps)
            logits = _mm(h, self.params["lm_head"],
                         self.cfg.dtype).astype(jnp.float32)
            self._key, sub = jax.random.split(self._key)
            # sanctioned drain, kept OFF the _fetch seam: pipeline-
            # depth accounting (one _fetch per drained decode step) is
            # pinned by the overlap tests
            # analysis: ignore[sync-in-hot-path] reason=admission first-token fetch; the pipeline is flushed before any _admit_* runs
            toks = np.asarray(_pick_token(logits, self.temperature,
                                          sub, self.top_k,
                                          self.top_p))
        for i, (req, slot) in enumerate(zip(reqs, slots)):
            if req.generated:                    # resume after preempt
                tok = req.generated[-1]
            else:
                tok = int(toks[i])
                req.generated.append(tok)
                self._stream.append((req.rid, tok))
            self._finish_admit(req, slot, tok)

    def _admit_chunked(self, req: Request, ctx: np.ndarray) -> None:
        """CHUNKED admission for prompts longer than ``prefill_chunk``
        (and, with prefix caching, for EVERY admission — a reused
        prefix means the row starts mid-context): the context advances
        chunk by chunk through the prefill-with-history program
        (attends cached pages + causal within chunk) — per-dispatch
        cost is bounded by the chunk, not the prompt, and cached
        prefix pages are never recomputed."""
        L = len(ctx)
        chunk = self.prefill_chunk or self.prefill_bucket
        page = self.cache.page
        slot = self._free_slots.pop()
        if self.enable_prefix_caching:
            # analysis: ignore[claim-lifecycle] reason=admission-phase fault transfer: the slot left _free_slots, so _quarantine reclaims its rows via release_row (audit-clean, pinned by test_serving_faults)
            start = self.cache.alloc_row_prefix(slot, ctx)
        else:
            # analysis: ignore[claim-lifecycle] reason=admission-phase fault transfer: the slot left _free_slots, so _quarantine reclaims its rows via release_row (audit-clean, pinned by test_serving_faults)
            self.cache.alloc_row(slot, L)
            start = 0
        q8 = self.cache.kv_quant == "int8"
        run = _prefill_chunk(self.cfg, q8)
        dummy = jnp.zeros((1,), jnp.float32)
        x = None
        pos = start
        nchunks = 0
        while pos < L:
            C_real = min(chunk, L - pos)
            toks = np.zeros((1, chunk), np.int64)
            toks[0, :C_real] = ctx[pos:pos + C_real]
            table = jnp.asarray(self.cache.tables[slot].copy())
            faults.fire("prefill_dispatch")
            x, ks, vs = run(
                self.params, jnp.asarray(toks), self.cache.kpool,
                self.cache.vpool,
                self.cache.kscale if q8 else dummy,
                self.cache.vscale if q8 else dummy,
                table, np.int32(pos))
            self.prefill_calls += 1
            nchunks += 1
            self.cache.write_row_pages(slot, ks, vs, C_real,
                                       first_page=pos // page)
            last_real = C_real
            pos += C_real
        waste = nchunks * chunk - (L - start)
        self.prefill_token_slots += nchunks * chunk
        self.prefill_padded_tokens += waste
        if self.metrics is not None and nchunks:
            self.metrics.prefill_dispatches.inc(nchunks)
            self.metrics.prefill_chunks.inc(nchunks)
            self.metrics.prefill_padded_tokens.inc(waste)
        if req.generated:                        # resume after preempt
            tok = req.generated[-1]
        else:
            h = _rms_norm(x[0, last_real - 1],
                          self.params["final_norm"],
                          self.cfg.rms_norm_eps)
            logits = _mm(h, self.params["lm_head"],
                         self.cfg.dtype).astype(jnp.float32)
            self._key, sub = jax.random.split(self._key)
            # analysis: ignore[sync-in-hot-path] reason=admission first-token fetch; the pipeline is flushed before any _admit_* runs
            tok = int(_pick_token(logits[None], self.temperature,
                                  sub, self.top_k, self.top_p)[0])
            req.generated.append(tok)
            self._stream.append((req.rid, tok))
        if self.enable_prefix_caching:
            # cache the PROMPT's full pages for future admissions
            # (generated context stays private — chains over sampled
            # tokens would pollute the index)
            self.cache.register_prefix(slot, req.prompt)
        self._finish_admit(req, slot, tok)

    def _packed_bucket(self, T: int) -> int:
        """Round a packed-stream length up to a power-of-two number of
        prefill buckets: compile count stays O(log total-token-buckets)
        and padded-token waste is bounded by the sub-bucket remainder
        of the LAST doubling, not per-request padding."""
        n = -(-T // self.prefill_bucket)
        return self.prefill_bucket * (1 << (n - 1).bit_length())

    def _admit_packed(self, group: List) -> None:
        """PACKED VARLEN admission: every waiting context — mixed
        lengths, prefix-cache suffixes, long prompts, preemption
        resumes — packs into ONE ``[T_bucket]`` token stream with
        segment ids and prefills as exactly ONE jitted segmented-flash
        program (``_prefill_packed``), replacing the K per-bucket
        dense dispatches of :meth:`_admit_batch` and the per-chunk
        loop of :meth:`_admit_chunked`.  Per-segment K/V scatter into
        each request's pages lands at page-aligned offsets (suffixes
        start on a page boundary because reused prefixes are whole
        pages); int8 caches quantise on write.  Each segment's LAST
        real position's hidden state feeds one shared logits tail for
        the first sampled token — same eager tail as the batched path,
        so greedy outputs are token-exact across lanes."""
        page = self.cache.page
        K = len(group)
        plan = []        # (req, ctx, slot, start, s_real, Wp, off)
        wave_src: Dict[int, int] = {}   # page id -> stream index of
        #   its first token, for pages WRITTEN by this wave (a same-
        #   wave prefix sharer must read them from the stream — their
        #   pool copy lands only after the program returns)
        T = 0
        for req, ctx in group:
            slot = self._free_slots.pop()
            L = len(ctx)
            if self.enable_prefix_caching:
                # analysis: ignore[claim-lifecycle] reason=admission-phase fault transfer: the slot left _free_slots, so _quarantine reclaims its rows via release_row (audit-clean, pinned by test_serving_faults)
                start = self.cache.alloc_row_prefix(slot, ctx)
            else:
                # analysis: ignore[claim-lifecycle] reason=admission-phase fault transfer: the slot left _free_slots, so _quarantine reclaims its rows via release_row (audit-clean, pinned by test_serving_faults)
                self.cache.alloc_row(slot, L)
                start = 0
            s_real = L - start
            Wp = -(-s_real // page) * page   # page-pad the suffix so
            #   write_row_pages sees whole pages
            off = T
            T += start + Wp
            plan.append((req, ctx, slot, start, s_real, Wp, off))
            for j in range(start // page, (start + Wp) // page):
                wave_src[int(self.cache.tables[slot, j])] = off + j * page
            if self.enable_prefix_caching:
                # register BEFORE later same-wave allocs so equal
                # prefixes share within one wave (index entries are
                # valid immediately; page CONTENT lands with this
                # wave's write — same-wave readers resolve in-stream)
                self.cache.register_prefix(slot, req.prompt)
        Tb = self._packed_bucket(T)
        toks = np.zeros((1, Tb), np.int64)
        seg = np.full((1, Tb), K, np.int32)      # sentinel tail id
        pos = np.zeros((1, Tb), np.int32)
        hist_page = np.zeros((Tb,), np.int32)
        hist_slot = np.zeros((Tb,), np.int32)
        pool_hist = np.zeros((Tb,), bool)
        stream_src = np.zeros((Tb,), np.int32)
        stream_hist = np.zeros((Tb,), bool)
        for i, (req, ctx, slot, start, s_real, Wp, off) in \
                enumerate(plan):
            W = start + Wp
            seg[0, off:off + W] = i
            pos[0, off:off + W] = np.arange(W)
            toks[0, off + start:off + start + s_real] = ctx[start:]
            for j in range(start // page):       # reused prefix pages
                pid = int(self.cache.tables[slot, j])
                a = off + j * page
                src = wave_src.get(pid)
                if src is not None and src < off:
                    stream_src[a:a + page] = src + np.arange(page)
                    stream_hist[a:a + page] = True
                else:
                    hist_page[a:a + page] = pid
                    hist_slot[a:a + page] = np.arange(page)
                    pool_hist[a:a + page] = True
        q8 = self.cache.kv_quant == "int8"
        if self._tp:
            # same stream layout, composed through the shard_map
            # seam: the wave stays ONE dispatch on the mesh
            run = _prefill_packed_tp(self.cfg, self.mesh, q8,
                                     self.enable_prefix_caching)
        else:
            run = _prefill_packed(self.cfg, q8,
                                  self.enable_prefix_caching)
        dummy = jnp.zeros((1,), jnp.float32)
        faults.fire("prefill_dispatch")
        x, ks, vs = run(
            self.params, jnp.asarray(toks), jnp.asarray(seg),
            jnp.asarray(pos), self.cache.kpool, self.cache.vpool,
            self.cache.kscale if q8 else dummy,
            self.cache.vscale if q8 else dummy,
            jnp.asarray(hist_page), jnp.asarray(hist_slot),
            jnp.asarray(pool_hist), jnp.asarray(stream_src),
            jnp.asarray(stream_hist))
        self.prefill_calls += 1
        real = sum(start + s_real
                   for _, _, _, start, s_real, _, _ in plan)
        self.prefill_token_slots += Tb
        self.prefill_padded_tokens += Tb - real
        if self.metrics is not None:
            self.metrics.prefill_dispatches.inc()
            self.metrics.prefill_padded_tokens.inc(Tb - real)
            self.metrics.prefill_packed_tokens.observe(Tb)
        # the whole wave's page writes coalesce into ONE scatter
        # dispatch (write_pages_batch) — per-segment write_row_pages
        # calls used to cost one device dispatch per admitted row
        self.cache.write_pages_batch(
            [(slot, ks[:, off + start:off + start + Wp],
              vs[:, off + start:off + start + Wp], s_real,
              start // page)
             for req, ctx, slot, start, s_real, Wp, off in plan])
        reqs = [p[0] for p in plan]
        toks_out = None
        if any(not r.generated for r in reqs):
            # batched first tokens from each segment's LAST real
            # position — skipped for an all-resume wave (saved tokens;
            # sampling would burn a PRNG split for nothing)
            with RecordEvent("admit.first_token_tail"):
                last = jnp.asarray([off + start + s_real - 1
                                    for _, _, _, start, s_real, _, off
                                    in plan])
                h = _rms_norm(x[0, last], self.params["final_norm"],
                              self.cfg.rms_norm_eps)
                logits = _mm(h, self.params["lm_head"],
                             self.cfg.dtype).astype(jnp.float32)
                self._key, sub = jax.random.split(self._key)
                # sanctioned drain, kept OFF the _fetch seam:
                # pipeline-depth accounting (one _fetch per drained
                # decode step) is pinned by the overlap tests
                # analysis: ignore[sync-in-hot-path] reason=admission first-token fetch; the pipeline is flushed before any _admit_* runs
                toks_out = np.asarray(_pick_token(
                    logits, self.temperature, sub, self.top_k, self.top_p))
        for i, (req, ctx, slot, start, s_real, Wp, off) in \
                enumerate(plan):
            if req.generated:                    # resume after preempt
                tok = req.generated[-1]
            else:
                tok = int(toks_out[i])
                req.generated.append(tok)
                self._stream.append((req.rid, tok))
            self._finish_admit(req, slot, tok)

    def _admit_swapped(self, req: Request) -> bool:
        """Re-admit a swapped-out request: restore its parked pages
        (one batched dispatch) and rebuild the table — ZERO prefill
        tokens, no sampling (the next input token was saved).  On
        device-pool exhaustion the swapped copy is dropped and False
        returns — the caller requeues for recompute admission in
        FIFO order."""
        t0 = time.perf_counter()
        handle = self._swap_handles[req.rid]
        slot = self._free_slots.pop()
        try:
            restored = self.cache.swap_in_row(slot, handle)
        except RuntimeError:
            del self._swap_handles[req.rid]
            self.cache.discard_swap(handle)
            self._free_slots.append(slot)
            return False
        except BaseException:
            # unexpected failure: return the slot and leave the
            # handle mapped — the quarantine/restart paths discard
            # parked records through _finish_queued_abnormal, so the
            # host pages cannot leak
            self._free_slots.append(slot)
            raise
        del self._swap_handles[req.rid]
        self.prefill_tokens_avoided += restored
        self.resumes_swapped += 1
        dt = time.perf_counter() - t0
        self.resume_wall_s += dt
        self.resume_events += 1
        if self.metrics is not None:
            m = self.metrics
            m.preempt_resume_swapped.inc()
            m.prefill_tokens_avoided.inc(restored)
            m.preempt_resume_seconds.observe(dt)
            m.ring.emit("swap_resume", rid=req.rid, slot=slot,
                        tokens=restored)
        self._finish_admit(req, slot, req.generated[-1])
        if req.trace is not None:
            # span AFTER the admission commit: the restore's row
            # claim must be committed before anything fallible runs
            t1 = time.monotonic()
            req.trace.span("swap_in", t1 - dt, t1, slot=slot,
                           tokens=restored)
        return True

    def _preempt_mode(self, slot: int) -> str:
        """Bytes-vs-FLOPs preemption cost model: ``"swap"`` when
        parking the victim's pages in the host tier and restoring them
        later is cheaper than re-prefilling the context, else
        ``"recompute"``.  The swap moves the row's PRIVATE pages out
        and back (2x the bytes) at ``offload_swap_gbps``; recompute
        pays one forward pass over the context (~2*N_params FLOPs per
        token) at the chip's rate.  Falls back to recompute when the
        host tier is absent, full, or the context is cheap."""
        if not self._offload:
            return "recompute"
        cache = self.cache
        L = int(cache.lens[slot])
        private = cache.private_pages(slot)
        if private == 0:
            return "swap"         # all pages shared: zero transfer,
            #                       and the resume still skips prefill
        if cache.host_available() < private:
            return "recompute"    # host tier full
        if self._n_params is None:
            self._n_params = _count_params(self.params)
        chip = self.offload_chip_flops
        if chip is None:
            chip = _chip_flops_default()
        swap_s = (2.0 * private * cache.page_bytes
                  / (self.offload_swap_gbps * 1e9))
        recompute_s = 2.0 * self._n_params * L / chip
        return "swap" if swap_s < recompute_s else "recompute"

    def _degrade_one_swap(self) -> bool:
        """Last-resort page reclamation: drop one parked swap record
        (its request falls back to recompute resumption), releasing
        the device refs it held on shared pages and its host pages.
        Keeps the engine at least as live as the pure-recompute one —
        swap records must never wedge the allocator."""
        if not self._swap_handles:
            return False
        rid = next(iter(self._swap_handles))
        self.cache.discard_swap(self._swap_handles.pop(rid))
        return True

    def _preempt(self, keep: Optional[int],
                 only: Optional[List[int]] = None) -> bool:
        """Evict one active request (except slot ``keep``) and requeue
        it at the FRONT of the queue — the victim is chosen by the
        scheduler policy: lowest priority class first, most recently
        admitted (``admit_seq`` LIFO) within a class.  ``only``
        restricts the candidate slots (the priority-preemption path
        passes the strictly-lower-class set).  With a host tier and a
        favourable cost model the victim's pages SWAP OUT (resume =
        restore, zero prefill); otherwise they release
        (recompute-style resumption).  Returns False when there is no
        eligible victim (pool genuinely too small).

        Mixed-lane rows parked mid-prefill are evicted FIRST
        (carve-order LIFO): they are the youngest page-holders and
        have produced nothing, and without this an over-eager carve
        could leave an active row's growth with NO victim — the
        sequential engine's equivalent admissions all sit in
        ``_active`` and are preemptible, so the mixed lane must not
        be less live.  A parked victim releases outright and requeues
        at the head (its partial prefill recomputes at the next
        carve); the pipeline is already drained when ``_preempt``
        runs, so its half-written pages are safe to free."""
        if self._mixed_pref and only is None:
            slot = next(reversed(self._mixed_pref))
            ent = self._mixed_pref.pop(slot)
            req = ent["req"]
            req.slot = None
            req.preempted += 1
            self.preemptions += 1
            advance_phase(req, "preempted")
            if req.trace is not None:
                req.trace.event("preempt", mode="mixed-parked",
                                slot=slot)
            self._release_slot(slot)
            self._free_slots.append(slot)
            self._remaining[slot] = 0
            self._active_mask[slot] = 0
            self._queue.appendleft(req)
            if self.metrics is not None:
                self.metrics.preemptions.inc()
                self.metrics.ring.emit(
                    "preemption", rid=req.rid, slot=slot,
                    mode="mixed-parked",
                    generated=len(req.generated))
            return True
        victims = [s for s in (self._active if only is None else only)
                   if s != keep and s in self._active]
        if not victims:
            return False
        slot = self.policy.select_victim(victims, self._active)
        mode = self._preempt_mode(slot)
        req = self._active.pop(slot)
        req.slot = None
        req.preempted += 1
        self.preemptions += 1
        if mode == "swap":
            t0 = time.perf_counter()
            try:
                self._swap_handles[req.rid] = \
                    self.cache.swap_out_row(slot)
            except RuntimeError:
                # swap-out refused (host tier raced full, or an
                # injected fault) — swap_out_row raises BEFORE
                # mutating, so degrade to recompute-style preemption
                # rather than poisoning the whole wave
                mode = "recompute"
                self._release_slot(slot)
            else:
                self._release_aux(slot)
                if self.metrics is not None:
                    self.metrics.swap_seconds.observe(
                        time.perf_counter() - t0)
        else:
            self._release_slot(slot)
        # "swapped" = parked in the host tier (restore pending);
        # "preempted" = recompute-style requeue.  This runs at a
        # flush point — the decode loop never touches phase clocks.
        advance_phase(req, "swapped" if mode == "swap"
                      else "preempted")
        if req.trace is not None:
            req.trace.event("preempt", mode=mode, slot=slot,
                            generated=len(req.generated))
        if self.metrics is not None:
            self.metrics.preemptions.inc()
            self.metrics.ring.emit("preemption", rid=req.rid,
                                   slot=slot, mode=mode,
                                   generated=len(req.generated))
        self._free_slots.append(slot)
        self._remaining[slot] = 0
        self._active_mask[slot] = 0
        self._queue.appendleft(req)
        if self.overlap:
            # the device-side active chain still carries the victim;
            # re-seed loop state before the next dispatch
            self._needs_flush = True
        return True

    def _retire(self, slot: int) -> None:
        req = self._active.pop(slot)
        req.done = True
        req.t_finish = time.monotonic()
        self._release_slot(slot)
        self._free_slots.append(slot)
        self._remaining[slot] = 0
        self._active_mask[slot] = 0
        self.requests_finished += 1
        if self.metrics is not None:
            m = self.metrics
            m.requests_finished.inc()
            n = len(req.generated)
            if n > 1 and req.t_first_token and not req.preempted:
                # mean inter-token time over the decode phase (TTFT
                # excluded — its own histogram).  Preempted requests
                # are excluded: their first-token→finish window spans
                # the requeue wait, which would inflate TPOT exactly
                # when the pool is under the pressure the preemption
                # counter already reports.
                m.tpot.observe(
                    (req.t_finish - req.t_first_token) / (n - 1),
                    exemplar=_tid(req))
            m.ring.emit("request_finished", rid=req.rid, tokens=n,
                        preempted=req.preempted)
        _finalize_trace(req)
        self._finished.append(req)

    # -- fault tolerance: abnormal retirement -----------------------------
    def _count_abnormal(self, req: Request, status: str) -> None:
        """Single bookkeeping site for every non-"ok" ending (plain
        counters + registry instruments stay in lockstep)."""
        if status == "cancelled":
            self.requests_cancelled += 1
        elif status == "expired":
            self.requests_expired += 1
        else:
            self.requests_faulted += 1
        if self.metrics is not None:
            m = self.metrics
            c = {"cancelled": m.requests_cancelled,
                 "expired": m.requests_expired}.get(
                     status, m.requests_faulted)
            c.inc()
            m.ring.emit("request_aborted", rid=req.rid, status=status,
                        generated=len(req.generated))

    def _retire_abnormal(self, slot: int, status: str,
                         error: Optional[str] = None) -> None:
        """Retire an ACTIVE request outside the normal eos/budget path
        (cancelled / expired / wave fault): its pages free through the
        same ``release_row`` seam, and it surfaces in ``finished()``
        carrying ``status`` (+ ``error``) so serving fronts answer the
        client honestly.  No TPOT sample — the generation did not run
        to completion.  The request is failed + finished even when the
        release itself raises (poisoned allocator): a client must
        ALWAYS get a terminal message, whatever the cache's state."""
        req = self._active.pop(slot)
        req.done = True
        req.status = status
        req.error = error
        req.t_finish = time.monotonic()
        try:
            self._release_slot(slot)
        finally:
            self._free_slots.append(slot)
            self._remaining[slot] = 0
            self._active_mask[slot] = 0
            self._count_abnormal(req, status)
            _finalize_trace(req)
            self._finished.append(req)

    def _finish_queued_abnormal(self, req: Request, status: str,
                                error: Optional[str] = None) -> None:
        """Retire a QUEUED request (cancelled / expired before
        admission): its host-tier swap record — the only resource a
        queued request can hold — discards, releasing held device refs
        and host pages."""
        handle = self._swap_handles.pop(req.rid, None)
        if handle is not None:
            self.cache.discard_swap(handle)
        req.done = True
        req.status = status
        req.error = error
        req.t_finish = time.monotonic()
        self._count_abnormal(req, status)
        _finalize_trace(req)
        self._finished.append(req)

    @RecordEvent("engine.sweep")
    def _sweep_cancelled_expired(self) -> None:
        """Retire cancelled/deadline-expired requests at this flush
        point.  Queued ones leave the queue (swap records discard);
        active ones release their slot only AFTER the lookahead
        pipeline drains — an in-flight dispatch still writes their
        pages, and freeing them under it would hand the pages to the
        victim's successor while stale writes are queued (the same
        flush discipline preemption follows)."""
        if not self._cancelled and not self._has_deadlines:
            return
        now = self._now()

        def _hit(req: Request) -> Optional[str]:
            if req.rid in self._cancelled:
                return "cancelled"
            if req.deadline and now >= req.deadline:
                return "expired"
            return None

        if self._queue:
            keep: deque = deque()
            for req in self._queue:
                status = _hit(req)
                if status is None:
                    keep.append(req)
                else:
                    self._finish_queued_abnormal(req, status)
            self._queue = keep
        victims = []
        for slot, req in list(self._active.items()):
            status = _hit(req)
            if status is not None:
                victims.append((slot, req, status))
        # mixed-lane rows mid-prefill hold a slot + pages but stream
        # nothing yet: release through the same flush-then-free
        # discipline (in-flight mixed dispatches still scatter into
        # their pages)
        mixed_victims = []
        for slot, ent in list(self._mixed_pref.items()):
            status = _hit(ent["req"])
            if status is not None:
                mixed_victims.append((slot, ent, status))
        if victims or mixed_victims:
            if self.overlap:
                self._pipeline_flush()
            for slot, req, status in victims:
                # the flush may have retired the victim normally
                # (eos/budget landed on-device first) — honour that
                if self._active.get(slot) is req:
                    self._retire_abnormal(slot, status)
            for slot, ent, status in mixed_victims:
                if self._mixed_pref.get(slot) is not ent:
                    continue
                del self._mixed_pref[slot]
                try:
                    self.cache.release_row(slot)
                finally:
                    # terminal message INSIDE the finally: even a
                    # poisoned allocator must not strand the waiter
                    # (same contract as _retire_abnormal)
                    self._free_slots.append(slot)
                    self._remaining[slot] = 0
                    self._active_mask[slot] = 0
                    self._finish_queued_abnormal(ent["req"], status)
        if self._cancelled:
            # purge consumed marks (and marks whose request finished
            # normally before the sweep saw them)
            live = {r.rid for r in self._queue}
            live.update(r.rid for r in self._active.values())
            self._cancelled &= live

    def _collect_admissions(self):
        """Pop every queued request that fits (slots + pool pages).
        Head-of-line FIFO within a class: the queue is class-ordered
        first (``policy.order_queue``, stable — arrival order and a
        preempted request's head position survive within a class;
        skipped entirely on all-"normal" traffic), then we stop at
        the first that doesn't fit — a failed alloc mid-loop would
        crash the engine.  Already-EXPIRED queued requests prune
        EAGERLY here, before any fit check: they release queue budget
        and 504 immediately instead of occupying a prefill slot (an
        expired request must never dispatch).  Swapped-out requests
        gate on the device pages their restore must claim (their
        on-device shared pages are already held) and bypass the
        prefill lanes entirely."""
        if self._has_priorities and len(self._queue) > 1:
            self._queue = self.policy.order_queue(self._queue)
        admits: List = []                    # (request, context) pairs
        swap_ins: List = []                  # swapped-row restores
        reserved = 0
        now = self._now() if self._has_deadlines else 0.0
        while self._queue and \
                len(self._free_slots) > len(admits) + len(swap_ins):
            head = self._queue[0]
            if head.deadline and now >= head.deadline:
                # eager prune: the deadline passed while waiting —
                # release queue budget (and any parked swap record,
                # via _finish_queued_abnormal) and 504 now
                self._queue.popleft()
                self._finish_queued_abnormal(head, "expired")
                continue
            handle = self._swap_handles.get(head.rid)
            if handle is not None:
                need = self.cache.swap_pages_needed(handle)
                if reserved + need > self.cache.available_pages():
                    break
                reserved += need
                swap_ins.append(self._queue.popleft())
                continue
            ctx = self._ctx_of(head)
            need = (len(ctx) + self.cache.page - 1) // self.cache.page
            # budget against free + EVICTABLE cached-prefix pages: the
            # raw free list shrinks permanently as prompts register,
            # and gating on it livelocks a prefix-caching engine
            if reserved + need > self.cache.available_pages():
                break
            reserved += need
            if head.generated:               # recompute-style resume
                self.resumes_recompute += 1
                if self.metrics is not None:
                    self.metrics.preempt_resume_recompute.inc()
            admits.append((self._queue.popleft(), ctx))
        return admits, swap_ins

    def step(self) -> int:
        """Admit + one decode token for every active slot.  Returns the
        number of active requests after the step.

        With ``quarantine_faults`` (default) a per-step exception does
        NOT kill the engine: the poisoned wave quarantines — every
        slot it carried retires with an error done-message
        (``status == "error"``), the lookahead pipeline's un-drained
        dispatches drop, and the next ``step()`` admits from the queue
        as if nothing happened.  ``max_consecutive_faults`` faults in
        a row escalate (re-raise): a fault on EVERY step means the
        engine itself is broken, and only a supervisor rebuild
        (:class:`EngineSupervisor`) can help."""
        try:
            n = self._step_inner()
        except Exception as exc:
            if not self.quarantine_faults:
                raise
            self._consecutive_faults += 1
            if self._consecutive_faults > self.max_consecutive_faults:
                raise
            self._quarantine(exc)
            return len(self._active)
        self._consecutive_faults = 0
        return n

    def _quarantine(self, exc: BaseException) -> None:
        """Contain a step fault: drop the poisoned in-flight
        dispatches un-drained (their tokens die with the wave), retire
        every slot the wave carried with an error done-message, and
        leave the queue + allocator ready for the next step."""
        text = f"{type(exc).__name__}: {exc}"
        self.last_fault = text
        self.step_faults += 1
        self._inflight.clear()
        self._dev = None
        self._needs_flush = False
        self._drain_active = np.zeros((self.B,), bool)
        if self.cache.host is not None:
            try:
                # commit staged swap-out copies: their device gathers
                # predate the fault, and dropping them would corrupt
                # parked rows
                self.cache.host.flush()
            except Exception:
                pass
        for slot in list(self._active):
            try:
                self._retire_abnormal(slot, "error", text)
            except Exception:
                # the allocator itself refused the release (poisoned
                # cache): the request is already failed + finished
                # (_retire_abnormal's finally) — if this recurs,
                # consecutive-fault escalation hands the engine to
                # the supervisor for a full rebuild
                pass
        # requests the faulted step had already popped off the queue
        # but not yet committed to _active (admission-phase fault, e.g.
        # a prefill dispatch OOM) must not vanish: fail them with an
        # error done-message so their waiters unblock (this also
        # discards a swap record a faulted swap-in resume left parked)
        for req in self._admitting:
            if req.done or (req.slot is not None
                            and self._active.get(req.slot) is req):
                continue
            try:
                self._finish_queued_abnormal(req, "error", text)
            except Exception:
                req.done, req.status, req.error = True, "error", text
                req.t_finish = time.monotonic()
                _finalize_trace(req)
                self._finished.append(req)
        self._admitting = []
        # mixed-lane rows mid-prefill die with the wave: their parked
        # chunk state cannot outlive the poisoned pipeline (the
        # in-flight dispatches carrying their context dropped), so
        # they fail loudly like the _admitting requests above; the
        # stranded-slot sweep below reclaims their pages
        for ent in self._mixed_pref.values():
            req = ent["req"]
            if req.done:
                continue
            try:
                self._finish_queued_abnormal(req, "error", text)
            except Exception:
                req.done, req.status, req.error = True, "error", text
                req.t_finish = time.monotonic()
                _finalize_trace(req)
                self._finished.append(req)
        self._mixed_pref.clear()
        # reclaim slots stranded mid-admission: popped from the free
        # list (rows possibly holding freshly-claimed pages) but never
        # committed to _active
        for slot in range(self.B):
            if slot in self._active or slot in self._free_slots:
                continue
            try:
                self.cache.release_row(slot)
            except Exception:
                pass
            self._free_slots.append(slot)
            self._remaining[slot] = 0
            self._active_mask[slot] = 0
        if self.metrics is not None:
            self.metrics.ring.emit(
                "engine_quarantine", error=text,
                consecutive=self._consecutive_faults)

    @RecordEvent("engine.step")
    def _step_inner(self) -> int:
        self._sweep_cancelled_expired()
        if self._mixed and (self._active or self._mixed_pref):
            # MIXED lane: decode never pauses for admission — waiting
            # prompts park as chunk state and their tokens ride inside
            # the decode dispatches below.  An IDLE mixed engine
            # (nothing decoding, nothing parked) degrades to the
            # sequential wave on purpose: there is no decode latency
            # to protect, and one packed wave admits a cold batch
            # faster than budget-sized ticks would.
            self._mixed_carve()
        else:
            self._admit_wave()
        if not self._active and not self._mixed_pref:
            return 0
        t0 = time.perf_counter()
        if self._mixed_pref:
            self._decode_mixed()
        else:
            self._decode_once()
        dt = time.perf_counter() - t0
        self.decode_wall_s += dt
        if self.metrics is not None:
            self.metrics.decode_seconds.observe(dt)
            if self._tp:
                # host-observed wall of the collective-bearing TP
                # decode round (single-device engines never record it)
                self.metrics.tp_collective_seconds.observe(dt)
        return len(self._active)

    def _admit_wave(self) -> None:
        """The SEQUENTIAL admission path: pop everything that fits,
        flush the pipeline (admission is a scheduler mutation) and
        prefill it as one wave through the packed/batched/chunked
        lanes."""
        admits, swap_ins = self._collect_admissions()
        while not admits and not swap_ins and not self._active \
                and self._queue and self._degrade_one_swap():
            # nothing fits and nothing is running: parked swap records
            # are the only thing still pinning pages — degrade them to
            # recompute resumes until the head of the queue fits
            admits, swap_ins = self._collect_admissions()
        while self._has_priorities and not admits and not swap_ins \
                and self._queue and self._priority_preempt():
            # PRIORITY PREEMPTION: the (class-ordered) queue head
            # cannot get a seat while strictly lower-class work holds
            # slots/pages — evict one victim per turn through the
            # existing swap/recompute machinery (token-exact resume)
            # until the head fits or no lower-class victim remains
            admits, swap_ins = self._collect_admissions()
        if (admits or swap_ins) and self.overlap:
            # admission is a scheduler mutation: drain the lookahead
            # pipeline before slots/pages move under it
            self._pipeline_flush()
        # track requests popped off the queue but not yet committed to
        # _active: an admission-phase fault must fail them loudly (see
        # _quarantine), never drop them with the stack
        self._admitting = [req for req, _ in admits] + list(swap_ins)
        failed_swap_ins = [req for req in swap_ins
                           if not self._admit_swapped(req)]
        for req in reversed(failed_swap_ins):
            # requeue in FIFO order (appendleft reverses, so walk the
            # failures back-to-front): the oldest failed resume must
            # stay at the head for its recompute admission
            self._queue.appendleft(req)
        self._admitting = [req for req, _ in admits]
        all_resumes = bool(admits) and all(r.generated
                                           for r, _ in admits)
        t_adm = time.perf_counter() if admits else 0.0
        if admits:
            self._admit_sequential(admits)
        self._admitting = []          # every admit committed to _active
        if all_resumes:
            # an all-resume recompute wave: its admission wall IS the
            # resume latency, attributed PER REQUEST so the sample
            # stays comparable with the per-request swap-in samples
            # (mixed waves are not attributed — a fresh prompt's
            # prefill would pollute the sample)
            dt = time.perf_counter() - t_adm
            self.resume_wall_s += dt
            self.resume_events += len(admits)
            if self.metrics is not None:
                self.metrics.preempt_resume_seconds.observe(
                    dt / len(admits))

    def _priority_preempt(self) -> bool:
        """Evict ONE active request of a class strictly below the
        queue head's so the head can admit (the policy picks the
        victim: lowest class, ``admit_seq`` LIFO within it).  Runs at
        a scheduler mutation point — the lookahead pipeline drains
        first, same flush discipline as every other preemption.
        Returns False when no lower-class victim exists (equal-class
        work is never churned by arrival order alone)."""
        victims = self.policy.preemptable_for(self._queue[0],
                                              self._active)
        if not victims:
            return False
        if self.overlap:
            self._pipeline_flush()
            # the flush may have retired rows — re-derive the set
            victims = [s for s in victims if s in self._active]
            if not victims:
                return True     # pages freed without a preemption
        return self._preempt(keep=None, only=victims)

    def _admit_sequential(self, admits: List) -> None:
        """One popped admission wave through its lane — shared by the
        sequential path and the mixed lane's shape-forced degrades
        (both call it behind a flushed pipeline) — under ONE
        ``engine.admit`` span: a ring event per wave (the ring is sized
        for waves, not steps) and, under a profiler session, a span on
        the device ops' clock."""
        attrs = dict(n_requests=len(admits),
                     tokens=sum(len(ctx) for _, ctx in admits),
                     lane="packed" if self._packed else "bucketed")
        span = (self.metrics.ring.span("engine.admit", **attrs)
                if self.metrics is not None
                else RecordEvent("engine.admit", **attrs))
        with span:
            self._admit_lanes(admits)

    def _admit_lanes(self, admits: List) -> None:
        """Lane choice for one admission wave."""
        for req, _ in admits:
            # the wave's wall lands in each rider's "prefill" clock
            advance_phase(req, "prefill")
        if self._packed:
            # PACKED VARLEN lane: any length mix (prefix-cache
            # suffixes, long prompts, resumes) is ONE dispatch per
            # wave — prefill_chunk is moot here, the per-wave cost is
            # bounded by the total waiting tokens, not per prompt
            self._admit_packed(admits)
            return
        buckets: Dict[int, List] = {}
        for req, ctx in admits:
            L = len(ctx)
            if self.enable_prefix_caching or (
                    self.prefill_chunk is not None
                    and L > self.prefill_chunk):
                self._admit_chunked(req, ctx)
                continue
            Lp = ((L + self.prefill_bucket - 1) //
                  self.prefill_bucket) * self.prefill_bucket
            buckets.setdefault(Lp, []).append((req, ctx))
        for group in buckets.values():
            self._admit_batch(group)

    # -- mixed prefill+decode lane (Sarathi-style piggybacking) ----------
    def _mixed_carve(self) -> None:
        """Admission for the MIXED lane: claim a slot + the full row's
        pages for each waiting request that fits and park it as chunk
        state in ``_mixed_pref`` — ZERO prefill dispatches here; the
        context tokens ride inside subsequent mixed decode dispatches
        (:meth:`_decode_mixed`), ``mixed_token_budget`` per tick.
        Swapped-out resumes restore through the ordinary (flushing)
        zero-prefill path; a context longer than ``mixed_ctx_cap``
        no longer fits the mixed stream shape and degrades to ONE
        sequential packed wave (counted in ``mixed_degraded``)."""
        cache = self.cache
        degrades: List = []
        res_pages = 0
        while self._queue:
            if len(self._free_slots) <= len(degrades):
                break                 # keep a slot per pending degrade
            head = self._queue[0]
            handle = self._swap_handles.get(head.rid)
            if handle is not None:
                need = cache.swap_pages_needed(handle)
                if need + res_pages > cache.available_pages():
                    break
                if self.overlap:
                    self._pipeline_flush()
                req = self._queue.popleft()
                self._admitting.append(req)
                if not self._admit_swapped(req):
                    # record dropped: requeue at the head for an
                    # ordinary (mixed-carve) recompute admission
                    self._queue.appendleft(req)
                self._admitting = []
                continue
            ctx = self._ctx_of(head)
            need = -(-len(ctx) // cache.page)
            if need + res_pages > cache.available_pages():
                break
            if len(ctx) > self.mixed_ctx_cap:
                degrades.append((self._queue.popleft(), ctx))
                res_pages += need
                continue
            slot = self._free_slots.pop()
            try:
                if self.enable_prefix_caching:
                    # analysis: ignore[claim-lifecycle] reason=mixed-lane transfer: the slot left _free_slots and parks in _mixed_pref, whose rows _quarantine/_sweep/restart reclaim via release_row (audit-clean, pinned by test_serving_mixed)
                    start = cache.alloc_row_prefix(slot, ctx)
                else:
                    # analysis: ignore[claim-lifecycle] reason=mixed-lane transfer: the slot left _free_slots and parks in _mixed_pref, whose rows _quarantine/_sweep/restart reclaim via release_row (audit-clean, pinned by test_serving_mixed)
                    cache.alloc_row(slot, len(ctx))
                    start = 0
            except RuntimeError:
                # raced out of pages (eviction couldn't cover): the
                # request stays queued for a later tick
                self._free_slots.append(slot)
                break
            req = self._queue.popleft()
            if req.generated:             # recompute-style resume
                self.resumes_recompute += 1
                if self.metrics is not None:
                    self.metrics.preempt_resume_recompute.inc()
            # parked mid-prefill: its context rides inside the mixed
            # dispatches from here — "prefill" until activation
            advance_phase(req, "prefill")
            self._mixed_pref[slot] = {"req": req, "ctx": ctx,
                                      "pos": start, "start": start}
        if degrades:
            self.mixed_degraded += len(degrades)
            if self.overlap:
                self._pipeline_flush()
            self._admitting = [r for r, _ in degrades]
            self._admit_sequential(degrades)
            self._admitting = []

    def _mixed_plan(self) -> List:
        """Carve this tick's prefill budget across the parked chunk
        states (FIFO by carve order): each gets up to the remaining
        budget pages, bounded by the stream room left after its
        history slots (a resumed chunk re-gathers its written context
        into the stream).  Returns ``(slot, pos, take, npg)`` tuples;
        page-aligned by construction.  Decode rows are never throttled
        — the budget only bounds the piggybacked prefill."""
        page = self.cache.page
        budget_pg = self.mixed_token_budget // page
        stream_pg = self.mixed_ctx_cap // page
        plan: List = []
        for slot, ent in self._mixed_pref.items():
            if budget_pg <= 0 or stream_pg <= 0:
                break
            pos = ent["pos"]
            rem = len(ent["ctx"]) - pos
            hist_pg = pos // page
            fit = stream_pg - hist_pg
            if fit <= 0:
                continue          # waits for a roomier tick
            npg = min(-(-rem // page), budget_pg, fit)
            if npg <= 0:
                continue
            take = min(rem, npg * page)
            plan.append((slot, pos, take, npg))
            budget_pg -= npg
            stream_pg -= hist_pg + npg
        return plan

    def _decode_mixed(self) -> None:
        """One MIXED tick: a single jitted dispatch advances every
        active decode row AND consumes up to ``mixed_token_budget``
        prefill tokens from the parked chunk states — the engine
        never stops decoding to admit.  Completing segments sample
        their first token INSIDE the program and activate on-device
        (the overlap chain carries them into the next dispatch with
        no flush); the host learns the sampled token at the ordinary
        one-step-behind drain.  Zero new host syncs: the overlap lane
        adds the first-token array to the existing single ``_fetch``
        per drained step, the sync lane keeps its one fetch per
        tick."""
        cache = self.cache
        page = cache.page
        B = self.B
        if self.overlap and self._needs_flush:
            self._pipeline_flush()
        if self._active:
            self._ensure_or_preempt()
            if self.overlap and self._needs_flush:  # a preemption landed
                self._pipeline_flush()
        plan = self._mixed_plan()
        if not plan:
            # the growth pass above preempted EVERY parked row (pool
            # pressure empties _mixed_pref — a non-empty parked set
            # always plans its first entry): nothing to piggyback, so
            # run the plain decode tick instead of a fused dispatch
            # over an all-padding stream
            self._decode_once()
            return
        # stream assembly (the packed lane's layout: contiguous
        # segments = [history slots][fresh chunk, page-padded])
        T = sum((pos // page + npg) * page for _, pos, _, npg in plan)
        Tb = self._packed_bucket(max(T, page))
        nseg = len(plan)
        toks = np.zeros((1, Tb), np.int64)
        seg = np.full((1, Tb), nseg, np.int32)       # sentinel tail
        posa = np.zeros((1, Tb), np.int32)
        hist_page = np.zeros((Tb,), np.int32)
        hist_slot = np.zeros((Tb,), np.int32)
        pool_hist = np.zeros((Tb,), bool)
        dest_page = np.zeros((Tb,), np.int32)
        dest_slot = np.zeros((Tb,), np.int32)
        sample_idx = np.zeros((B,), np.int32)
        activate = np.zeros((B,), bool)
        p_first = np.zeros((B,), np.int64)
        p_sample = np.zeros((B,), bool)
        p_len = np.zeros((B,), np.int32)
        p_rem = np.zeros((B,), np.int64)
        off = 0
        fresh = 0
        hist_total = 0
        completing: List = []
        for i, (slot, pos, take, npg) in enumerate(plan):
            ent = self._mixed_pref[slot]
            hist = pos
            W = hist + npg * page
            seg[0, off:off + W] = i
            posa[0, off:off + W] = np.arange(W, dtype=np.int32)
            toks[0, off + hist:off + hist + take] = \
                ent["ctx"][pos:pos + take]
            for j in range(hist // page):
                a = off + j * page
                hist_page[a:a + page] = int(cache.tables[slot, j])
                hist_slot[a:a + page] = np.arange(page)
                pool_hist[a:a + page] = True
            for j in range(npg):
                a = off + hist + j * page
                dest_page[a:a + page] = int(
                    cache.tables[slot, pos // page + j])
                dest_slot[a:a + page] = np.arange(page)
            fresh += take
            hist_total += hist
            if pos + take == len(ent["ctx"]):
                req = ent["req"]
                activate[slot] = True
                p_len[slot] = len(ent["ctx"])
                if req.generated:        # resume: saved next input
                    p_first[slot] = req.generated[-1]
                    p_rem[slot] = req.max_new_tokens - \
                        len(req.generated)
                else:                    # fresh: sample in-program
                    p_sample[slot] = True
                    sample_idx[slot] = off + hist + take - 1
                    p_rem[slot] = req.max_new_tokens - 1
                completing.append((slot, req))
            off += W
        with RecordEvent("engine.dispatch"):
            q8 = cache.kv_quant == "int8"
            if self.overlap:
                d = self._seed_or_refresh_dev()
                tables_in, lens_in, tok_in = (d["tables"], d["lens"],
                                              d["tok"])
                act_in, rem_in = d["active"], d["remaining"]
            else:
                tables_in = jnp.asarray(cache.tables.copy())
                lens_in = jnp.asarray(cache.lens.copy())
                tok_in = jnp.asarray(self._next_tok.copy())
                act_in = jnp.asarray(self._active_mask.astype(bool))
                rem_in = jnp.asarray(self._remaining.copy())
            self._key, sub = jax.random.split(self._key)
            faults.fire("step_dispatch")
            args = (self.params, cache.kpool, cache.vpool)
            if q8:
                args += (cache.kscale, cache.vscale)
            args += (tables_in, lens_in, tok_in, act_in, rem_in,
                     self._eos_dev, sub, jnp.asarray(toks),
                     jnp.asarray(seg), jnp.asarray(posa),
                     jnp.asarray(hist_page), jnp.asarray(hist_slot),
                     jnp.asarray(pool_hist), jnp.asarray(dest_page),
                     jnp.asarray(dest_slot), jnp.asarray(sample_idx),
                     jnp.asarray(activate), jnp.asarray(p_first),
                     jnp.asarray(p_sample), jnp.asarray(p_len),
                     jnp.asarray(p_rem))
            out = self._step_mixed(*args)
            if q8:
                (cache.kpool, cache.vpool, cache.kscale, cache.vscale,
                 nxt, lens2, rem2, act2, done, ftok) = out
            else:
                (cache.kpool, cache.vpool, nxt, lens2, rem2, act2,
                 done, ftok) = out
        self.decode_steps += 1
        self.mixed_ticks += 1
        self.mixed_prefill_tokens += fresh
        self._count_tp_dispatch()
        self.prefill_token_slots += Tb
        padded = Tb - hist_total - fresh
        self.prefill_padded_tokens += padded
        if self.metrics is not None:
            m = self.metrics
            m.decode_steps.inc()
            m.mixed_ticks.inc()
            m.mixed_prefill_tokens.inc(fresh)
            m.mixed_budget_tokens.observe(fresh)
            m.prefill_padded_tokens.inc(padded)
        # host lens mirror BEFORE activation: the newly-activated
        # rows' first decode write lands NEXT dispatch at p_len
        # (cache.lens already reads the full context length from the
        # carve-time alloc)
        cache.lens = cache.lens + self._active_mask
        if self.overlap:
            d["lens"], d["tok"] = lens2, nxt
            d["active"], d["remaining"] = act2, rem2
            entry: Dict = {"nxt": nxt, "done": done}
            if completing:
                entry["ftok"] = ftok
                entry["activate"] = activate.copy()
                entry["mixed_first"] = {
                    slot: req for slot, req in completing
                    if not req.generated}
            self._inflight.append(entry)
        # chunk-state advance + progressive prefix registration (a
        # page registers only AFTER the dispatch carrying its content
        # — later sharers gather from the pool one dispatch behind,
        # ordered by the threaded pool arrays)
        for slot, pos, take, npg in plan:
            ent = self._mixed_pref.get(slot)
            req = ent["req"] if ent is not None else None
            if req is None:
                continue
            ent["pos"] = pos + take if pos + take == len(ent["ctx"]) \
                else pos + npg * page
            if self.enable_prefix_caching:
                written_prompt = min(pos + take, len(req.prompt))
                if written_prompt >= page:
                    self.cache.register_prefix(
                        slot, np.asarray(req.prompt[:written_prompt]))
        # activation commit: completing rows join the decode batch
        # for the NEXT dispatch (the device chain already carries
        # them); fresh rows' first token surfaces at the drain
        for slot, req in completing:
            ent = self._mixed_pref.pop(slot, None)
            if ent is None:
                continue
            if req.t_admit == 0.0:
                req.t_admit = time.monotonic()
                if self.metrics is not None:
                    self.metrics.queue_wait.observe(
                        req.t_admit - req.t_submit,
                        exemplar=_tid(req))
            advance_phase(req, "decode_active")
            req.slot = slot
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            self._active[slot] = req
            self._active_mask[slot] = 1
            self._remaining[slot] = int(p_rem[slot])
            if req.generated:            # resume: token already known
                self._next_tok[slot] = req.generated[-1]
                if self._hit_stop(req, req.generated[-1]) or \
                        self._remaining[slot] <= 0:
                    # host-only retirement under an in-flight
                    # dispatch: same discipline as stop sequences
                    self._retire(slot)
                    if self.overlap:
                        self._needs_flush = True
        if self.overlap:
            if len(self._inflight) > self.lookahead:
                self._drain_one()
            return
        # -- synchronous lane: one fetch per tick (mirrors
        # _decode_sync's single blocking round-trip)
        with RecordEvent("engine.fetch"):
            # analysis: ignore[sync-in-hot-path] reason=the synchronous (overlap=False) mixed lane's one fetch per tick — the exact counterpart of _decode_sync's blocking round-trip
            nxt_h, ftok_h = np.asarray(nxt), np.asarray(ftok)
            self.host_syncs += 1
        with RecordEvent("engine.drain"):
            t0 = time.perf_counter() if self.metrics is not None else 0.0
            advanced = 0
            for slot, req in list(self._active.items()):
                if activate[slot]:
                    continue       # activated this tick: first decode
                    #                token arrives next tick
                t = int(nxt_h[slot])
                self._deliver_token(slot, req, t)
                advanced += 1
                self._remaining[slot] -= 1
                if self._hit_stop(req, t) or self._remaining[slot] <= 0:
                    self._retire(slot)
            for slot, req in completing:
                if req.generated or self._active.get(slot) is not req:
                    continue
                t = int(ftok_h[slot])
                self._deliver_token(slot, req, t, count=False)
                if self._hit_stop(req, t) or self._remaining[slot] <= 0:
                    self._retire(slot)
            if self.metrics is not None:
                self.metrics.tokens_generated.inc(advanced)
                self.metrics.host_bookkeeping.observe(
                    time.perf_counter() - t0)

    def _deliver_token(self, slot: int, req: Request, t: int,
                       count: bool = True) -> None:
        """The shared per-token delivery core every lane uses —
        append + lifecycle stamp + stream emission + next-input
        bookkeeping.  ONE definition, so the sync / overlap-drain /
        mixed lanes' emission behaviour can never fork.
        ``count=False`` for admission first tokens (no lane counts
        them in ``tokens_generated``).  Remaining-budget decrement
        and retire decisions stay at the call sites — they are what
        legitimately differs per lane."""
        req.generated.append(t)
        if count:
            self.tokens_generated += 1
        self._note_first_token(req)
        self._stream.append((req.rid, t))
        self._next_tok[slot] = t

    def _count_tp_dispatch(self, n: int = 1,
                           bytes_per: Optional[int] = None) -> None:
        """Account one (or ``n``) TP decode dispatches' collective
        traffic: the analytic per-dispatch bytes of the per-layer
        output reductions (attention wo + FFN w_down) in the engine's
        ``tp_allreduce`` mode.  No-op off-mesh."""
        if not self._tp:
            return
        b = (self._tp_bytes_step if bytes_per is None else bytes_per) \
            * n
        self.tp_allreduce_bytes += b
        if self.metrics is not None:
            self.metrics.tp_allreduce_bytes.inc(b)

    def _grow_tokens(self, slot: int, new_tokens: int) -> int:
        """How many tokens of pages THIS dispatch's growth must claim
        for ``slot``.  HORIZON claims (``_step_multi`` built) clamp
        ``new_tokens`` to the row's remaining budget (the horizon
        scan stops advancing at remaining==0, so claiming the full H
        past it would spuriously exceed the row cap for near-done
        rows; the host mirror only over-estimates remaining, never
        under, so the clamp always covers what the device will write)
        and to the row's table capacity (an over-advanced lens mirror
        of a row that already retired on-device must not spuriously
        ValueError).  NON-horizon claims pass through unclamped — the
        speculative lane's gamma+1 claim deliberately covers verify
        K/V written PAST the remaining budget, so a remaining clamp
        there would push real writes onto the junk page.  ``<= 0``
        means nothing to claim — skip the row."""
        if self._spec is not None:
            # SPECULATIVE claim: gamma+1 candidate K/V scatter, which
            # deliberately writes PAST the remaining budget (the round
            # commits at most ``remaining`` tokens but scores every
            # candidate) — so NO remaining clamp; the table-capacity
            # clamp still guards rows whose mirror over-advanced
            # (retired on-device, not yet drained) and keeps the tail
            # of a near-cap row's candidates on the junk page, where
            # the fused scatter steers unclaimed positions anyway
            lens_m = int(self.cache.lens[slot])
            return min(new_tokens,
                       self.cache.pages_max * self.cache.page - lens_m)
        if self._step_multi is None:
            if self._inflight and int(self.cache.lens[slot]) \
                    // self.cache.page >= self.cache.pages_max:
                # lens MIRROR past the row's table capacity: a live
                # row can never get here (submit bounds its worst
                # case) — this is a row that already retired
                # on-device and whose undrained dispatches
                # over-advanced the mirror
                return 0
            return new_tokens
        lens_m = int(self.cache.lens[slot])
        cap = self.cache.pages_max * self.cache.page - lens_m
        return min(new_tokens, max(int(self._remaining[slot]), 1),
                   cap)

    def _ensure_or_preempt(self, new_tokens: int = 1,
                           aux_cache=None, aux_new: int = 0,
                           aux_rows=None) -> None:
        """Grow every active row's pages (and optionally an auxiliary
        cache's), preempting the youngest other request on pool
        exhaustion instead of crashing the engine.

        Fast path: the whole tick's growth is ONE coalesced
        ``ensure_capacity_batch`` claim — at most one
        ``tables_version`` bump, hence at most one device tables
        re-upload per tick, however many rows grew (the old per-slot
        loop re-uploaded once per growing row; with H-token horizon
        pre-claims that multiplied).  Pool pressure falls back to the
        per-slot grow-or-preempt loop.

        ``aux_rows`` (bool mask over slots) restricts the auxiliary
        claim to rows that actually own an aux row — the speculative
        lane's spec-off rows never allocate a draft row, so claiming
        for them would leak draft pages."""
        needs = []
        for slot in self._active:
            n = self._grow_tokens(slot, new_tokens)
            if n > 0:
                needs.append((slot, n))
        if not needs:
            return
        try:
            self.cache.ensure_capacity_batch(needs)
            if aux_cache is not None:
                aux_needs = [(slot, aux_new) for slot, _ in needs
                             if aux_rows is None or aux_rows[slot]]
                if aux_needs:
                    aux_cache.ensure_capacity_batch(aux_needs)
            return
        except RuntimeError:
            pass                   # pool pressure: per-slot fallback
        for slot in list(self._active):
            if slot not in self._active:     # evicted by an earlier turn
                continue
            n = self._grow_tokens(slot, new_tokens)
            if n <= 0:
                # nothing to claim (over-advanced mirror of a row
                # retired on-device, or a full table)
                continue
            while True:
                try:
                    self.cache.ensure_capacity(slot, n)
                    if aux_cache is not None and \
                            (aux_rows is None or aux_rows[slot]):
                        aux_cache.ensure_capacity(slot, aux_new)
                    break
                except RuntimeError:
                    if self._inflight:
                        # drain the pipeline first: a pending on-device
                        # retirement may free pages without preempting
                        # anyone (and preempting under an in-flight
                        # dispatch would hand its pages to the victim's
                        # successor while stale writes are still queued)
                        self._pipeline_flush()
                        if slot not in self._active:
                            break
                        # the flush made the mirrors exact: re-clamp
                        # (the row may now need fewer tokens of pages)
                        n = self._grow_tokens(slot, new_tokens)
                        if n <= 0:
                            break
                        continue
                    # pool exhausted mid-flight: preempt the youngest
                    # other request (pages freed or swapped, request
                    # requeued) instead of crashing the engine and
                    # losing every in-flight generation
                    if not self._preempt(keep=slot):
                        # no victim left — parked swap records may
                        # still hold shared-page refs: degrade them to
                        # recompute resumes before giving up
                        if self._degrade_one_swap():
                            continue
                        raise RuntimeError(
                            "KV page pool exhausted and no preemption "
                            "victim remains; the pool is too small for "
                            "a single request of this length")

    def _decode_once(self) -> None:
        """One decode round advancing every active slot: the
        synchronous dispatch-then-sync loop, or — with
        ``overlap=True`` — one turn of the dispatch-ahead pipeline.
        With ``decode_horizon > 1`` both lanes advance by horizon
        BLOCKS — one multi-step dispatch (and one fetch) per H
        tokens.  With ``spec=SpecConfig(...)`` every round is one
        fused draft+verify dispatch committing up to gamma+1 tokens
        per row (draft-model spec overlaps like the plain pipeline;
        prompt-lookup runs the sync cadence even under
        ``overlap=True`` — the host proposer needs the round's
        committed tokens before it can draft the next)."""
        if self._spec is not None:
            if self.overlap and self._spec.source == "draft":
                self._decode_spec_overlap()
            else:
                self._decode_spec_sync()
        elif self.overlap:
            self._decode_overlap()
        elif self._step_multi is not None:
            self._decode_sync_multi()
        else:
            self._decode_sync()

    def _decode_sync(self) -> None:
        """One decode dispatch + blocking host round-trip."""
        cache = self.cache
        with RecordEvent("engine.dispatch"):
            self._ensure_or_preempt()
            tables = jnp.asarray(cache.tables.copy())
            lens = jnp.asarray(cache.lens.copy())
            tok = jnp.asarray(self._next_tok.copy())
            self._key, sub = jax.random.split(self._key)
            faults.fire("step_dispatch")
            if cache.kv_quant == "int8":
                (cache.kpool, cache.vpool, cache.kscale, cache.vscale,
                 nxt) = self._step(self.params, cache.kpool, cache.vpool,
                                   cache.kscale, cache.vscale, tables,
                                   lens, tok, sub)
            else:
                cache.kpool, cache.vpool, nxt = self._step(
                    self.params, cache.kpool, cache.vpool, tables, lens,
                    tok, sub)
            cache.lens = cache.lens + self._active_mask
            self.decode_steps += 1
            self._count_tp_dispatch()
        with RecordEvent("engine.fetch"):
            # analysis: ignore[sync-in-hot-path] reason=the synchronous lane's one blocking fetch per tick IS its design (overlap=False); reachable from the mixed hot root only via the degenerate all-parked-rows-preempted fallback tick
            nxt = np.asarray(nxt)
            self.host_syncs += 1
        with RecordEvent("engine.drain"):
            t0 = time.perf_counter() if self.metrics is not None else 0.0
            advanced = 0
            for slot, req in list(self._active.items()):
                # analysis: ignore[sync-in-hot-path] reason=host-numpy read: nxt was fetched by the sanctioned sync above (the taint walker keeps the rebind tainted)
                t = int(nxt[slot])
                self._deliver_token(slot, req, t)
                advanced += 1
                self._remaining[slot] -= 1
                if self._hit_stop(req, t) or self._remaining[slot] <= 0:
                    self._retire(slot)
            if self.metrics is not None:
                self.metrics.decode_steps.inc()
                self.metrics.tokens_generated.inc(advanced)
                self.metrics.host_bookkeeping.observe(
                    time.perf_counter() - t0)

    # -- dispatch-ahead pipeline (overlap=True) ---------------------------
    def _decode_overlap(self) -> None:
        """One turn of the one-step-lookahead pipeline: dispatch step
        k chained off step k-1's ON-DEVICE outputs (no host sync),
        THEN drain step k-1's token/done arrays while k runs — the
        admission/streaming/retirement bookkeeping below overlaps
        device compute instead of serialising with it."""
        if self._needs_flush:
            self._pipeline_flush()
        if self._active:
            # grow rows for the next write positions — the whole
            # horizon's worth, so tables stay constant across the
            # block.  The host lens mirror is exact for live rows; a
            # row that already retired on-device but is not yet
            # drained may over-allocate (released at retirement).
            self._ensure_or_preempt(self.decode_horizon)
            if self._needs_flush:          # a preemption landed
                self._pipeline_flush()
            if self._active:
                self._dispatch_async()
        if self._active and len(self._inflight) > self.lookahead:
            self._drain_one()
        if not self._active and self._inflight:
            # the batch just went idle: the lookahead dispatch(es)
            # carry no live rows — drain them so the engine parks with
            # an empty pipeline (depth gauge reads 0, the steps'
            # device arrays unpin) instead of stranding them until the
            # next admission's flush
            while self._inflight:
                self._drain_one()
            self._dev = None

    def _seed_or_refresh_dev(self) -> Dict:
        """(Re)seed the device-resident loop state from host truth
        after a flush, or re-upload only the block tables when page
        allocations bumped ``tables_version`` — the ONE owner of the
        overlap chain's seeding invariant, shared by the plain
        dispatch-ahead lane and the mixed lane (their chained state
        must never diverge)."""
        cache = self.cache
        if self._dev is None:
            self._dev = {
                "tables": jnp.asarray(cache.tables.copy()),
                "lens": jnp.asarray(cache.lens.copy()),
                "tok": jnp.asarray(self._next_tok.copy()),
                "active": jnp.asarray(self._active_mask.astype(bool)),
                "remaining": jnp.asarray(self._remaining.copy()),
            }
            if self._spec is not None:
                # the speculative chain additionally carries the
                # prev-token feed (draft catch-up) and the per-row
                # on/off mask (constant between flushes — admission
                # and retirement both flush)
                self._dev["prev"] = jnp.asarray(self._prev_tok.copy())
                self._dev["spec_on"] = jnp.asarray(
                    self._spec_on.copy())
                # force the draft-table upload into the fresh dict
                # (its version may not have bumped since the flush)
                self._dev_dtables_version = -1
            self._dev_tables_version = cache.tables_version
            self._drain_active = self._active_mask.astype(bool)
        elif self._dev_tables_version != cache.tables_version:
            # page growth / carve allocs: only the tables re-upload —
            # the chained lens/tok/active/remaining stay
            # device-resident
            self._dev["tables"] = jnp.asarray(cache.tables.copy())
            self._dev_tables_version = cache.tables_version
        if self._spec is not None and self._spec_dcache is not None:
            dcache = self._spec_dcache
            if self._dev_dtables_version != dcache.tables_version:
                self._dev["dtables"] = jnp.asarray(
                    dcache.tables.copy())
                self._dev_dtables_version = dcache.tables_version
        return self._dev

    @RecordEvent("engine.dispatch")
    def _dispatch_async(self) -> None:
        """Issue one decode step — or, with ``decode_horizon > 1``,
        one H-micro-step horizon BLOCK — chained off the
        device-resident loop state.  Zero blocking host work: uploads
        happen only when the state was invalidated by a flush (or the
        block tables grew)."""
        cache = self.cache
        d = self._seed_or_refresh_dev()
        self._key, sub = jax.random.split(self._key)
        faults.fire("step_dispatch")
        if self._step_multi is not None:
            if cache.kv_quant == "int8":
                (cache.kpool, cache.vpool, cache.kscale, cache.vscale,
                 toks, dones, tok_f, lens_f, rem_f,
                 act_f) = self._step_multi(
                    self.params, cache.kpool, cache.vpool,
                    cache.kscale, cache.vscale, d["tables"], d["lens"],
                    d["tok"], d["active"], d["remaining"],
                    self._eos_dev, sub)
            else:
                (cache.kpool, cache.vpool, toks, dones, tok_f, lens_f,
                 rem_f, act_f) = self._step_multi(
                    self.params, cache.kpool, cache.vpool, d["tables"],
                    d["lens"], d["tok"], d["active"], d["remaining"],
                    self._eos_dev, sub)
            d["lens"], d["tok"] = lens_f, tok_f
            d["active"], d["remaining"] = act_f, rem_f
            self._inflight.append({"toks": toks, "dones": dones})
            # one horizon block carries H micro-steps of collectives
            self._count_tp_dispatch(self.decode_horizon)
            # mirror advances the FULL horizon: exact for rows that
            # stay live through the block (they advanced H on-device),
            # over for rows retiring mid-horizon — those retire at the
            # drain and their release zeroes the entry (self-healing,
            # same discipline as the single-step lane)
            cache.lens = cache.lens + (self.decode_horizon
                                       * self._active_mask)
        else:
            if cache.kv_quant == "int8":
                (cache.kpool, cache.vpool, cache.kscale, cache.vscale,
                 nxt, lens2, rem2, act2, done) = self._step_async(
                    self.params, cache.kpool, cache.vpool, cache.kscale,
                    cache.vscale, d["tables"], d["lens"], d["tok"],
                    d["active"], d["remaining"], self._eos_dev, sub)
            else:
                (cache.kpool, cache.vpool, nxt, lens2, rem2, act2,
                 done) = self._step_async(
                    self.params, cache.kpool, cache.vpool, d["tables"],
                    d["lens"], d["tok"], d["active"], d["remaining"],
                    self._eos_dev, sub)
            d["lens"], d["tok"] = lens2, nxt
            d["active"], d["remaining"] = act2, rem2
            self._inflight.append({"nxt": nxt, "done": done})
            self._count_tp_dispatch()
            # advance the host lens mirror for the NEXT dispatch's
            # capacity check (exact for live rows; self-healing for
            # device-retired rows — their release zeroes the entry)
            cache.lens = cache.lens + self._active_mask
        self.decode_steps += 1
        if self.metrics is not None:
            self.metrics.decode_steps.inc()

    @RecordEvent("engine.fetch")
    def _fetch(self, *arrs):
        """Blocking device->host fetch — the pipeline's ONLY sync
        point, one call per drained step (tests count calls and their
        ordering vs dispatches through this seam)."""
        self.host_syncs += 1
        return [np.asarray(a) for a in arrs]

    def _drain_one(self) -> None:
        """Sync on the OLDEST in-flight step's outputs (by then the
        next step is already running on-device) and run the per-token
        host bookkeeping: streaming, lifecycle timestamps, retirement.
        Multi-token stop sequences are only visible here — hitting one
        retires the request and schedules a pipeline flush, since the
        device-side active chain cannot know about it."""
        e = self._inflight.pop(0)
        if "emits" in e:                     # fused speculative round
            self._drain_spec_entry(e)
            return
        if "toks" in e:                      # multi-token horizon block
            self._drain_horizon_entry(e)
            return
        has_first = "ftok" in e
        arrs = ([e["nxt"], e["done"], e["ftok"]] if has_first
                else [e["nxt"], e["done"]])
        # a mixed tick's first-token array rides the SAME single fetch
        # as the decode outputs — zero syncs added by the mixed lane
        # analysis: ignore[sync-in-hot-path] reason=the pipeline's one sanctioned sync point: drains the OLDEST step while a newer dispatch is already in flight
        self._drain_step(e, self._fetch(*arrs), has_first)

    @RecordEvent("engine.drain")
    def _drain_step(self, e: Dict, fetched, has_first: bool) -> None:
        """The bookkeeping of one drained single-token step: delivery,
        retirement, the device active chain."""
        nxt, done = fetched[0], fetched[1]
        t0 = time.perf_counter() if self.metrics is not None else 0.0
        mask = self._drain_active
        advanced = 0
        for slot in np.nonzero(mask)[0]:
            slot = int(slot)
            req = self._active.get(slot)
            if req is None:
                # host-retired (stop sequence) after this step was
                # dispatched: its token is dead, and the scheduled
                # flush keeps the slot from being reused under it
                continue
            t = int(nxt[slot])
            self._deliver_token(slot, req, t)
            advanced += 1
            self._remaining[slot] -= 1
            if done[slot]:
                self._retire(slot)          # eos / budget (on-device)
            elif self._hit_stop(req, t):
                self._retire(slot)          # stop sequence (host-only)
                self._needs_flush = True
        # follow the DEVICE active chain: the next undrained step ran
        # with active & ~done (host-only retirements are excluded by
        # the _active lookup above until the flush lands)
        self._drain_active = mask & ~done.astype(bool)
        if has_first:
            # first tokens of segments the mixed dispatch completed:
            # deliver to the rows it activated (skipped if a cancel/
            # preemption took the row since dispatch — the re-prefill
            # will re-sample the same greedy token)
            ftok = fetched[2]
            for slot, req in e.get("mixed_first", {}).items():
                if self._active.get(slot) is not req or req.generated:
                    continue
                t = int(ftok[slot])
                self._deliver_token(slot, req, t, count=False)
                if self._hit_stop(req, t) or \
                        self._remaining[slot] <= 0:
                    # first token ended the request (eos / budget 1):
                    # host-only retirement, same flush discipline as
                    # stop sequences — the chained dispatch's extra
                    # token dies undelivered
                    self._retire(slot)
                    self._needs_flush = True
        if "activate" in e:
            # rows the mixed dispatch activated are live in every
            # LATER undrained step
            self._drain_active = self._drain_active | e["activate"]
        if self.metrics is not None:
            self.metrics.tokens_generated.inc(advanced)
            self.metrics.host_bookkeeping.observe(
                time.perf_counter() - t0)

    def _drain_horizon_entry(self, e: Dict) -> None:
        """Drain one in-flight HORIZON block: ONE blocking fetch for
        the whole ``[H, B]`` token/done block (the pipeline's
        one-fetch-per-H-tokens amortization), then the shared
        per-micro-step bookkeeping."""
        # analysis: ignore[sync-in-hot-path] reason=the pipeline's one sanctioned sync point, horizon form: ONE fetch drains a whole [H, B] block while a newer dispatch is already in flight
        toks, dones = self._fetch(e["toks"], e["dones"])
        self._drain_active = self._drain_horizon_block(
            toks, dones, self._drain_active)

    @RecordEvent("engine.drain")
    def _drain_horizon_block(self, toks, dones, mask):
        """Per-token host bookkeeping for one fetched horizon block —
        shared by the overlap drain and the synchronous horizon lane
        so their emission/retirement/trim behaviour can never fork.
        ``mask`` is the device-active mask at the block's dispatch;
        returns the mask after the block (device chain: rows drop at
        their on-device done, host-only stop retirements stay in the
        mask exactly like the single-step lane — the scheduled flush
        keeps their slots from being reused under the pipeline).

        Host-only stop sequences fire mid-block: the row retires at
        the stop and the tokens the device over-generated past it
        (at most H-1, fewer when its on-device eos/budget done fired
        first) are DISCARDED before emission and counted in
        ``horizon_trimmed_tokens`` — the chained-dispatch extra-token
        discipline, generalized from one token to the tail of the
        block."""
        t0 = time.perf_counter() if self.metrics is not None else 0.0
        H = toks.shape[0]
        advanced = 0
        trimmed = 0
        out_mask = mask.copy()
        for slot in np.nonzero(mask)[0]:
            slot = int(slot)
            dcol = dones[:, slot]
            nd = np.nonzero(dcol)[0]
            # the row generated up to and including its first
            # on-device done (eos/budget); after it the column repeats
            # the last token (the advance holds inactive rows)
            n_gen = (int(nd[0]) + 1) if nd.size else H
            device_done = nd.size > 0
            if device_done:
                out_mask[slot] = False   # the device chain dropped it
            req = self._active.get(slot)
            if req is None:
                # host-retired (stop sequence / cancel sweep) before
                # this block drained: its tokens are dead; the
                # scheduled flush keeps the slot from being reused
                # under the in-flight pipeline
                continue
            col = toks[:, slot]
            if req.stop_sequences:
                # stop-sequence rows deliver token-by-token so a stop
                # retires the row exactly where the H=1 lane would,
                # discarding (and counting) the device's
                # over-generated tail
                for h in range(n_gen):
                    t = int(col[h])
                    self._deliver_token(slot, req, t)
                    advanced += 1
                    self._remaining[slot] -= 1
                    if h == n_gen - 1 and device_done:
                        self._retire(slot)   # eos/budget (on-device)
                    elif self._hit_stop(req, t):
                        self._retire(slot)   # stop seq (host-only)
                        if self.overlap:
                            self._needs_flush = True
                        trimmed += n_gen - 1 - h
                        break
                continue
            # FAST PATH (no stop sequences): the whole column delivers
            # as one bulk append/extend — per-token Python machinery
            # (call into _deliver_token, tail scans, mask rebuilds) is
            # exactly the host overhead the horizon exists to
            # amortize, so the common case must not pay it per token
            toks_list = col[:n_gen].tolist()
            req.generated.extend(toks_list)
            self.tokens_generated += n_gen
            advanced += n_gen
            self._note_first_token(req)
            rid = req.rid
            self._stream.extend((rid, t) for t in toks_list)
            self._next_tok[slot] = toks_list[-1]
            self._remaining[slot] -= n_gen
            if device_done:
                self._retire(slot)           # eos/budget (on-device)
        mask = out_mask
        if trimmed:
            self.horizon_trimmed_tokens += trimmed
            if self.metrics is not None:
                self.metrics.horizon_trimmed_tokens.inc(trimmed)
        if self.metrics is not None:
            self.metrics.tokens_generated.inc(advanced)
            self.metrics.decode_horizon_tokens.observe(advanced)
            self.metrics.host_bookkeeping.observe(
                time.perf_counter() - t0)
        return mask

    def _decode_sync_multi(self) -> None:
        """The synchronous horizon lane: one H-micro-step dispatch +
        ONE blocking fetch per tick — H tokens per blocking host
        round-trip instead of one (``overlap=False``,
        ``decode_horizon > 1``)."""
        with RecordEvent("engine.dispatch"):
            cache = self.cache
            self._ensure_or_preempt(self.decode_horizon)
            tables = jnp.asarray(cache.tables.copy())
            lens = jnp.asarray(cache.lens.copy())
            tok = jnp.asarray(self._next_tok.copy())
            active = jnp.asarray(self._active_mask.astype(bool))
            remaining = jnp.asarray(self._remaining.copy())
            self._key, sub = jax.random.split(self._key)
            faults.fire("step_dispatch")
            if cache.kv_quant == "int8":
                (cache.kpool, cache.vpool, cache.kscale, cache.vscale,
                 toks, dones, _, _, _, _) = self._step_multi(
                    self.params, cache.kpool, cache.vpool, cache.kscale,
                    cache.vscale, tables, lens, tok, active, remaining,
                    self._eos_dev, sub)
            else:
                (cache.kpool, cache.vpool, toks, dones, _, _, _,
                 _) = self._step_multi(
                    self.params, cache.kpool, cache.vpool, tables, lens,
                    tok, active, remaining, self._eos_dev, sub)
            # mirror the full horizon; retirements below zero the rows
            # that stopped mid-block (same self-healing as the overlap
            # mirror — here the very next lines heal it)
            cache.lens = cache.lens + (self.decode_horizon
                                       * self._active_mask)
            self.decode_steps += 1
            self._count_tp_dispatch(self.decode_horizon)
            if self.metrics is not None:
                self.metrics.decode_steps.inc()
        mask = self._active_mask.astype(bool)
        # analysis: ignore[sync-in-hot-path] reason=the synchronous horizon lane's ONE blocking fetch per H-token tick (overlap=False) — the amortized counterpart of _decode_sync's per-token round-trip
        toks, dones = self._fetch(toks, dones)
        self._drain_horizon_block(toks, dones, mask)

    # -- fused speculative lane (spec=SpecConfig(...)) --------------------
    def _spec_fused(self):
        """The fused draft+verify program for the CURRENT gamma.
        :func:`make_spec_step` memoises per (cfg, gamma, quant, mesh)
        — adaptive retunes pay one compile per distinct gamma, then
        hit the cache."""
        spec = self._spec
        return make_spec_step(
            self.cfg, self.gamma,
            draft_cfg=self._spec_dcfg if spec.source == "draft"
            else None,
            kv_quant=self.cache.kv_quant,
            draft_kv_quant=(self._spec_dcache.kv_quant
                            if self._spec_dcache is not None
                            else None),
            mesh=self.mesh, tp_allreduce=self.tp_allreduce)

    def _count_spec_tp(self, C: int) -> None:
        """Collective-traffic accounting for one fused speculative
        round: C verify tokens reduce exact-fp, C draft micro-steps
        reduce in the engine's ``tp_allreduce`` mode (prompt-lookup
        rounds have no draft half).  No-op off-mesh."""
        if not self._tp:
            return
        self._count_tp_dispatch(
            1, self._tp_bytes_spec_verify * C
            + self._tp_bytes_spec_draft * C)

    def _propose_lookup(self) -> np.ndarray:
        """PROMPT-LOOKUP drafting: match each spec-on row's last
        ``ngram`` committed tokens against its own history and
        propose the continuation of the EARLIEST prior occurrence.
        A miss proposes nothing (zeros) — the verify rejects them and
        the row still commits its one exact greedy token, so a bad
        proposal only ever costs acceptance."""
        G = self.gamma
        n = self._spec.ngram
        out = np.zeros((self.B, G), np.int64)
        for slot in self._active:
            if not self._spec_on[slot]:
                continue
            seq = self._spec_seq.get(slot)
            if seq is None or len(seq) <= n:
                continue
            idx = self._spec_ngrams[slot].get(tuple(seq[-n:]))
            if idx is None:
                continue
            cand = seq[idx:idx + G]
            out[slot, :len(cand)] = cand
        return out

    def _spec_note_tokens(self, slot: int, toks_list) -> None:
        """Extend a prompt-lookup row's history + n-gram table with
        the round's committed tokens (first occurrence wins, matching
        the admission-time build)."""
        seq = self._spec_seq.get(slot)
        if seq is None:
            return
        tab = self._spec_ngrams[slot]
        n = self._spec.ngram
        start = max(len(seq), n)
        seq.extend(int(t) for t in toks_list)
        for i in range(start, len(seq)):
            tab.setdefault(tuple(seq[i - n:i]), i)

    def _spec_dispatch_args(self, fused_inputs: Dict):
        """Assemble the fused step's positional args from a dict of
        device inputs — ONE place owns the (draft, q8, dq8) layout
        for the sync and overlap lanes alike."""
        cache, dcache = self.cache, self._spec_dcache
        q8 = cache.kv_quant == "int8"
        args = [self.params]
        if self._spec.source == "draft":
            args.append(self._spec_dparams)
        args += [cache.kpool, cache.vpool]
        if q8:
            args += [cache.kscale, cache.vscale]
        if self._spec.source == "draft":
            args += [dcache.kpool, dcache.vpool]
            if dcache.kv_quant == "int8":
                args += [dcache.kscale, dcache.vscale]
        args.append(fused_inputs["tables"])
        if self._spec.source == "draft":
            args.append(fused_inputs["dtables"])
        args += [fused_inputs["lens"], fused_inputs["tok"]]
        if self._spec.source == "draft":
            args.append(fused_inputs["prev"])
        else:
            args.append(fused_inputs["drafts"])
        args += [fused_inputs["active"], fused_inputs["remaining"],
                 fused_inputs["spec_on"], self._eos_dev,
                 fused_inputs["key"]]
        return args

    def _spec_unpack(self, rets):
        """Split the fused step's outputs: reassign the donated pools
        (+scales), return (toks, dones, emits, accepts, chain) where
        ``chain`` is the on-device loop state (tok', [prev',] lens',
        remaining', active') for the overlap lane to feed the next
        dispatch."""
        cache, dcache = self.cache, self._spec_dcache
        q8 = cache.kv_quant == "int8"
        cache.kpool, cache.vpool = rets[0], rets[1]
        i = 2
        if q8:
            cache.kscale, cache.vscale = rets[2], rets[3]
            i = 4
        if self._spec.source == "draft":
            dcache.kpool, dcache.vpool = rets[i], rets[i + 1]
            i += 2
            if dcache.kv_quant == "int8":
                dcache.kscale, dcache.vscale = rets[i], rets[i + 1]
                i += 2
        toks, dones, emits, accs = rets[i:i + 4]
        return toks, dones, emits, accs, rets[i + 4:]

    def _decode_spec_sync(self) -> None:
        """One fused speculative round, synchronous cadence: ONE
        dispatch runs the gamma-iteration draft scan (or takes the
        host's prompt-lookup proposals) AND the batched target
        verify, ONE blocking fetch drains up to gamma+1 committed
        tokens per row.  Also the overlap engine's prompt-lookup
        cadence — the host proposer needs the round's committed
        tokens before it can draft the next, so lookup rounds cannot
        run ahead of the drain."""
        if self._needs_flush:    # lookup-on-overlap-engine stop/preempt
            self._pipeline_flush()
        with RecordEvent("engine.dispatch"):
            cache, dcache = self.cache, self._spec_dcache
            G = self.gamma
            C = G + 1
            self._ensure_or_preempt(C, aux_cache=dcache, aux_new=C,
                                    aux_rows=self._spec_on)
            fused = self._spec_fused()
            self._key, sub = jax.random.split(self._key)
            mask = self._active_mask.astype(bool)
            spec_rows = mask & self._spec_on
            inputs = {
                "tables": jnp.asarray(cache.tables.copy()),
                "lens": jnp.asarray(cache.lens.copy()),
                "tok": jnp.asarray(self._next_tok.copy()),
                "active": jnp.asarray(mask),
                "remaining": jnp.asarray(self._remaining.copy()),
                "spec_on": jnp.asarray(self._spec_on.copy()),
                "key": sub,
            }
            if self._spec.source == "draft":
                inputs["dtables"] = jnp.asarray(dcache.tables.copy())
                inputs["prev"] = jnp.asarray(self._prev_tok.copy())
            else:
                inputs["drafts"] = jnp.asarray(self._propose_lookup())
            faults.fire("step_dispatch")
            rets = fused(*self._spec_dispatch_args(inputs))
            toks, dones, emits, accs, _ = self._spec_unpack(rets)
            # mirror the worst case (C per live row, draft rows too); the
            # drain corrects each row to its actual commit count
            cache.lens = cache.lens + C * self._active_mask
            if dcache is not None:
                dcache.lens = dcache.lens + C * spec_rows.astype(
                    dcache.lens.dtype)
            self.decode_steps += 1
            self._count_spec_tp(C)
            if self.metrics is not None:
                self.metrics.decode_steps.inc()
        # analysis: ignore[sync-in-hot-path] reason=the synchronous speculative lane's ONE blocking fetch per round — the fused-round counterpart of _decode_sync's per-token round-trip
        toks, dones, emits, accs = self._fetch(toks, dones, emits,
                                               accs)
        self._drain_spec_block(toks, dones, emits, accs, mask)

    def _decode_spec_overlap(self) -> None:
        """One turn of the dispatch-ahead pipeline in speculative
        form (``source='draft'`` only): round k+1's dispatch chains
        round k's ON-DEVICE accepted-token state (tok'/prev'/lens'/
        remaining'/active') with zero host round-trips, and the host
        drains round k's committed block while k+1 runs."""
        if self._needs_flush:
            self._pipeline_flush()
        if self._active:
            self._ensure_or_preempt(self.gamma + 1,
                                    aux_cache=self._spec_dcache,
                                    aux_new=self.gamma + 1,
                                    aux_rows=self._spec_on)
            if self._needs_flush:          # a preemption landed
                self._pipeline_flush()
            if self._active:
                self._dispatch_spec_async()
        if self._active and len(self._inflight) > self.lookahead:
            self._drain_one()
        if not self._active and self._inflight:
            while self._inflight:
                self._drain_one()
            self._dev = None

    @RecordEvent("engine.dispatch")
    def _dispatch_spec_async(self) -> None:
        """Issue one fused speculative round chained off the
        device-resident loop state (zero blocking host work — same
        discipline as :meth:`_dispatch_async`)."""
        cache, dcache = self.cache, self._spec_dcache
        C = self.gamma + 1
        fused = self._spec_fused()
        d = self._seed_or_refresh_dev()
        self._key, sub = jax.random.split(self._key)
        spec_rows = self._active_mask.astype(bool) & self._spec_on
        inputs = {
            "tables": d["tables"], "dtables": d["dtables"],
            "lens": d["lens"], "tok": d["tok"], "prev": d["prev"],
            "active": d["active"], "remaining": d["remaining"],
            "spec_on": d["spec_on"], "key": sub,
        }
        faults.fire("step_dispatch")
        rets = fused(*self._spec_dispatch_args(inputs))
        toks, dones, emits, accs, chain = self._spec_unpack(rets)
        tok_f, prev_f, lens_f, rem_f, act_f = chain
        d["tok"], d["prev"] = tok_f, prev_f
        d["lens"], d["remaining"], d["active"] = lens_f, rem_f, act_f
        self._inflight.append({"toks": toks, "dones": dones,
                               "emits": emits, "accepts": accs})
        # mirror the worst case; each drain corrects its round's rows
        cache.lens = cache.lens + C * self._active_mask
        dcache.lens = dcache.lens + C * spec_rows.astype(
            dcache.lens.dtype)
        self.decode_steps += 1
        self._count_spec_tp(C)
        if self.metrics is not None:
            self.metrics.decode_steps.inc()

    def _drain_spec_entry(self, e: Dict) -> None:
        """Drain one in-flight speculative round: ONE blocking fetch
        for the whole committed block + accept counts."""
        # analysis: ignore[sync-in-hot-path] reason=the pipeline's one sanctioned sync point, speculative form: ONE fetch drains a whole [gamma+1, B] committed block while a newer round is already in flight
        toks, dones, emits, accs = self._fetch(
            e["toks"], e["dones"], e["emits"], e["accepts"])
        self._drain_active = self._drain_spec_block(
            toks, dones, emits, accs, self._drain_active)

    @RecordEvent("engine.drain")
    def _drain_spec_block(self, toks, dones, emits, accs, mask):
        """Host bookkeeping for one fetched speculative round —
        shared by the sync lane and the overlap drain so emission /
        retirement / trim behaviour can never fork.  ``toks`` /
        ``dones`` / ``emits`` are ``[C, B]`` micro-step arrays
        (committed token, just-retired mask, validity window) and
        ``accs`` the raw per-row accepted-draft counts; ``mask`` is
        the device-active mask at dispatch.  Per row, the round
        committed ``n_emit = emits[:, slot].sum()`` tokens; the
        worst-case lens mirror advance (gamma+1 at dispatch) is
        corrected here to the actual count.  Host-only stop
        sequences trim the over-committed tail exactly like the
        horizon drain (counted in ``horizon_trimmed_tokens``)."""
        t0 = time.perf_counter() if self.metrics is not None else 0.0
        cache, dcache = self.cache, self._spec_dcache
        C = toks.shape[0]
        G = C - 1
        lookup = self._spec.source == "prompt_lookup"
        # drafted accounting from the DEVICE-chain mask, not the
        # dispatch-time host mask: the overlap pipeline's last rounds
        # chain past every row's on-device done (phantom rounds whose
        # drafts are masked to junk) and must not inflate the
        # denominator of the acceptance ratio
        n_spec = int((mask & self._spec_on).sum())
        advanced = 0
        trimmed = 0
        acc_round = 0
        out_mask = mask.copy()
        for slot in np.nonzero(mask)[0]:
            slot = int(slot)
            ecol = emits[:, slot]
            n_emit = int(ecol.sum())
            device_done = bool(dones[:n_emit, slot].any())
            if device_done:
                out_mask[slot] = False   # the device chain dropped it
            req = self._active.get(slot)
            if req is not None and n_emit > 0:
                # worst-case mirror (C at dispatch) -> actual commit
                cache.lens[slot] -= C - n_emit
                if dcache is not None and self._spec_on[slot]:
                    dcache.lens[slot] = cache.lens[slot]
            if req is None or n_emit == 0:
                # host-retired (stop sequence / cancel sweep) before
                # this round drained: its tokens are dead; the
                # scheduled flush keeps the slot from being reused
                # under the in-flight pipeline
                continue
            if self._spec_on[slot]:
                k = int(accs[slot])
                acc_round += k
                self._accept_ema = 0.8 * self._accept_ema + 0.2 * k
                if self.metrics is not None:
                    self.metrics.spec_accept_len.observe(k)
            col = toks[:, slot]
            # prev mirror BEFORE _next_tok moves: the second-to-last
            # committed token overall (the draft catch-up feed)
            if n_emit >= 2:
                self._prev_tok[slot] = int(col[n_emit - 2])
            else:
                self._prev_tok[slot] = int(self._next_tok[slot])
            if lookup:
                self._spec_note_tokens(slot, col[:n_emit])
            if req.stop_sequences:
                # stop-sequence rows deliver token-by-token so a stop
                # retires the row exactly where the plain lane would,
                # discarding (and counting) the over-committed tail
                for h in range(n_emit):
                    t = int(col[h])
                    self._deliver_token(slot, req, t)
                    advanced += 1
                    self._remaining[slot] -= 1
                    if h == n_emit - 1 and device_done:
                        self._retire(slot)   # eos/budget (on-device)
                    elif self._hit_stop(req, t):
                        self._retire(slot)   # stop seq (host-only)
                        if self._inflight or self._dev is not None:
                            self._needs_flush = True
                        trimmed += n_emit - 1 - h
                        break
                continue
            # FAST PATH (no stop sequences): bulk append/extend —
            # per-token Python machinery is exactly the host overhead
            # the fused round exists to amortize
            toks_list = col[:n_emit].tolist()
            req.generated.extend(toks_list)
            self.tokens_generated += n_emit
            advanced += n_emit
            self._note_first_token(req)
            rid = req.rid
            self._stream.extend((rid, t) for t in toks_list)
            self._next_tok[slot] = toks_list[-1]
            self._remaining[slot] -= n_emit
            if device_done:
                self._retire(slot)           # eos/budget (on-device)
        if n_spec:
            self.spec_rounds += 1
            self.spec_drafted += G * n_spec
            self.spec_accepted += acc_round
            if self.adaptive_gamma:
                self._spec_retune()
            if self.metrics is not None:
                m = self.metrics
                m.spec_rounds.inc()
                m.spec_drafted_tokens.inc(G * n_spec)
                m.spec_accepted_tokens.inc(acc_round)
                m.spec_gamma.set(self.gamma)  # post-retune = next
                m.spec_acceptance.set(
                    self.spec_accepted / max(self.spec_drafted, 1))
        if trimmed:
            self.horizon_trimmed_tokens += trimmed
            if self.metrics is not None:
                self.metrics.horizon_trimmed_tokens.inc(trimmed)
        if self.metrics is not None:
            self.metrics.tokens_generated.inc(advanced)
            self.metrics.host_bookkeeping.observe(
                time.perf_counter() - t0)
        return out_mask

    def _spec_retune(self) -> None:
        """Adaptive gamma for the NEXT round, from the acceptance
        EMA: shrink when drafts keep missing, grow when they keep
        landing.  Each distinct gamma compiles one fused program
        (make_spec_step memoises) — a bounded one-time cost per
        value, amortized across every later round at that gamma."""
        if self._accept_ema < 0.4 * self.gamma and self.gamma > 1:
            self.gamma -= 1
        elif self._accept_ema > 0.85 * self.gamma and \
                self.gamma < self.max_gamma:
            self.gamma += 1

    def _pipeline_flush(self) -> None:
        """Drain every in-flight dispatch and invalidate the
        device-resident loop state.  Called at every scheduler
        mutation point — admission, preemption, stop-sequence
        retirement — after which the host arrays are authoritative
        and the next dispatch re-seeds the device from them."""
        if not self._inflight and self._dev is None \
                and not self._needs_flush:
            return
        while self._inflight:
            self._drain_one()
        if self.cache.host is not None:
            # scheduler-mutation point: commit staged swap-out copies
            # (they rode under the drained dispatches) into host RAM
            self.cache.host.flush()
        self._dev = None
        self._needs_flush = False
        self.pipeline_flushes += 1

    def run_to_completion(self, max_steps: int = 10_000):
        """Drive until the queue drains; returns all finished requests
        in completion order."""
        return _drive_to_completion(self, max_steps)


class EngineSupervisor:
    """Crash-recovery wrapper over :class:`ContinuousBatchingEngine`:
    drive it through :meth:`step` and, when a step exception ESCAPES
    the engine's own wave quarantine (consecutive-fault escalation, a
    poisoned allocator, device OOM), the supervisor rebuilds the
    engine from ``factory`` and carries the still-live work over —
    queued requests transplant with their rids/deadlines/timestamps
    intact (swapped-out ones degrade to recompute resumes: their
    host-tier records died with the old cache), active requests retire
    with an error done-message (their device pages are gone), and
    un-drained ``finished()`` results survive the swap.

    Restart budget: ``max_restarts`` within a sliding ``window_s``,
    each preceded by an exponential ``backoff_s * 2**k`` sleep
    (``backoff_s=0`` disables sleeping — tests observe restarts
    through the counters, never through time).  Past the budget
    :class:`EngineDeadError` raises and the serving front fails
    pending requests loudly.

    Lifecycle: ``state`` reports ``READY`` / ``DRAINING`` / ``DEAD``;
    :meth:`drain` stops admission while in-flight work finishes
    (``drained`` flips True, readiness probes report false so traffic
    routes elsewhere) and :meth:`resume` re-opens it.  The fleet
    router (``paddle_tpu/fleet``) drives these verbs per replica and
    steers around every non-READY state.

    ``factory()`` must return a fresh engine; if it reuses a cache
    object, the supervisor best-effort releases the dead engine's rows
    and swap records first so page accounting starts clean (verified
    by ``PagedKVCache.audit()`` in tests)."""

    def __init__(self, factory, max_restarts: int = 3,
                 window_s: float = 60.0, backoff_s: float = 0.05):
        self._factory = factory
        self.engine: ContinuousBatchingEngine = factory()
        self.max_restarts = int(max_restarts)
        self.window_s = float(window_s)
        self.backoff_s = float(backoff_s)
        self.restarts = 0
        self._restart_times: deque = deque()
        self._draining = False
        self._dead = False

    # -- lifecycle (the fleet router's replica verbs; serving fronts
    #    read `state` for readiness) --------------------------------------
    @property
    def state(self) -> str:
        """``READY`` (serving), ``DRAINING`` (finishing in-flight
        work, refusing new submissions — readiness probes report
        false so load balancers pull the node out of rotation), or
        ``DEAD`` (restart budget exhausted; only a rebuild/replace
        helps)."""
        if self._dead:
            return "DEAD"
        if self._draining:
            return "DRAINING"
        return "READY"

    def drain(self) -> None:
        """Stop admitting: ``submit()`` raises while ``step()`` keeps
        finishing queued + active work.  ``drained`` turns True once
        nothing is left — the caller then restarts/replaces the engine
        (a fleet router does) or :meth:`resume`\\ s admission."""
        self._draining = True

    def resume(self) -> None:
        """Re-open admission after a :meth:`drain` (maintenance done
        without a rebuild)."""
        self._draining = False

    @property
    def drained(self) -> bool:
        """True once a drain has finished its in-flight work."""
        return self._draining and not self.engine.has_work()

    # -- engine API passthrough (the serving front drives these) ----------
    def submit(self, *a, **kw) -> int:
        if self._dead:
            raise EngineDeadError(
                "engine dead: restart budget exhausted")
        if self._draining:
            raise RuntimeError(
                "engine draining: not admitting new requests (the "
                "in-flight work is finishing; restart/replace or "
                "resume() follows)")
        return self.engine.submit(*a, **kw)

    def cancel(self, rid: int) -> bool:
        return self.engine.cancel(rid)

    def finished(self) -> List[Request]:
        return self.engine.finished()

    def drain_stream(self) -> List:
        return self.engine.drain_stream()

    def has_work(self) -> bool:
        return self.engine.has_work()

    def step(self) -> int:
        try:
            return self.engine.step()
        except Exception as exc:
            self._restart(exc)
            return len(self.engine._active)

    def run_to_completion(self, max_steps: int = 10_000):
        return _drive_to_completion(self, max_steps)

    def _restart(self, exc: BaseException) -> None:
        now = time.monotonic()
        while self._restart_times and \
                now - self._restart_times[0] > self.window_s:
            self._restart_times.popleft()
        if len(self._restart_times) >= self.max_restarts:
            self._dead = True
            raise EngineDeadError(
                f"engine unrecoverable after {self.restarts} "
                f"restart(s) ({len(self._restart_times)} in the last "
                f"{self.window_s:.0f}s): {type(exc).__name__}: {exc}"
            ) from exc
        if self.backoff_s > 0:
            time.sleep(self.backoff_s
                       * (2 ** len(self._restart_times)))
        old = self.engine
        text = f"{type(exc).__name__}: {exc}"
        _release_engine_claims(old)
        new = self._factory()
        if getattr(new, "tracer", None) is None:
            # factory-built engines rarely carry a tracer: keep the
            # serving front's tracing alive across restarts
            new.tracer = old.tracer
        # results the serving front has not drained yet survive
        new._finished.extend(old._finished)
        old._finished = []
        # active requests died with their pages: error done-message
        for slot, req in list(old._active.items()):
            req.done, req.status, req.error = True, "error", text
            req.t_finish = time.monotonic()
            new._count_abnormal(req, "error")
            _finalize_trace(req)
            new._finished.append(req)
        old._active.clear()
        # requests the fatal step had popped off the queue but not yet
        # committed to _active (admission-phase death) fail loudly too
        # — never dropped with the dead engine
        for req in old._admitting:
            if req.done or any(q is req for q in old._queue):
                continue
            req.done, req.status, req.error = True, "error", text
            req.t_finish = time.monotonic()
            new._count_abnormal(req, "error")
            _finalize_trace(req)
            new._finished.append(req)
        old._admitting = []
        # mixed-lane rows mid-prefill died with their pages (partial
        # context K/V is gone): error done-message, never dropped
        for ent in getattr(old, "_mixed_pref", {}).values():
            req = ent["req"]
            if req.done:
                continue
            req.done, req.status, req.error = True, "error", text
            req.t_finish = time.monotonic()
            new._count_abnormal(req, "error")
            _finalize_trace(req)
            new._finished.append(req)
        if hasattr(old, "_mixed_pref"):
            old._mixed_pref.clear()
        # still-live queued requests transplant (rids preserved);
        # cancelled/expired ones retire on the way over
        for req in old._queue:
            req.slot = None
            if req.rid in old._cancelled:
                new._finish_queued_abnormal(req, "cancelled")
            elif req.deadline and new._now() >= req.deadline:
                new._finish_queued_abnormal(req, "expired")
            else:
                new._queue.append(req)
                if req.deadline:
                    new._has_deadlines = True
                if req.priority != "normal":
                    new._has_priorities = True
        old._queue.clear()
        new._next_rid = max(new._next_rid, old._next_rid)
        # engines carrying cross-engine state (the disagg DecodeEngine's
        # adopted-but-unadmitted KV handoffs, the PrefillEngine's
        # exported-but-untaken records) re-register / fail it here — a
        # rebuilt decode engine must not strand the prefill side's
        # half of an in-flight handoff until its deadline
        hook = getattr(new, "transplant_extra", None)
        if hook is not None:
            hook(old)
        new.last_fault = text
        self.engine = new
        self._restart_times.append(now)
        self.restarts += 1
        if new.metrics is not None:
            new.metrics.engine_restarts.inc()
            new.metrics.ring.emit("engine_restart", error=text,
                                  restarts=self.restarts)
