"""Disaggregated prefill/decode serving: split the compute-bound and
memory-bound phases onto separate engines with a pipelined KV handoff.

The problem (ROADMAP item 3): on a unified engine every admission wave
— a compute-bound packed prefill over every waiting prompt — runs on
the same device as the decode loop, so each wave stalls the decode
pipeline and inflates TPOT p99 exactly when load is highest.  The
production fix (vLLM/Mooncake-style) is to SPLIT them:

* :class:`PrefillEngine` — a :class:`~paddle_tpu.models.
  serving_engine.ContinuousBatchingEngine` whose "decode" is an
  EXPORT: it runs packed varlen admission waves exactly as before
  (one jitted dispatch per wave, single-device or
  ``_prefill_packed_tp`` on a mesh, prefix caching included), samples
  each context's first token from the shared logits tail, then ships
  the finished rows out as :class:`HandoffRecord`\\ s instead of
  decoding them.  The export stages through the host tier's async
  D2H path (``PagedKVCache.export_row`` — the same per-shard
  ``copy_to_host_async`` discipline swap-out uses), so the copy
  rides under neighbouring dispatches, T3-style.
* :class:`DecodeEngine` — an engine that admits handoffs exclusively
  through the ``_admit_swapped`` path: the record ADOPTS into its
  cache's host tier (``PagedKVCache.adopt_swap``) and re-admission is
  ONE batched restore scatter with ZERO prefill tokens — the exact
  machinery preemption resume already trusts, bitwise-audited.  A
  decode engine serving pure disagg traffic never runs a prefill
  dispatch (pinned by counters in tests/test_disagg.py).
* :class:`DisaggCoordinator` — the in-process 1P+1D pipeline (the
  fleet-tier N:M form is :class:`~paddle_tpu.fleet.FleetRouter` with
  ``roles=``): drives both engines through the engine-compatible
  ``submit``/``step``/``finished`` surface, PIPELINES the handoff —
  wave *k*'s staged copies materialise one tick later, after wave
  *k+1*'s prefill dispatch and the neighbouring decode dispatches
  have ridden over them — bounds the in-flight handoff queue (which
  backpressures prefill admission), and routes each request through
  the PR-4 bytes-vs-FLOPs cost model: short prompts stay colocated
  on the decode engine (the stall is cheaper than shipping pages);
  the decision is a counter, not a guess.

Degradation (docs/FAULT_TOLERANCE.md): an injected ``kv_handoff``
fault — ship half (record materialisation) or restore half (decode
adopt) — degrades the request to a COLOCATED re-prefill on the decode
side, token-exact, preserving the already-sampled first token; the
receiving host tier running full degrades the same way; orphaned
records from a dead prefill engine are reclaimed through
``release_extra_claims`` (audit-clean, never leaked); and an
``EngineSupervisor`` restart of a decode engine re-registers its half
of every in-flight handoff through ``transplant_extra``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from ..observability import (DisaggMetrics, advance_phase,
                             finalize_request_trace, phase_clocks)
from ..testing import faults
from .paged_decode import PagedKVCache
from .serving_engine import (ContinuousBatchingEngine, QueueFullError,
                             Request, _drive_to_completion,
                             _finalize_trace)

__all__ = ["DisaggCoordinator", "DecodeEngine", "HandoffRecord",
           "PrefillEngine", "handoff_flip_gbps", "handoff_wins"]


@dataclass
class HandoffRecord:
    """One finished prefill context in flight to a decode engine: the
    request (carrying its sampled first token in ``generated``), the
    source cache whose host tier holds the staged pages, and the
    opaque export state.  ``materialize()`` is the SHIP half of the
    ``kv_handoff`` fault site (the staging flush that commits the
    async D2H copies); the RESTORE half fires in
    :meth:`DecodeEngine.admit_handoff`."""

    request: Request
    cache: PagedKVCache               # source cache (staging tier)
    export: dict
    pages: int
    nbytes: int
    blobs: Optional[tuple] = None     # (k, v, ks, vs, L) once fetched

    def materialize(self) -> tuple:
        """Fetch the shipped pages as portable numpy blocks (idempotent
        — a retry after decode-side backpressure reuses the fetched
        blobs; the staging host pages freed at the first fetch)."""
        if self.blobs is None:
            faults.fire("kv_handoff")          # SHIP half
            self.blobs = self.cache.export_fetch(self.export)
        return self.blobs

    def discard(self) -> None:
        """Reclaim the record without shipping it (cancel/expiry/
        degrade/death): staging host pages free; idempotent."""
        if self.blobs is None:
            self.cache.export_discard(self.export)
        self.blobs = None


def handoff_wins(prompt_len: int, decode_engine, gbps: float,
                 chip_flops: Optional[float] = None) -> bool:
    """The PR-4 bytes-vs-FLOPs cost model applied to ADMISSION:
    disaggregate when the prefill stall the decode device would pay
    (one forward pass over the context, ~2*N_params FLOPs/token at the
    chip's rate) exceeds the handoff DMA (ship + restore = 2x the
    context's page bytes at ``gbps``).  Short prompts lose: their
    stall is cheaper than moving their pages, so they stay colocated.
    Chip-rate and parameter-count defaults are the SAME helpers the
    preemption cost model uses (serving_engine) — the two models can
    never disagree about the hardware.

    A MIXED-CAPABLE colocated lane (``decode_engine`` built with
    ``mixed=True``, serving_engine's token-budget piggybacking) pays
    NO admission stall — its prefill tokens ride inside the decode
    dispatches — so there is no stall for disaggregation to delete
    and the handoff DMA is pure cost: every request colocates
    (``handoff_flip_gbps`` reads ``inf``)."""
    return gbps > handoff_flip_gbps(prompt_len, decode_engine,
                                    chip_flops)


def handoff_flip_gbps(prompt_len: int, decode_engine,
                      chip_flops: Optional[float] = None) -> float:
    """The link speed at which :func:`handoff_wins` flips for this
    prompt length — strictly above it, disaggregation wins.  Owns the
    inversion of the cost-model arithmetic in one place: tests
    calibrate split-inducing ``handoff_gbps`` knobs from it instead of
    re-deriving the algebra."""
    from .serving_engine import _chip_flops_default, _count_params

    if prompt_len <= 0:
        # a zero-length context has no prefill stall to avoid: no
        # finite link speed makes disaggregation win (readiness
        # probes ask with prompt_len=0)
        return float("inf")
    if getattr(decode_engine, "_mixed", False):
        # a mixed-capable lane admits WITHOUT stalling decode
        # (token-budget piggybacking): the stall term of the
        # inequality is zero, so no finite link speed makes the
        # handoff DMA worth paying
        return float("inf")
    cache = decode_engine.cache
    npg = (int(prompt_len) + cache.page - 1) // cache.page
    if decode_engine._n_params is None:
        decode_engine._n_params = _count_params(decode_engine.params)
    chip = chip_flops if chip_flops is not None \
        else _chip_flops_default()
    # solve prefill_s > handoff_s for gbps:
    #   2*N*L/chip  >  2*npg*page_bytes/(gbps*1e9)
    return (npg * cache.page_bytes * chip
            / (decode_engine._n_params * prompt_len * 1e9))


class PrefillEngine(ContinuousBatchingEngine):
    """The compute-bound half of a disaggregated pair: admission waves
    run exactly as on a unified engine (packed varlen lane by default,
    one dispatch per wave, TP mesh / chunked / batched lanes
    included), but instead of decoding, every slot the wave filled
    EXPORTS — its pages stage to the host tier (async D2H), its
    request (first token sampled) wraps into a :class:`HandoffRecord`
    awaiting :meth:`take_handoffs`.  ``decode_steps`` stays 0 by
    construction.

    ``max_inflight_handoffs`` bounds the records waiting to be taken
    PLUS whatever the owning coordinator reports in flight
    (``handoff_backlog`` is a seam the coordinator re-points at its
    pipeline-wide count): a full queue stalls ADMISSION — queued
    requests wait, backpressure flows to ``submit()``'s bounded queue
    — it never drops work.

    ``overlap=True`` is rejected: there is no decode loop to overlap,
    and the dispatch-ahead machinery would only add flush points."""

    def __init__(self, *args, max_inflight_handoffs: int = 8, **kw):
        if kw.get("overlap"):
            raise ValueError(
                "PrefillEngine has no decode loop to overlap "
                "(overlap=True applies to the DecodeEngine of a "
                "disaggregated pair)")
        if kw.get("mixed"):
            raise ValueError(
                "PrefillEngine has no decode rows to piggyback on "
                "(mixed=True deletes the stall a COLOCATED engine "
                "pays; a disaggregated prefill engine has no stall "
                "to delete — see handoff_wins)")
        if int(kw.get("decode_horizon", 1) or 1) > 1:
            raise ValueError(
                "PrefillEngine has no decode cadence to fuse "
                "(decode_horizon amortizes per-token decode "
                "dispatches; set it on the DecodeEngine of a "
                "disaggregated pair, or on a colocated engine)")
        super().__init__(*args, **kw)
        self.max_inflight_handoffs = int(max_inflight_handoffs)
        self._handoff_ready: List[HandoffRecord] = []
        # seam: the coordinator re-points this at its pipeline-wide
        # in-flight count so the bound covers shipped-not-yet-admitted
        # records too; only ever consulted under the driver's lock
        self.handoff_backlog: Callable[[], int] = \
            lambda: len(self._handoff_ready)
        self.handoffs_exported = 0
        self.admission_stalls = 0         # waves deferred by the bound

    # -- admission gating (the bounded handoff queue's backpressure) ------
    def _collect_admissions(self):
        backlog = self.handoff_backlog()
        room = self.max_inflight_handoffs - backlog
        if room <= 0:
            self.admission_stalls += 1
            return [], []
        admits, swap_ins = super()._collect_admissions()
        # trim the wave to the queue's remaining room, returning the
        # excess to the FRONT of the queue in FIFO order
        while len(admits) + len(swap_ins) > room and admits:
            req, _ = admits.pop()
            self._queue.appendleft(req)
        return admits, swap_ins

    # -- "decode": export every slot the wave filled ----------------------
    def _decode_once(self) -> None:
        for slot in sorted(list(self._active),
                           key=lambda s: self._active[s].admit_seq):
            req = self._active.pop(slot)
            state = self.cache.export_row(slot)
            self._free_slots.append(slot)
            self._remaining[slot] = 0
            self._active_mask[slot] = 0
            req.slot = None
            rec = HandoffRecord(
                request=req, cache=self.cache, export=state,
                pages=state["pages"],
                nbytes=state["pages"] * self.cache.page_bytes)
            # the request leaves this engine: its clocks ride the
            # record to the decode side (trace-context propagation
            # across the handoff — ONE trace, stitched)
            advance_phase(req, "handoff_inflight")
            if req.trace is not None:
                req.trace.event("handoff_export", rid=req.rid,
                                pages=rec.pages)
            self._handoff_ready.append(rec)
            self.handoffs_exported += 1
            if self.metrics is not None:
                self.metrics.ring.emit(
                    "kv_handoff_export", rid=req.rid,
                    pages=rec.pages, ctx_len=state["lens"])

    def has_work(self) -> bool:
        # exported-but-untaken records ARE work: the owning
        # coordinator/router must keep ticking (and a draining
        # supervisor must not report drained) until someone takes
        # them — otherwise an idle driver strands them forever
        return bool(self._handoff_ready) or super().has_work()

    def take_handoffs(self) -> List[HandoffRecord]:
        """Drain the exported records (coordinator/router side).  The
        caller owns them from here: ship, degrade, or discard."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def release_extra_claims(self) -> None:
        """Reclaim every exported-but-untaken record's staging pages —
        called through the ``_release_engine_claims`` seam when this
        engine dies or a supervisor rebuilds it, so orphaned handoff
        records never leak host pages (``audit()``-verified).  The
        record list survives for :meth:`transplant_extra` to fail the
        requests loudly."""
        for rec in self._handoff_ready:
            try:
                rec.discard()
            except Exception:
                pass

    def transplant_extra(self, old) -> None:
        """Supervisor-restart hook: requests the dead engine had
        exported but nobody took yet fail with an error done-message
        (their pages died with the claims release) — never dropped
        silently."""
        if not isinstance(old, PrefillEngine):
            return
        for rec in old._handoff_ready:
            req = rec.request
            if req.done:
                continue
            req.done, req.status = True, "error"
            req.error = old.last_fault or \
                "prefill engine restarted mid-handoff"
            req.t_finish = time.monotonic()
            self._count_abnormal(req, "error")
            _finalize_trace(req)
            self._finished.append(req)
        old._handoff_ready = []


class DecodeEngine(ContinuousBatchingEngine):
    """The memory-bound half of a disaggregated pair: handoff records
    ADOPT into the cache's host tier and re-admit through the
    ordinary ``_admit_swapped`` path — one batched restore scatter,
    zero prefill tokens, never a prefill dispatch for disagg traffic.
    Colocated requests (short prompts the cost model keeps here, and
    degraded handoffs) still ``submit()``/prefill normally — the
    engine serves both lanes.

    Requires a host tier (``PagedKVCache(host_pages=N)``): adopted
    records park there until their restore."""

    def __init__(self, *args, **kw):
        if kw.get("mixed"):
            raise ValueError(
                "mixed=True on a DecodeEngine is unsupported: its "
                "admission overrides (_handoff_first single-emission, "
                "adopted-blob bookkeeping) do not compose with the "
                "mixed lane's in-program first-token sampling.  Run "
                "the UNIFIED engine with mixed=True instead — the "
                "cost model (handoff_wins) then keeps traffic "
                "colocated, which is the point")
        super().__init__(*args, **kw)
        if self.cache.host is None:
            raise ValueError(
                "DecodeEngine needs a host page tier "
                "(PagedKVCache(host_pages=N)): handoff records adopt "
                "there until their batched restore")
        # adopted-but-unadmitted handoffs: rid -> materialised blobs,
        # kept until admission so a supervisor restart can re-adopt
        # them into the rebuilt cache (transplant_extra)
        self._handoff_blobs: Dict[int, tuple] = {}
        # rids whose (already-sampled) first token streams at THIS
        # engine's admission — the handoff window closes there, and a
        # client must see token 1 exactly once whichever path admits
        self._handoff_first: set = set()
        self.handoff_admits = 0
        self.colocated_fallbacks = 0      # restores degraded to prefill

    def _import_request(self, src: Request) -> Request:
        """A decode-side Request mirroring the prefill-side one:
        fresh local rid, lifecycle timestamps carried over (TTFT and
        queue-wait were observed at the prefill engine and must not
        re-observe), absolute deadline intact.  Validates against
        THIS cache's row capacity — handoffs bypass ``submit()``, and
        admitting a request this pool can never hold would wedge the
        FIFO head exactly the way submit()'s guard documents (the
        prefill cache's geometry may be roomier than ours)."""
        row_cap = min(self.cache.pages_max,
                      self.cache.num_pages - 1) * self.cache.page
        worst = len(src.prompt) + src.max_new_tokens
        if worst > row_cap:
            raise ValueError(
                f"handoff request needs up to {worst} cache slots "
                f"(prompt {len(src.prompt)} + max_new_tokens "
                f"{src.max_new_tokens}) > decode-side row capacity "
                f"{row_cap} — source and destination cache "
                f"geometries disagree")
        req = Request(self._next_rid, src.prompt, src.max_new_tokens,
                      generated=list(src.generated),
                      stop_sequences=src.stop_sequences,
                      t_submit=src.t_submit or time.monotonic(),
                      t_admit=src.t_admit,
                      t_first_token=src.t_first_token,
                      deadline=src.deadline)
        # trace-context propagation: the decode-side request
        # CONTINUES the trace and phase accounting the prefill side
        # accrued — spans stitch across the two engines through the
        # HandoffRecord, so /trace/<rid> shows one tree
        req.trace = src.trace
        req.phase = src.phase
        req.t_phase = src.t_phase or req.t_submit
        req.phase_log = list(src.phase_log)
        self._next_rid += 1
        if req.deadline:
            self._has_deadlines = True
        return req

    def admit_handoff(self, rec: HandoffRecord) -> int:
        """RESTORE half of a KV handoff: adopt the record into the
        host tier and queue its request for ``_admit_swapped``
        re-admission (zero prefill tokens).  Returns the decode-local
        rid.  Raises :class:`QueueFullError` when the bounded queue
        refuses (backpressure — the caller retries next tick, blobs
        cached) and ``RuntimeError`` when the host tier cannot hold
        the pages or the ``kv_handoff`` fault fires (the caller
        degrades to :meth:`admit_degraded`)."""
        src = rec.request
        why = self.queue_capacity_reason(len(src.prompt))
        if why is not None:
            # deliberately NOT _reject(): a coordinator retry is a
            # routing event, and charging requests_rejected would
            # count 429s no client ever saw (the fleet router learned
            # this the same way)
            raise QueueFullError(why, retry_after=self.retry_after_s())
        # validate + import BEFORE claiming the host tier: a geometry
        # mismatch used to raise AFTER adopt_swap, orphaning the
        # adopted record (host pages pinned forever — caught by the
        # claim-lifecycle rule, pinned by test_claim_regressions)
        req = self._import_request(src)
        blobs = rec.materialize()
        faults.fire("kv_handoff")              # RESTORE half
        handle = self.cache.adopt_swap(*blobs)
        self._swap_handles[req.rid] = handle
        self._handoff_blobs[req.rid] = blobs
        self._handoff_first.add(req.rid)
        self._queue.append(req)
        self.handoff_admits += 1
        if self.metrics is not None:
            self.metrics.ring.emit(
                "kv_handoff_adopt", rid=req.rid, pages=rec.pages)
        return req.rid

    def admit_degraded(self, src: Request) -> int:
        """Colocated FALLBACK for a failed handoff: queue the request
        for an ordinary (re-)prefill on THIS device.  The first token
        the prefill engine already sampled is preserved in
        ``generated`` — admission resumes at it without re-sampling
        (token-exact at any temperature) and streams it exactly once;
        a request that never reached a first token (prefill side died
        pre-admission) prefills fresh."""
        why = self.queue_capacity_reason(len(src.prompt))
        if why is not None:
            raise QueueFullError(why, retry_after=self.retry_after_s())
        req = self._import_request(src)
        if req.generated:
            self._handoff_first.add(req.rid)
        self._queue.append(req)
        self.colocated_fallbacks += 1
        if self.metrics is not None:
            self.metrics.ring.emit("kv_handoff_degraded", rid=req.rid)
        return req.rid

    def pending_handoffs(self) -> int:
        """Adopted-but-unadmitted handoffs (the coordinator's
        in-flight gauge counts these)."""
        return len(self._handoff_blobs)

    # -- admission hooks --------------------------------------------------
    def _finish_admit(self, req: Request, slot: int, tok: int) -> None:
        if req.rid in self._handoff_first:
            # the handoff window closes HERE: the prefill-side first
            # token reaches the stream only once the decode side owns
            # the request (restore or degraded re-prefill alike)
            self._handoff_first.discard(req.rid)
            self._handoff_blobs.pop(req.rid, None)
            self._stream.append((req.rid, tok))
        super()._finish_admit(req, slot, tok)

    def _admit_swapped(self, req: Request) -> bool:
        ok = super()._admit_swapped(req)
        if not ok and req.rid in self._handoff_blobs:
            # device pool could not take the restore: the request
            # requeued for recompute admission = a colocated
            # re-prefill; the blobs are dead weight now
            self._handoff_blobs.pop(req.rid, None)
            self.colocated_fallbacks += 1
        return ok

    def _finish_queued_abnormal(self, req: Request, status: str,
                                error: Optional[str] = None) -> None:
        self._handoff_blobs.pop(req.rid, None)
        self._handoff_first.discard(req.rid)
        super()._finish_queued_abnormal(req, status, error)

    def transplant_extra(self, old) -> None:
        """Supervisor-restart hook (the restart-mid-handoff bugfix):
        re-adopt every in-flight handoff the dead engine held for a
        still-queued transplanted request into the REBUILT cache —
        without this a rebuilt decode engine would strand the prefill
        side's record (and silently re-prefill instead of restoring).
        A record the new host tier cannot hold degrades to recompute
        admission, which is the same colocated fallback a live engine
        uses."""
        if not isinstance(old, DecodeEngine):
            return
        queued = {r.rid for r in self._queue}
        for rid, blobs in old._handoff_blobs.items():
            if rid not in queued:
                continue
            try:
                handle = self.cache.adopt_swap(*blobs)
            except RuntimeError:
                self.colocated_fallbacks += 1
                continue
            self._swap_handles[rid] = handle
            self._handoff_blobs[rid] = blobs
        self._handoff_first |= (old._handoff_first & queued)
        old._handoff_blobs = {}
        old._handoff_first = set()


@dataclass
class _DisaggRequest:
    """Coordinator-side bookkeeping for one accepted request: which
    engine (or the handoff queue) owns it now."""
    rid: int                          # coordinator rid (client-visible)
    prompt: np.ndarray
    max_new_tokens: int
    stop_sequences: Optional[list]
    deadline: float                   # absolute monotonic; 0.0 = none
    t_submit: float
    where: str = "decode"             # "prefill" | "handoff" | "decode"
    local: int = -1                   # engine-local rid (when owned)
    rec: Optional[HandoffRecord] = None   # while where == "handoff"
    cancelled: bool = False
    trace: Optional[object] = None    # coordinator-managed TraceContext


class DisaggCoordinator:
    """In-process 1P+1D disaggregated serving pipeline — drive it
    exactly like an engine (``submit`` / ``step`` / ``finished`` /
    ``drain_stream`` / ``cancel``), so ``GenerationServer`` and the
    bench harness work unchanged.

    One :meth:`step` is one pipeline tick::

        1. SHIP wave k        (records taken last tick: staging flush
                               materialises copies that rode under the
                               intervening dispatches; decode adopts)
        2. PREFILL wave k+1   (one packed dispatch; exports stage)
        3. TAKE wave k+1      (records queue for next tick's ship)
        4. DECODE             (restores wave k — one batched scatter
                               per row, zero prefill tokens — then one
                               decode round)

    so prefill wave *k+1* and the decode-side restore of wave *k*
    overlap on disaggregated hardware, and the staged D2H copies
    always have a dispatch to hide under.  The in-flight handoff
    count (exported + pending-ship + adopted-unadmitted) is bounded
    by the prefill engine's ``max_inflight_handoffs`` — a full queue
    stalls prefill ADMISSION, which backpressures ``submit()``.

    Routing: :func:`handoff_wins` (PR-4 bytes-vs-FLOPs, knobs
    ``handoff_gbps`` / ``handoff_chip_flops``) decides per request;
    ``force_route="prefill"|"colocated"`` pins it for tests/benches.
    Decisions, handoffs, and fallbacks are counters (``routed``,
    ``handoffs_shipped``, ``colocated_fallbacks``), surfaced through
    :class:`~paddle_tpu.observability.DisaggMetrics`.

    Thread safety: every public method serializes on ``_lock`` (the
    ``lock-discipline`` analysis rule enforces it via SHARED_STATE);
    the engines are only ever touched under that lock."""

    def __init__(self, prefill_engine: PrefillEngine,
                 decode_engine: DecodeEngine, *,
                 handoff_gbps: float = 10.0,
                 handoff_chip_flops: Optional[float] = None,
                 force_route: Optional[str] = None,
                 metrics_registry=None, metrics_ring=None,
                 tracer=None):
        if not hasattr(prefill_engine, "take_handoffs"):
            raise ValueError(
                "prefill_engine must be a PrefillEngine (it exports "
                "handoff records instead of decoding)")
        if not hasattr(decode_engine, "admit_handoff"):
            raise ValueError(
                "decode_engine must be a DecodeEngine (it adopts "
                "handoff records through the _admit_swapped path)")
        if force_route not in (None, "prefill", "colocated"):
            raise ValueError(
                "force_route must be None, 'prefill' or 'colocated', "
                f"got {force_route!r}")
        self._lock = threading.Lock()
        # per-request tracing: the coordinator mints a MANAGED
        # TraceContext per accepted request (trace id = coordinator
        # rid) and propagates it into whichever engine owns the
        # request — the handoff carries it across, so one trace spans
        # both engines.  GenerationServer attaches its tracer here.
        self.tracer = tracer
        self.prefill = prefill_engine
        self.decode = decode_engine
        # the bound must cover the WHOLE pipeline, not just the
        # untaken records — re-point the engine's backlog seam
        self.prefill.handoff_backlog = self._inflight_locked
        self.handoff_gbps = float(handoff_gbps)
        self.handoff_chip_flops = handoff_chip_flops
        self.force_route = force_route
        self._requests: Dict[int, _DisaggRequest] = {}
        self._prefill_rids: Dict[int, int] = {}   # local -> rid
        self._decode_rids: Dict[int, int] = {}
        self._handoffs: deque = deque()   # (rec, freq) awaiting ship
        self._degraded: deque = deque()   # freqs awaiting fallback room
        self._stream: List = []
        self._finished: List[Request] = []
        self._next_rid = 0
        self._now = time.monotonic        # seam: tests pin the clock
        # routing / pipeline stats (plain counters — exact even with
        # metrics off; "the decision is a counter, not a guess")
        self.routed = {"prefill": 0, "colocated": 0}
        self.handoffs_shipped = 0
        self.handoff_pages = 0
        self.handoff_bytes = 0
        self.handoff_wall_s = 0.0
        self.colocated_fallbacks = 0
        # bench seam: wall of the decode engine's step on the last
        # tick (the disagg A/B reads the decode-side step latency
        # during admission waves through this)
        self.last_decode_step_s = 0.0
        self.last_tick_admissions = 0
        if metrics_registry is False:
            self.metrics = None
        else:
            if metrics_registry is None:
                # share the engines' registry so /metrics on the
                # serving front is one aggregated exposition
                for eng in (self.decode, self.prefill):
                    m = getattr(eng, "metrics", None)
                    if m is not None:
                        metrics_registry = m.registry
                        if metrics_ring is None:
                            metrics_ring = m.ring
                        break
            from ..observability import MetricsRegistry
            self.metrics = DisaggMetrics(
                metrics_registry if metrics_registry is not None
                else MetricsRegistry(), ring=metrics_ring)
        self._update_gauges_locked()

    # -- client side ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 64,
               stop_sequences=None,
               deadline_s: Optional[float] = None) -> int:
        """Route + queue a request; returns the coordinator rid.  The
        cost model picks the lane: long prompts go to the prefill
        engine (disaggregated — handoff follows), short ones stay
        colocated on the decode engine.  Validation and backpressure
        (``ValueError`` / ``QueueFullError``) come from the target
        engine.  Thread safety: ``any-thread`` (serializes on the
        coordinator lock)."""
        with self._lock:
            return self._submit_locked(prompt, max_new_tokens,
                                       stop_sequences, deadline_s)

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it lives — on either engine
        (retired at that engine's next flush point) or in the handoff
        queue (record reclaimed immediately).  False for
        unknown/finished rids."""
        with self._lock:
            freq = self._requests.get(rid)
            if freq is None:
                return False
            freq.cancelled = True
            if freq.where == "prefill":
                # the engine may have exported it already this tick
                # (record not yet taken) — the mark catches it at ship
                return self.prefill.cancel(freq.local) or True
            if freq.where == "decode":
                return self.decode.cancel(freq.local) or True
            # in the handoff queue: reclaim inline
            src = None
            for i, (rec, f) in enumerate(self._handoffs):
                if f is freq:
                    del self._handoffs[i]
                    rec.discard()
                    src = rec.request
                    break
            for r, f in self._degraded:
                if f is freq:
                    src = r
            self._degraded = deque(
                (r, f) for r, f in self._degraded if f is not freq)
            self._finish_synth_locked(freq, "cancelled", None,
                                      src=src)
            return True

    def finished(self) -> List[Request]:
        with self._lock:
            out, self._finished = self._finished, []
            return out

    def drain_stream(self) -> List:
        with self._lock:
            out, self._stream = self._stream, []
            return out

    def has_work(self) -> bool:
        with self._lock:
            return bool(self.prefill.has_work()
                        or self.decode.has_work()
                        or self._handoffs or self._degraded
                        or self._finished)

    def step(self) -> int:
        """One pipeline tick (see the class docstring).  Returns the
        number of active decode slots."""
        with self._lock:
            return self._step_locked()

    def run_to_completion(self, max_steps: int = 10_000):
        return _drive_to_completion(self, max_steps)

    # -- serving-front compatibility (GenerationServer /health reads
    #    these; each is a host-int read under the server's lock) ----------
    def queue_capacity_reason(
            self, prompt_len: int = 0, factor: float = 1.0,
            priority: Optional[str] = None) -> Optional[str]:
        """Readiness form of the routing decision — readiness can
        never disagree with what ``submit()`` accepts: a disagg-routed
        prompt is accepted while EITHER lane has room (a full prefill
        queue falls back to colocated admission), a colocated one
        answers for the decode engine alone.  ``factor``/``priority``
        forward to the lanes' class-aware forms unchanged."""
        with self._lock:
            if self._route_prefill_locked(prompt_len):
                if self.prefill.queue_capacity_reason(
                        prompt_len, factor=factor,
                        priority=priority) is None:
                    return None
            return self.decode.queue_capacity_reason(
                prompt_len, factor=factor, priority=priority)

    def queued_tokens(self) -> int:
        return (self.prefill.queued_tokens()
                + self.decode.queued_tokens())

    def retry_after_s(self) -> float:
        return min(self.prefill.retry_after_s(),
                   self.decode.retry_after_s())

    @property
    def cache(self):
        """The decode engine's cache (the pool a serving front's
        ``/health`` free-page gauge should watch — the prefill pool
        recycles within a wave)."""
        return self.decode.cache

    @property
    def _active(self):
        return self.decode._active

    @property
    def _queue(self):
        return list(self.prefill._queue) + list(self.decode._queue)

    def _sum(self, attr: str) -> int:
        return getattr(self.prefill, attr) + getattr(self.decode, attr)

    @property
    def requests_cancelled(self):
        return self._sum("requests_cancelled")

    @property
    def requests_expired(self):
        return self._sum("requests_expired")

    @property
    def requests_rejected(self):
        return self._sum("requests_rejected")

    @property
    def requests_faulted(self):
        return self._sum("requests_faulted")

    @property
    def requests_finished(self):
        return self._sum("requests_finished")

    @property
    def step_faults(self):
        return self._sum("step_faults")

    @property
    def decode_steps(self):
        return self.decode.decode_steps

    @property
    def tokens_generated(self):
        return self._sum("tokens_generated")

    @property
    def prefill_calls(self):
        return self._sum("prefill_calls")

    @property
    def preemptions(self):
        return self._sum("preemptions")

    @property
    def prefill_tokens_avoided(self):
        return self._sum("prefill_tokens_avoided")

    # -- locked internals (CONTRACT: caller holds _lock; registered in
    #    analysis/annotations.py locked_methods) --------------------------
    def _inflight_locked(self) -> int:
        """Handoffs anywhere in the pipeline: exported-untaken +
        awaiting ship/fallback + adopted-unadmitted.  Also the
        prefill engine's backlog seam (consulted during its step,
        which only ever runs under this lock)."""
        return (len(self.prefill._handoff_ready)
                + len(self._handoffs) + len(self._degraded)
                + self.decode.pending_handoffs())

    def _route_prefill_locked(self, prompt_len: int) -> bool:
        """The cost-model verdict (pure — counting happens only once
        a placement actually lands, so rejected submits and fallbacks
        can never skew the decision counters)."""
        if self.force_route is not None:
            return self.force_route == "prefill"
        return handoff_wins(prompt_len, self.decode,
                            self.handoff_gbps,
                            self.handoff_chip_flops)

    def _count_placement_locked(self, disagg: bool) -> None:
        self.routed["prefill" if disagg else "colocated"] += 1
        if self.metrics is not None:
            (self.metrics.routed_prefill if disagg
             else self.metrics.routed_colocated).inc()

    def _submit_locked(self, prompt, max_new_tokens, stop_sequences,
                       deadline_s) -> int:
        prompt = np.asarray(prompt, np.int64)
        disagg = self._route_prefill_locked(len(prompt))
        if disagg:
            dc = self.decode.cache
            row_cap = min(dc.pages_max, dc.num_pages - 1) * dc.page
            if len(prompt) + int(max_new_tokens) > row_cap:
                # the decode pool can never hold the full generation:
                # route colocated so the canonical submit() ValueError
                # rejects it upfront instead of failing mid-handoff
                disagg = False
        target = self.prefill if disagg else self.decode
        # place BEFORE committing the rid: a rejected submit must not
        # burn a coordinator rid or count a routing decision.  The
        # clock read and the decision counter both moved OUT of the
        # placement→commit window: nothing fallible may run between
        # the engine accepting the request and the rid tables mapping
        # it, or the engine generates for a request the coordinator
        # cannot cancel/triage (claim-lifecycle: placed-request)
        now = self._now()
        ctx = None
        if self.tracer is not None:
            # the coordinator OWNS the trace lifecycle (managed=True):
            # the engines report phase spans into it, the close lands
            # at the finished-merge under the coordinator rid
            ctx = self.tracer.begin_trace(
                str(self._next_rid), managed=True,
                prompt_len=len(prompt),
                lane="prefill" if disagg else "colocated")
            ctx.default_attrs["engine"] = \
                "prefill" if disagg else "decode"
        try:
            try:
                local = target.submit(prompt,
                                      max_new_tokens=max_new_tokens,
                                      stop_sequences=stop_sequences,
                                      deadline_s=deadline_s,
                                      trace=ctx)
            except QueueFullError:
                if not disagg:
                    raise
                # the prefill lane's bounded queue is full: colocation
                # is strictly better than shedding while the decode
                # engine has room (parity with the fleet router's
                # fallback — the 429 verdict belongs to the decode
                # lane alone)
                disagg = False
                target = self.decode
                if ctx is not None:
                    ctx.default_attrs["engine"] = "decode"
                    ctx.event("prefill_lane_full_fallback")
                    # the index must not keep claiming the prefill
                    # lane for a request that never rode it
                    ctx.tracer.annotate(ctx.trace_id,
                                        lane="colocated")
                local = target.submit(prompt,
                                      max_new_tokens=max_new_tokens,
                                      stop_sequences=stop_sequences,
                                      deadline_s=deadline_s,
                                      trace=ctx)
        except BaseException:
            if ctx is not None:
                ctx.close(status="rejected",
                          error="submit refused (validation or "
                                "backpressure)")
            raise
        freq = _DisaggRequest(
            self._next_rid, prompt, int(max_new_tokens),
            stop_sequences,
            0.0 if deadline_s is None else now + float(deadline_s),
            now, where="prefill" if disagg else "decode", local=local,
            trace=ctx)
        self._next_rid += 1
        self._requests[freq.rid] = freq
        if disagg:
            self._prefill_rids[local] = freq.rid
        else:
            self._decode_rids[local] = freq.rid
        self._count_placement_locked(disagg)
        return freq.rid

    def _step_locked(self) -> int:
        now = self._now()
        self.last_decode_step_s = 0.0     # no decode ran (yet) this tick
        # 1. ship wave k (+ retry degraded fallbacks waiting for room)
        self._ship_locked(now)
        # 2. prefill wave k+1 (exports stage under its dispatch)
        pf0 = self.prefill.prefill_calls
        if self.prefill.has_work():
            self.prefill.step()
        self.last_tick_admissions = self.prefill.prefill_calls - pf0
        # 3. take the new records; they ship NEXT tick, after their
        # staged D2H copies have ridden under the decode dispatch
        # below and wave k+2's prefill
        for rec in self.prefill.take_handoffs():
            rid = self._prefill_rids.pop(rec.request.rid, None)
            if rid is None:               # already triaged away
                rec.discard()
                continue
            freq = self._requests[rid]
            freq.where, freq.rec, freq.local = "handoff", rec, -1
            self._handoffs.append((rec, freq))
        # prefill stream/finished: only requests that finished ON the
        # prefill engine still have a live rid mapping (direct
        # finishers — eos at the first token, cancels, errors); taken
        # handoffs popped theirs above, so their first token is NOT
        # forwarded here — it streams at decode-side admission
        for local, tok in self.prefill.drain_stream():
            rid = self._prefill_rids.get(local)
            if rid is not None:
                self._stream.append((rid, tok))
        for req in self.prefill.finished():
            rid = self._prefill_rids.pop(req.rid, None)
            if rid is None:
                continue
            freq = self._requests.pop(rid, None)
            req.rid = rid
            self._close_trace_locked(freq, req)
            self._finished.append(req)
        # 4. decode: restore wave k (batched scatters, zero prefill
        # tokens) + one decode round
        active = 0
        if self.decode.has_work():
            t0 = time.perf_counter()
            self.decode.step()
            self.last_decode_step_s = time.perf_counter() - t0
            active = len(self.decode._active)
        for local, tok in self.decode.drain_stream():
            rid = self._decode_rids.get(local)
            if rid is not None:
                self._stream.append((rid, tok))
        for req in self.decode.finished():
            rid = self._decode_rids.pop(req.rid, None)
            if rid is None:
                continue
            freq = self._requests.pop(rid, None)
            req.rid = rid
            self._close_trace_locked(freq, req)
            self._finished.append(req)
        self._update_gauges_locked()
        return active

    def _close_trace_locked(self, freq: Optional[_DisaggRequest],
                            req: Request) -> None:
        """Seal the coordinator-managed trace once the request
        surfaces with its final status (the engine already reported
        its phase spans at retirement); CONTRACT: caller holds
        ``_lock``."""
        if freq is None or freq.trace is None:
            return
        try:
            freq.trace.close(status=req.status, error=req.error,
                             tokens=len(req.generated),
                             clocks=phase_clocks(req))
        except Exception:
            pass

    def _ship_locked(self, now: float) -> None:
        # degraded fallbacks first: they are oldest and already lost
        # their handoff — only decode-queue room gates them
        retry: deque = deque()
        while self._degraded:
            src, freq = self._degraded.popleft()
            if freq.cancelled:
                self._finish_synth_locked(freq, "cancelled", None,
                                          src=src)
                continue
            if freq.deadline and now >= freq.deadline:
                self._finish_synth_locked(freq, "expired", None,
                                          src=src)
                continue
            try:
                local = self.decode.admit_degraded(src)
            except QueueFullError:
                retry.append((src, freq))
                continue
            except ValueError as e:
                # the decode cache can never hold it: terminal —
                # better an honest error than a wedged FIFO head
                self._finish_synth_locked(freq, "error", str(e))
                continue
            self._commit_decode_locked(freq, local)
        self._degraded = retry
        keep: deque = deque()
        while self._handoffs:
            rec, freq = self._handoffs.popleft()
            if freq.cancelled:
                rec.discard()
                self._finish_synth_locked(freq, "cancelled", None,
                                          src=rec.request)
                continue
            if freq.deadline and now >= freq.deadline:
                rec.discard()
                self._finish_synth_locked(freq, "expired", None,
                                          src=rec.request)
                continue
            t0 = time.perf_counter()
            try:
                rec.materialize()              # SHIP half (faultable)
                local = self.decode.admit_handoff(rec)   # RESTORE half
            except QueueFullError:
                keep.append((rec, freq))       # backpressure: retry
                continue
            except Exception:
                # ship/restore fault or the receiving host tier is
                # full: degrade to a colocated re-prefill, preserving
                # the sampled first token — token-exact, never dropped
                self._degrade_locked(rec, freq)
                continue
            dt = time.perf_counter() - t0
            # commit FIRST: the placed-request claim must reach the
            # rid table before anything fallible (span reporting
            # included) can raise — claim-lifecycle discipline
            self._commit_decode_locked(freq, local)
            self.handoffs_shipped += 1
            self.handoff_pages += rec.pages
            self.handoff_bytes += rec.nbytes
            self.handoff_wall_s += dt
            if freq.trace is not None:
                t1 = time.monotonic()
                freq.trace.span("handoff_ship", t1 - dt, t1,
                                pages=rec.pages, bytes=rec.nbytes)
                freq.trace.default_attrs["engine"] = "decode"
            if self.metrics is not None:
                m = self.metrics
                m.handoff_pages.inc(rec.pages)
                m.handoff_bytes.inc(rec.nbytes)
                m.handoff_seconds.observe(dt)
        self._handoffs = keep

    def _commit_decode_locked(self, freq: _DisaggRequest,
                              local: int) -> None:
        freq.where, freq.local, freq.rec = "decode", local, None
        self._decode_rids[local] = freq.rid

    def _degrade_locked(self, rec: HandoffRecord,
                        freq: _DisaggRequest) -> None:
        rec.discard()
        self.colocated_fallbacks += 1
        if freq.trace is not None:
            freq.trace.event("handoff_degraded")
            freq.trace.default_attrs["engine"] = "decode"
        if self.metrics is not None:
            self.metrics.colocated_fallback.inc()
            self.metrics.ring.emit("kv_handoff_fallback", rid=freq.rid)
        try:
            local = self.decode.admit_degraded(rec.request)
        except QueueFullError:
            self._degraded.append((rec.request, freq))
            return
        except ValueError as e:
            # no cache on this coordinator can hold it: terminal
            self._finish_synth_locked(freq, "error", str(e))
            return
        self._commit_decode_locked(freq, local)

    def _finish_synth_locked(self, freq: _DisaggRequest, status: str,
                             error: Optional[str],
                             src: Optional[Request] = None) -> None:
        """Terminal message for a request neither engine owns anymore
        (cancelled/expired while in the handoff queue): the client
        ALWAYS gets a status.  ``src`` is the engine-side Request the
        handoff was carrying, when one is at hand — its accrued phase
        intervals report into the trace before the close, so the
        always-kept abnormal traces still answer "where did the time
        go"."""
        self._requests.pop(freq.rid, None)
        req = Request(freq.rid, freq.prompt, freq.max_new_tokens,
                      stop_sequences=freq.stop_sequences,
                      t_submit=freq.t_submit)
        req.done = True
        req.status = status
        req.error = error
        req.t_finish = self._now()
        if freq.trace is not None:
            if src is not None:
                finalize_request_trace(freq.trace, src, status=status,
                                       error=error)
            else:
                try:
                    freq.trace.close(status=status, error=error)
                except Exception:
                    pass
        self._finished.append(req)

    def _update_gauges_locked(self) -> None:
        if self.metrics is not None:
            self.metrics.handoff_inflight.set(self._inflight_locked())
