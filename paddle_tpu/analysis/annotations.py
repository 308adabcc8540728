"""Invariant annotations: the ground truth the rules are seeded with.

The analyzer cannot infer *intent* — which methods form the overlap
hot loop, which attribute is the designated blocking seam, which lock
guards which attributes across the engine/HTTP/supervisor threads.
This module records those facts ONCE, next to the analysis code, and
everything consumes it:

* the rules (``paddle_tpu/analysis/rules/``) read their roots, seam
  names and shared-state specs from here;
* ``tests/test_analysis.py`` consistency-checks the thread-safety
  documentation (docs/FAULT_TOLERANCE.md and the ``submit``/``cancel``
  docstrings) against :data:`THREAD_SAFETY` — the docs cannot drift
  from the registry without a test failure;
* humans read it as the canonical statement of the concurrency and
  sync contracts.

When the serving stack grows a new thread, a new lock, or a new hot
path, THIS file is where the invariant is declared.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Tuple

__all__ = ["SharedStateSpec", "SHARED_STATE", "SYNC_HOT_ROOTS",
           "DEVICE_PRODUCER_NAMES", "DEVICE_PRODUCER_ATTRS",
           "BLOCKING_SEAMS", "EXTRA_TRACED", "FLUSH_MUTATORS",
           "FLUSH_SAFE", "ENGINE_CLASSES", "THREAD_SAFETY",
           "thread_safety_doc_lines", "ClaimSpec", "CLAIMS",
           "checked_claims", "claims_doc_lines"]


# ---------------------------------------------------------------------------
# sync-lint: the overlap decode / packed-admission hot paths
# ---------------------------------------------------------------------------
# Call-graph roots of the "zero blocking host syncs in steady state"
# contract (PERF.md round 6): the dispatch-ahead decode loop, every
# admission lane (admission runs between flushed pipelines, but its
# syncs must still route through the audited seam), and the
# speculative round.  Patterns are segment-aligned suffixes resolved
# by Project.match_qualnames; make_paged_decode_step_async matches its
# jitted closures too.
SYNC_HOT_ROOTS: List[str] = [
    "ContinuousBatchingEngine._decode_overlap",
    "ContinuousBatchingEngine._dispatch_async",
    "ContinuousBatchingEngine._drain_one",
    "ContinuousBatchingEngine._pipeline_flush",
    "ContinuousBatchingEngine._ensure_or_preempt",
    "ContinuousBatchingEngine._admit_packed",
    "ContinuousBatchingEngine._admit_batch",
    "ContinuousBatchingEngine._admit_chunked",
    "ContinuousBatchingEngine._admit_swapped",
    # ISSUE-19 fused speculative lane: one draft+verify dispatch per
    # round with ONE sanctioned fetch — every other hop (proposal
    # building, mirror corrections, accept bookkeeping, n-gram table
    # maintenance) must stay pure host work or the round serializes
    "ContinuousBatchingEngine._decode_spec_sync",
    "ContinuousBatchingEngine._decode_spec_overlap",
    "ContinuousBatchingEngine._dispatch_spec_async",
    "ContinuousBatchingEngine._drain_spec_entry",
    "ContinuousBatchingEngine._drain_spec_block",
    "ContinuousBatchingEngine._spec_admit",
    "ContinuousBatchingEngine._propose_lookup",
    "ContinuousBatchingEngine._spec_note_tokens",
    # the fleet routing decision path (PR 8): a routing choice runs on
    # the submit path under the router lock while replicas decode —
    # a blocking host sync here would stall every handler thread, so
    # the placement walk must stay pure host bookkeeping
    "FleetRouter._submit_locked",
    "FleetRouter._candidates_locked",
    "FleetRouter._place_locked",
    # QoS scheduler-policy seam (ISSUE 20): class-ordered admission,
    # priority-preemption victim selection and the shed verdict all
    # run inside the admission wave / submit path — policy decisions
    # must stay pure host bookkeeping (a device sync inside victim
    # selection would stall every admission)
    "ContinuousBatchingEngine._collect_admissions",
    "ContinuousBatchingEngine._priority_preempt",
    "SchedulerPolicy.order_queue",
    "SchedulerPolicy.select_victim",
    "SchedulerPolicy.preemptable_for",
    # disaggregated prefill/decode (PR 9): the restore-side admission
    # path (adopt + zero-prefill re-admission) and the coordinator/
    # router handoff-ship paths run under the pipeline lock while
    # replicas decode — they must stay pure host bookkeeping except
    # for the audited staging flush inside materialize()
    "DecodeEngine.admit_handoff",
    "DecodeEngine.admit_degraded",
    "DecodeEngine._admit_swapped",
    "DecodeEngine._finish_admit",
    "PrefillEngine._decode_once",
    "PrefillEngine._collect_admissions",
    "DisaggCoordinator._ship_locked",
    "DisaggCoordinator._submit_locked",
    "FleetRouter._ship_handoffs_locked",
    "FleetRouter._disagg_wins_locked",
    "make_paged_decode_step_async",
    # the TP shard_map lanes (PR 7): the sharded step/prefill inner
    # fns and the quantized-collective builder must stay lint-clean
    # themselves, not merely be reachable from the engine roots
    "paged_decode._build_tp_inner",
    "paged_decode._prefill_packed_tp",
    "paged_decode._prefill_chunk_batched_tp",
    "paged_decode._make_q8_allreduce",
    # the mixed prefill+decode lane (PR 11, ISSUE 12): carving parks chunk state
    # with ZERO dispatches, and the mixed tick is one fused program —
    # a blocking sync in either would stall the decode cadence the
    # lane exists to protect (the sync lane's one fetch per tick and
    # the drain seam carry the only sanctioned drains)
    "ContinuousBatchingEngine._mixed_carve",
    "ContinuousBatchingEngine._mixed_plan",
    "ContinuousBatchingEngine._decode_mixed",
    # the multi-token decode horizon (ISSUE 15): one dispatch / one
    # fetch / one bookkeeping pass per H tokens — the horizon drain
    # and the batched page pre-claim are the amortized hot path and
    # must stay sync-clean; the sync horizon lane's single fetch per
    # tick is its sanctioned drain
    "ContinuousBatchingEngine._decode_sync_multi",
    "ContinuousBatchingEngine._drain_horizon_entry",
    "ContinuousBatchingEngine._drain_horizon_block",
    "make_paged_decode_step_multi",
    # per-request tracing (ISSUE 13): phase clocks accrue and
    # materialize as spans ONLY at scheduler mutation / retirement
    # points — the decode hot loop never touches the tracer, and the
    # materialization path itself must stay pure host bookkeeping
    # (no device fetch may hide inside a span report)
    "ContinuousBatchingEngine._retire",
    "ContinuousBatchingEngine._retire_abnormal",
    "serving_engine._finalize_trace",
    "tracing.TraceContext.report_request",
    "paged_decode.make_mixed_step",
    "paged_decode._packed_prefill_body",
    "paged_decode._packed_prefill_body_tp",
]

# Calls whose RESULT lives on the device: the taint seeds for the
# "int()/float()/np.asarray() on a device value" checks.  Bare names
# (module functions) and `self.<attr>` callables (the engine's jitted
# step handles).  `jnp.*` / `jax.*` calls are device producers by
# construction and are recognized structurally, not listed here.
DEVICE_PRODUCER_NAMES: FrozenSet[str] = frozenset({
    "_prefill", "_prefill_chunk", "_prefill_packed",
    "_prefill_chunk_batched", "_pick_token", "_mm", "_rms_norm",
    "_last_logits",
})
DEVICE_PRODUCER_ATTRS: FrozenSet[str] = frozenset({
    "_step", "_step_async", "_step_mixed", "_step_multi", "_dstep",
    "_verify",
})

# The engine's DESIGNATED blocking drain: every hot-path call to it is
# a deliberate sync and must carry a suppression documenting why that
# sync is sound (steady-state drain one step behind; admission
# first-token fetch behind a flushed pipeline; speculative round
# boundary).  This is how "reviewer vigilance" became "machine
# checked": an unjustified drain cannot land.
BLOCKING_SEAMS: FrozenSet[str] = frozenset({"_fetch"})


# ---------------------------------------------------------------------------
# trace-purity: functions staged by jit/shard_map/pallas
# ---------------------------------------------------------------------------
# Traced functions the structural detector cannot see (the def is
# returned by a factory and jitted at a distance, e.g.
# `step, step_q8 = _build_step_fns(...); jax.jit(step_q8)`).
# Patterns match qualnames, including nested defs.
EXTRA_TRACED: List[str] = [
    "paged_decode._build_step_fns",
    "paged_decode._build_tp_inner",
    # PR 7 TP shard_map seams: packed prefill + batched verify are
    # jitted shard_map programs built by factories, and the quantized
    # ring collective is a closure staged inside the TP step
    "paged_decode._prefill_packed_tp",
    "paged_decode._prefill_chunk_batched_tp",
    "paged_decode._make_q8_allreduce",
    # PR-11 mixed lane: the packed-prefill bodies are unjitted
    # factories (jitted at a distance by _prefill_packed[_tp] and
    # composed into make_mixed_step's outer jit), and the mixed step
    # itself stages its fn/fn_fp closures
    "paged_decode._packed_prefill_body",
    "paged_decode._packed_prefill_body_tp",
    "paged_decode.make_mixed_step",
    # ISSUE-15 horizon: the H-micro-step scan stages fn closures (and
    # the micro bodies) inside its own jit
    "paged_decode.make_paged_decode_step_multi",
    # ISSUE-19 fused speculative: the round program (gamma-iteration
    # draft scan + batched verify + on-device fold) is one jit built
    # by a memoised factory; the verify bodies are factory-staged
    # closures composed into it (and into the TP shard_map form)
    "paged_decode.make_spec_step",
    "paged_decode._spec_verify_body",
    "paged_decode._spec_verify_body_tp",
]


# ---------------------------------------------------------------------------
# flush-point discipline (overlap=True scheduler mutations)
# ---------------------------------------------------------------------------
ENGINE_CLASSES: FrozenSet[str] = frozenset({
    "ContinuousBatchingEngine", "SpeculativeEngine",
    "PrefillEngine", "DecodeEngine",
})

# Scheduler-mutation methods: calling one moves slots/pages under the
# decode pipeline, so the CALL SITE must be dominated by a pipeline
# flush (or schedule one) whenever overlap=True can reach it.
FLUSH_MUTATORS: FrozenSet[str] = frozenset({
    "_retire", "_retire_abnormal", "_preempt",
    "_admit_packed", "_admit_batch", "_admit_chunked",
    "_admit_swapped",
})

# Contexts exempt from the dominance check, WITH the reason the
# exemption is sound (rendered in the finding hint when a mutant
# removes the justification):
FLUSH_SAFE: Dict[str, str] = {
    "ContinuousBatchingEngine._drain_one":
        "the drain IS the pipeline: tokens are attributed against the "
        "dispatch-time active mask, and host-only retirements schedule "
        "_needs_flush",
    "ContinuousBatchingEngine._drain_step":
        "_drain_one's bookkeeping half, split off only so that the "
        "engine.drain span covers it and not the fetch: same "
        "attribution against the dispatch-time mask, same "
        "_needs_flush scheduling",
    "ContinuousBatchingEngine._pipeline_flush":
        "the flush itself",
    "ContinuousBatchingEngine._quarantine":
        "quarantine clears _inflight first — no dispatch is in flight "
        "when the wave's slots retire",
    "ContinuousBatchingEngine._finish_admit":
        "admission tail: every admission lane runs behind the "
        "_step_inner flush",
    "ContinuousBatchingEngine._decode_sync":
        "synchronous lane: overlap=False, there is no pipeline",
    "ContinuousBatchingEngine._decode_sync_multi":
        "synchronous horizon lane: overlap=False, there is no "
        "pipeline — the block fetch precedes every retirement",
    "ContinuousBatchingEngine._drain_horizon_block":
        "the horizon drain IS the pipeline: a whole [H, B] block's "
        "tokens are attributed against the dispatch-time active "
        "mask, and host-only stop retirements schedule _needs_flush "
        "exactly like _drain_one",
    "ContinuousBatchingEngine._decode_spec_sync":
        "synchronous spec lane: overlap=False, there is no pipeline "
        "— the round's ONE fetch precedes every retirement",
    "ContinuousBatchingEngine._drain_spec_block":
        "the spec drain IS the pipeline: a whole round's [C, B] "
        "emit block is attributed against the DEVICE-CHAIN active "
        "mask (phantom chained rounds excluded), and host-only stop "
        "retirements schedule _needs_flush exactly like _drain_one",
    "PrefillEngine._decode_once":
        "prefill engines have no decode pipeline: overlap=True is "
        "rejected at construction, so no dispatch is ever in flight "
        "when a wave's slots export",
    "DecodeEngine._admit_swapped":
        "delegates to the base admission path, which runs behind "
        "_step_inner's flush (the override only reclaims dead "
        "handoff blobs on failure)",
    "ContinuousBatchingEngine._admit_lanes":
        "lane choice only, reached through _admit_sequential's "
        "engine.admit span alone: both of its call sites "
        "(_admit_wave's sequential path and _mixed_carve's "
        "shape-forced degrades) flush the pipeline before handing it "
        "the popped wave",
}


# ---------------------------------------------------------------------------
# lock-discipline: shared state across engine / HTTP / supervisor threads
# ---------------------------------------------------------------------------
@dataclass
class SharedStateSpec:
    """Which attributes of a class are shared across threads and which
    lock guards them.

    ``attrs``: attribute names that MUST be accessed under ``lock``.
    ``proxies``: attributes whose referent's whole state is owned by
    the engine thread — any chained access (``self.engine.X``,
    ``srv._driver.m()``) must hold the lock; reading the bare
    reference is allowed (atomic ref read).
    ``locked_methods``: methods whose body is only ever entered with
    the lock already held (documented contract) — treated as
    lock-held.
    ``exempt_methods``: methods outside the discipline (single-
    threaded construction, pure ref-read properties).  ``__init__`` /
    ``__del__`` are always exempt.
    """

    lock: str
    attrs: FrozenSet[str] = frozenset()
    proxies: FrozenSet[str] = frozenset()
    locked_methods: FrozenSet[str] = frozenset()
    exempt_methods: FrozenSet[str] = frozenset()
    note: str = ""


SHARED_STATE: Dict[str, SharedStateSpec] = {
    # HTTP front: handler threads (submit/cancel/health) race the
    # engine drive thread; _lock serializes every engine touch.
    "inference.serving.GenerationServer": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_queues", "_fatal"}),
        proxies=frozenset({"engine", "_engine", "_driver",
                           "_supervisor"}),
        locked_methods=frozenset({"_rebind_observability",
                                  "_is_ready_locked",
                                  "_health_locked",
                                  "_attach_tracer"}),
        exempt_methods=frozenset({"engine", "_driver", "restarts",
                                  "start", "stop"}),
        note="engine state is owned by the drive thread; HTTP "
             "handlers reach it only through submit()/cancel()/"
             "health_snapshot(), all of which take _lock"),
    "inference.serving.InferenceServer": SharedStateSpec(
        lock="_count_lock",
        attrs=frozenset({"request_count"}),
        exempt_methods=frozenset({"start", "stop"})),
    "inference.serving.DevicePool": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_rr"})),
    # observability primitives: scraped from HTTP threads while the
    # engine thread records
    "observability.metrics.Counter": SharedStateSpec(
        lock="_lock", attrs=frozenset({"_value"})),
    "observability.metrics.Gauge": SharedStateSpec(
        lock="_lock", attrs=frozenset({"_value", "_fn"})),
    "observability.metrics.Histogram": SharedStateSpec(
        lock="_lock", attrs=frozenset({"_counts", "_sum", "_count",
                                       "_exemplars"})),
    "observability.metrics.MetricsRegistry": SharedStateSpec(
        lock="_lock", attrs=frozenset({"_metrics"})),
    "observability.events.EventRing": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_events", "_seq", "_dropped"})),
    # per-request tracing: engines report spans at retirement while
    # HTTP handler threads read /trace*, so both tables live behind
    # their own locks.  Lock order: a server/router/coordinator lock
    # may wrap the tracer lock, and the tracer's finish_trace calls
    # the store OUTSIDE its own lock — neither ever takes a lock
    # upward, so no ABBA pairing exists.
    "observability.tracing.Tracer": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_live"}),
        note="begin/add_span/finish/get/index all serialize on "
             "_lock; sealed docs leave the table before the store "
             "offer runs"),
    "observability.tracing.TraceStore": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_traces", "_n_ok", "retained",
                         "sampled_out", "evicted"}),
        note="tail-retention decision + FIFO eviction under _lock; "
             "metric instruments update after release (internally "
             "locked leaves)"),
    # fault plane: consulted from the engine thread and HTTP handler
    # threads concurrently
    "testing.faults.FaultPlane": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_rules", "counts", "fired"})),
    # fleet router (PR 8): HTTP handler threads submit/cancel while
    # the serving front's drive thread steps; the replica table,
    # request table and routing stats all serialize on the router
    # lock (the replica ENGINES inherit engine-thread-only semantics
    # — they are only ever touched under this lock)
    "fleet.router.FleetRouter": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_replicas", "_requests", "_pending",
                         "_stream", "_finished", "_prefix_owner",
                         "_next_rid", "routed", "failovers",
                         "rejected", "deaths", "replaces",
                         "route_errors", "_handoffs",
                         "disagg_decisions", "handoffs_shipped",
                         "handoff_pages", "handoff_bytes",
                         "colocated_fallbacks", "quota_rejected",
                         "scale_ups", "scale_downs"}),
        locked_methods=frozenset({
            "_submit_locked", "_candidates_locked", "_place_locked",
            "_step_locked", "_on_death_locked", "_replace_locked",
            "_flush_pending_locked", "_finish_synth_locked",
            "_has_work_locked", "_accepting_locked",
            "_states_locked", "_snapshot_locked",
            "_update_gauges_locked", "_ship_handoffs_locked",
            "_transport_default", "_disagg_wins_locked",
            "_count_disagg_placement_locked",
            "_inflight_handoffs_locked", "_roles_locked",
            "_harvest_dead_traces_locked",
            "_add_replica_locked", "_retire_locked"}),
        note="public API takes _lock; every *_locked helper is a "
             "documented called-with-lock-held contract "
             "(handoff_transport, _transport_default included: ship "
             "runs inside the router step).  quotas (TenantQuotas) "
             "is internally locked — charged under the router lock "
             "in _submit_locked but safe standalone"),
    # fleet autoscaler (ISSUE 20): a periodic controller thread ticks
    # while HTTP/dashboard threads read snapshot(); streaks, cooldown
    # clock and decision counters serialize on the autoscaler lock.
    # LOCK ORDER: autoscaler lock -> router lock (tick calls only the
    # router's PUBLIC verbs: fleet_snapshot/add_replica/
    # retire_replica); the router never calls into the autoscaler, so
    # no ABBA pairing exists.
    "fleet.autoscaler.FleetAutoscaler": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_up_streak", "_down_streak", "_last_scale",
                         "scale_ups", "scale_downs", "ticks",
                         "skipped_settling", "skipped_cooldown",
                         "desired"}),
        locked_methods=frozenset({"_tick_locked",
                                  "_publish_desired"}),
        note="tick()/snapshot() take _lock; the router lock is only "
             "ever acquired INSIDE (autoscaler -> router, never "
             "reverse)"),
    # disaggregation coordinator (PR 9): HTTP handler threads
    # submit/cancel while the serving front's drive thread ticks the
    # pipeline; the request table, handoff queues and pipeline
    # counters all serialize on the coordinator lock (the two engines
    # inherit engine-thread-only semantics — only ever touched under
    # it).  Lock order: a server lock may wrap the coordinator lock
    # (GenerationServer -> coordinator); the coordinator never takes
    # the router/server lock, so no ABBA pairing exists.
    "models.disagg.DisaggCoordinator": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_requests", "_prefill_rids", "_decode_rids",
                         "_handoffs", "_degraded", "_stream",
                         "_finished", "_next_rid", "routed",
                         "handoffs_shipped", "handoff_pages",
                         "handoff_bytes", "handoff_wall_s",
                         "colocated_fallbacks", "last_decode_step_s",
                         "last_tick_admissions"}),
        locked_methods=frozenset({
            "_submit_locked", "_step_locked", "_ship_locked",
            "_commit_decode_locked", "_degrade_locked",
            "_finish_synth_locked", "_update_gauges_locked",
            "_inflight_locked", "_route_prefill_locked",
            "_count_placement_locked"}),
        exempt_methods=frozenset({"cache", "queued_tokens",
                                  "retry_after_s"}),
        note="public API takes _lock; engine-summing compatibility "
             "properties read only host ints the serving front "
             "already serializes behind its own lock"),
    # sockets transport (ISSUE 14): the router thread drives RPCs
    # while HTTP handler threads cancel through the same connection —
    # the socket, seq counter and lease clock serialize on the
    # connection lock.  Lock order: the router lock may wrap a
    # connection lock (placement/sync under FleetRouter._lock); a
    # connection never takes a router/server lock, so no ABBA
    # pairing exists.
    "fleet.transport.Connection": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_sock", "_seq", "_closed", "_dialed",
                         "last_ok", "reconnects", "retries",
                         "heartbeat_misses", "frames", "bytes_sent",
                         "bytes_recv"}),
        locked_methods=frozenset({"_call_once_locked",
                                  "_ensure_locked", "_drop_locked",
                                  "_send_truncated_locked"}),
        exempt_methods=frozenset({"lease_age", "lease_expired"}),
        note="call()/close()/lease_expire() take _lock; lease_age/"
             "lease_expired read one monotonic float (atomic under "
             "the GIL) so the router's death triage never blocks on "
             "an RPC in flight"),
    # replica agent (server side of the transport): RPC handler
    # threads and the drive thread serialize every engine touch on
    # the agent lock — the GenerationServer discipline, one process
    # over
    "fleet.remote.ReplicaAgent": SharedStateSpec(
        lock="_lock",
        attrs=frozenset({"_by_key", "_key_order", "_trace_ids",
                         "_mut", "_ho_seq", "_ho_last"}),
        proxies=frozenset({"_sup"}),
        locked_methods=frozenset({"_harvest_locked",
                                  "_remember_key_locked",
                                  "_snapshot_locked", "_rpc_hello",
                                  "_rpc_ping", "_rpc_submit",
                                  "_rpc_cancel",
                                  "_rpc_audit", "_rpc_drain",
                                  "_rpc_resume", "_rpc_shutdown",
                                  "_rpc_take_handoffs",
                                  "_rpc_admit_handoff",
                                  "_rpc_admit_degraded"}),
        exempt_methods=frozenset({"start", "stop", "die", "join"}),
        note="_dispatch takes _lock around every engine-touching op; "
             "the drive loop steps + harvests under the same lock, "
             "then PUBLISHES events/snapshot under the subordinate "
             "_buf_lock (strict order _lock > _buf_lock), which is "
             "all the sync heartbeat ever takes — a first-compile "
             "step can hold _lock for seconds and must not expire a "
             "healthy lease; lifecycle flags (_stop/_closing/_fatal) "
             "are single-writer booleans read monotonically"),
    # fleet HTTP front: same discipline as GenerationServer (it IS
    # GenerationServer's plumbing over the router)
    "fleet.server.FleetServer": SharedStateSpec(
        lock="_lock",
        # _queues is inherited and only touched by GenerationServer's
        # own methods (checked under ITS spec); the subclass body
        # reaches _fatal and the proxies only
        attrs=frozenset({"_fatal"}),
        proxies=frozenset({"engine", "_engine", "_driver",
                           "_supervisor"}),
        locked_methods=frozenset({"_is_ready_locked",
                                  "_health_locked", "_fleet_locked"}),
        exempt_methods=frozenset({"engine", "_driver", "restarts",
                                  "router", "start", "stop"}),
        note="inherits GenerationServer's contract; fleet_state() "
             "bounded-waits on _lock (the health_snapshot idiom) "
             "before reaching the router through _fleet_locked"),
}


# ---------------------------------------------------------------------------
# claim lifecycle: refcounted resources the CFG rules audit
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ClaimSpec:
    """One refcounted claim kind the allocator facade hands out.

    ``acquires``/``releases`` are CALL NAMES (bare function or
    attribute method names): a call to an acquire name creates a live
    claim at that CFG node; a call to a release name — or to any
    function whose interprocedural summary transitively reaches one
    (``_release_engine_claims`` → ``release_row``/``discard_swap``/
    ``release_extra_claims``) — retires it.

    ``value_bearing`` claims return a token (swap handle, export
    state, engine-local rid) the caller must route somewhere: the
    claim also retires when the token ESCAPES — returned, stored into
    an attribute/subscript (the audited registries: ``_swap_handles``,
    ``_handoff_ready``, ``local_rids``...), or passed onward.  A
    value-bearing claim leaks when ANY path reaches a function exit
    with the token neither released nor escaped.  Value-less claims
    (``alloc_row`` binds pages to a row the scheduler already owns)
    leak only on EXCEPTIONAL paths — the unwind that strands the row.

    ``scope``: ``"cfg"`` kinds are checked by the claim-lifecycle /
    except-swallow rules; ``"registry"`` kinds live across ticks
    inside audited containers, where an intraprocedural CFG proof is
    the wrong tool — their accounting is pinned at runtime by
    ``PagedKVCache.audit()`` and the fleet/disagg reclamation tests
    (the taxonomy table in docs/STATIC_ANALYSIS.md documents both).
    """

    kind: str
    acquires: FrozenSet[str]
    releases: FrozenSet[str]
    value_bearing: bool = True
    scope: str = "cfg"                 # "cfg" | "registry"
    leak: str = ""                     # what a leak strands
    note: str = ""


CLAIMS: Dict[str, ClaimSpec] = {
    # device KV pages claimed for a row: alloc_row/alloc_row_prefix
    # bind pages to a slot the scheduler owns from that moment, and
    # swap_in_row converts a parked record back into row pages.  The
    # steady-state release is retirement/preemption (release_row via
    # _release_slot); the CFG-checked hazard is the UNWIND — a
    # prefill fault after the alloc strands the slot off the free
    # list unless the quarantine/rollback path releases it.
    "device-pages": ClaimSpec(
        kind="device-pages",
        acquires=frozenset({"alloc_row", "alloc_row_prefix",
                            "swap_in_row"}),
        releases=frozenset({"release_row"}),
        value_bearing=False,
        leak="slot pages off the free list forever (admission "
             "faults, PR 5's stranded-slot class; partially-prefilled "
             "mixed rows parked in _mixed_pref; horizon pre-claims "
             "stranded past a trim/retire)",
        note="swap_in_row acquires row pages AND releases the swap "
             "record it consumes; the mixed lane's carve transfers "
             "its claim into _mixed_pref, whose rows the sweep/"
             "quarantine/restart paths release (audit-pinned by "
             "test_serving_mixed).  ensure_capacity[_batch] GROWS an "
             "existing row claim (the decode-horizon H-token "
             "pre-claim rides it): the grown pages belong to the row "
             "and release through the same release_row seam on "
             "retire/trim/cancel/quarantine — audit-pinned by "
             "test_serving_horizon.  The spec lane's DRAFT cache is "
             "a second pool under the SAME claim: _spec_admit "
             "acquires the draft row alongside the target row, "
             "per-round growth claims C slots for spec-on rows only "
             "(the aux-rows mask — off rows must not leak draft "
             "pages), and _release_aux releases both pools through "
             "every retire/preempt/cancel/quarantine path — "
             "audit-pinned on both caches by test_serving_spec"),
    # host-tier swap record: parked preempted rows + adopted handoff
    # blobs.  The handle MUST land in an audited registry
    # (_swap_handles) or be discarded — a dropped handle pins host
    # pages and held device refs until engine death.
    "swap-record": ClaimSpec(
        kind="swap-record",
        acquires=frozenset({"swap_out_row", "adopt_swap"}),
        releases=frozenset({"swap_in_row", "discard_swap"}),
        value_bearing=True,
        leak="host pages + held device refs pinned by an orphaned "
             "record (audit() fails)"),
    # cross-cache KV export (disaggregated handoff ship half): the
    # opaque state must reach a HandoffRecord (or be fetched /
    # discarded) on every path, including the degrade branches.
    "export-record": ClaimSpec(
        kind="export-record",
        acquires=frozenset({"export_row"}),
        releases=frozenset({"export_fetch", "export_discard",
                            "materialize"}),
        value_bearing=True,
        leak="staging host pages of an un-shipped export (orphaned "
             "export records on prefill death, PR 9's class)",
        note="HandoffRecord.discard is credited through its summary "
             "(it calls export_discard), NOT by the bare name "
             "`discard` — that would collide with set.discard "
             "bookkeeping on the very triage paths under check"),
    # an engine-local placement: submit()/admit_* return a local rid
    # whose engine-side state only the caller can still reach — it
    # must commit to a routing table (local_rids, _decode_rids,
    # _queues) before anything on the path can raise, or the replica
    # generates for a client nobody can deliver to.
    "placed-request": ClaimSpec(
        kind="placed-request",
        acquires=frozenset({"submit", "admit_handoff",
                            "admit_degraded"}),
        releases=frozenset({"cancel"}),
        value_bearing=True,
        leak="an accepted request no routing table maps: tokens "
             "generated for nobody, failover/cancel blind to it"),
    # a live client connection to a remote replica agent: opened at
    # handle spawn/replace, it must reach close() (normal teardown)
    # or lease_expire() (the death edge) on every path — including
    # the hello-failed unwind, where an unreleased socket would pin
    # an FD per failed replace retry forever.
    "connection-lease": ClaimSpec(
        kind="connection-lease",
        acquires=frozenset({"open_connection"}),
        releases=frozenset({"close", "lease_expire"}),
        value_bearing=True,
        leak="a leaked socket FD + a peer that still believes a "
             "client holds its lease (handle replace-retry loops "
             "would exhaust FDs)"),
    # -- registry-scope kinds (runtime-audited, documented here) ------
    "prefix-ref": ClaimSpec(
        kind="prefix-ref",
        acquires=frozenset({"register_prefix", "alloc_row_prefix"}),
        releases=frozenset({"release_row"}),
        value_bearing=False,
        scope="registry",
        leak="un-evictable index pages / un-purged fleet "
             "prefix-owner entries steering traffic to cold replicas",
        note="refcount identities pinned by PagedKVCache.audit(); "
             "fleet _prefix_owner purge pinned by the replace tests"),
    "handoff-record": ClaimSpec(
        kind="handoff-record",
        acquires=frozenset({"take_handoffs"}),
        releases=frozenset({"discard", "admit_handoff",
                            "release_extra_claims"}),
        value_bearing=True,
        scope="registry",
        leak="records stranded between engines on cancel/expiry/"
             "death (reclaimed through _release_engine_claims)",
        note="owned by coordinator/router deques across ticks; "
             "every triage branch discards or ships — chaos-tested"),
    # a scaled-up replica slot: add_replica appends a live handle
    # (engine threads, sockets, device pages behind it) that only the
    # router's replica table reaches — it must park RETIRED through
    # retire_replica's drain (or the DEAD->retire edge) before its
    # resources are truly free.  Registry-scope: the lifecycle pass
    # in _step_locked audits every slot each tick.
    "replica-handle": ClaimSpec(
        kind="replica-handle",
        acquires=frozenset({"add_replica"}),
        releases=frozenset({"retire_replica", "retire"}),
        value_bearing=True,
        scope="registry",
        leak="a live replica no controller retires: engine threads + "
             "device pages held past the fleet's need, autoscaler "
             "bounds silently violated",
        note="RETIRED slots stay in _replicas (fleet rids index the "
             "table) but hold no engine claims — retire() runs "
             "_release_engine_claims / closes the agent connection; "
             "pinned by the autoscaler chaos tests"),
    # a live trace entry: begun at submit, it must reach
    # finish_trace on EVERY request ending (retire / synth finish /
    # rejected placement) or it squats in Tracer._live — bounded by
    # max_live eviction to "abandoned", audited by the
    # no-live-traces-after-drain pins in tests/test_tracing.py.
    "trace-entry": ClaimSpec(
        kind="trace-entry",
        acquires=frozenset({"begin_trace"}),
        releases=frozenset({"finish_trace", "close"}),
        value_bearing=True,
        scope="registry",
        leak="live traces pinned in Tracer._live until the "
             "max_live eviction brands them 'abandoned' (a request "
             "that ended without closing its trace)",
        note="owned by the Request/_FleetRequest/_DisaggRequest that "
             "carries the context across engines; engine-minted "
             "contexts close at retirement, managed ones at the "
             "router/coordinator finished-merge"),
}


def checked_claims() -> Dict[str, ClaimSpec]:
    """The kinds the CFG rules enforce (``scope == "cfg"``)."""
    return {k: s for k, s in CLAIMS.items() if s.scope == "cfg"}


def claims_doc_lines() -> List[str]:
    """The markdown taxonomy rows docs/STATIC_ANALYSIS.md must carry,
    generated from :data:`CLAIMS` so the doc cannot drift from the
    registry (asserted by tests/test_analysis.py, the same discipline
    as the THREAD_SAFETY table)."""
    rows = []
    for kind in sorted(CLAIMS):
        s = CLAIMS[kind]
        acq = ", ".join(f"`{a}`" for a in sorted(s.acquires))
        rel = ", ".join(f"`{r}`" for r in sorted(s.releases))
        rows.append(f"| `{kind}` | {acq} | {rel} | {s.scope} | "
                    f"{s.leak} |")
    return rows


# ---------------------------------------------------------------------------
# thread-safety contract (consistency-checked against the docs)
# ---------------------------------------------------------------------------
# designation -> meaning:
#   "any-thread"          safe to call from any thread as-is
#   "external-lock"       safe from any thread ONLY behind one shared
#                         lock (GenerationServer serializes on _lock)
#   "engine-thread-only"  must run on the thread driving step()
THREAD_SAFETY: Dict[str, Tuple[str, str]] = {
    "submit": ("external-lock",
               "validates + enqueues; races cancel()/step() on _queue "
               "and the rid counter"),
    "cancel": ("external-lock",
               "marks the rid; the engine retires it at the next "
               "flush point"),
    "step": ("engine-thread-only",
             "drives admission + decode; owns every scheduler "
             "structure"),
    "finished": ("engine-thread-only",
                 "drains the finished list the step loop appends to"),
    "drain_stream": ("engine-thread-only",
                     "drains the token stream the step loop appends "
                     "to"),
    "has_work": ("engine-thread-only",
                 "reads _queue/_active without synchronization"),
    "queued_tokens": ("any-thread",
                      "sums atomic tuple() snapshots of _queue and "
                      "the mixed lane's parked-row map, so "
                      "scrape-thread gauges read it lock-free (at "
                      "most one admission stale); exact behind the "
                      "serving front's _lock"),
    "retry_after_s": ("external-lock",
                      "reads throughput counters the step loop "
                      "writes; submit() consults it under the same "
                      "serialization"),
    "run_to_completion": ("engine-thread-only",
                          "wraps step()/finished()"),
}


def thread_safety_doc_lines() -> List[str]:
    """The markdown table rows docs/FAULT_TOLERANCE.md must carry,
    generated from :data:`THREAD_SAFETY` so prose and registry cannot
    diverge (asserted by tests/test_analysis.py)."""
    rows = []
    for api in sorted(THREAD_SAFETY):
        designation, why = THREAD_SAFETY[api]
        rows.append(f"| `{api}()` | `{designation}` | {why} |")
    return rows
