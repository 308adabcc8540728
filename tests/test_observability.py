"""Unified metrics + tracing layer (paddle_tpu.observability).

Covers: registry semantics (counter monotonicity, histogram buckets,
thread-safety, Prometheus exposition format), the structured-event
ring (bounded, seq-tagged, chrome-trace export merged with profiler
spans), end-to-end engine instrumentation (TTFT/TPOT/queue-wait
samples, preemption + prefix-cache counters consistent with the
engine's own bookkeeping), the comm-watchdog routing, and the
metric-name lint against docs/OBSERVABILITY.md.
"""

import json
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observability import (Counter, EngineMetrics, EventRing,
                                      Gauge, Histogram, MetricsRegistry)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_monotonic_and_negative_rejected():
    r = MetricsRegistry()
    c = r.counter("paddle_tpu_test_things_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_registration_idempotent_type_mismatch_raises():
    r = MetricsRegistry()
    c1 = r.counter("paddle_tpu_test_things_total")
    c2 = r.counter("paddle_tpu_test_things_total")
    assert c1 is c2                       # get-or-create
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("paddle_tpu_test_things_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        r.counter("bad name!")


def test_gauge_set_function_and_error_isolation():
    r = MetricsRegistry()
    g = r.gauge("paddle_tpu_test_depth_count")
    g.set(4)
    assert g.value == 4.0
    g.inc()
    assert g.value == 5.0
    g.set_function(lambda: 7.25)
    assert g.value == 7.25
    g.set(1.0)                            # set clears the callback
    assert g.value == 1.0

    def boom():
        raise RuntimeError("scrape must survive")

    g.set_function(boom)
    assert g.value != g.value             # NaN, not an exception
    assert r.snapshot()["paddle_tpu_test_depth_count"]["value"] is None


def test_histogram_buckets_cumulative_and_validation():
    r = MetricsRegistry()
    h = r.histogram("paddle_tpu_test_latency_seconds",
                    buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(5.605)
    assert h.cumulative() == [1, 3, 4, 5]     # le=0.01/0.1/1.0/+Inf
    snap = h.snapshot()
    assert snap["buckets"]["+Inf"] == 5
    with pytest.raises(ValueError, match="strictly increase"):
        Histogram("paddle_tpu_test_bad_seconds", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("paddle_tpu_test_bad_seconds", buckets=())


def test_thread_safety_smoke():
    r = MetricsRegistry()
    c = r.counter("paddle_tpu_test_hammer_total")
    h = r.histogram("paddle_tpu_test_hammer_seconds", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.25)

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000
    assert h.count == 8000
    assert h.cumulative() == [8000, 8000]


_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{le=\"[^\"]+\"\})? "
    r"(?:[+-]?(?:[0-9.e+-]+|Inf)|NaN))$")


def test_prometheus_exposition_parses():
    r = MetricsRegistry()
    r.counter("paddle_tpu_test_things_total", "things done").inc(3)
    r.gauge("paddle_tpu_test_depth_count", "queue depth").set(2)
    h = r.histogram("paddle_tpu_test_latency_seconds", "latency",
                    buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(2.0)
    text = r.render_prometheus()
    assert text.endswith("\n")
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"malformed line: {line!r}"
    # histogram exposition: cumulative buckets, +Inf == count
    assert 'paddle_tpu_test_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'paddle_tpu_test_latency_seconds_bucket{le="+Inf"} 2' in text
    assert "paddle_tpu_test_latency_seconds_count 2" in text
    assert "# TYPE paddle_tpu_test_things_total counter" in text
    # snapshot is JSON-safe
    json.dumps(r.snapshot())


# ---------------------------------------------------------------------------
# event ring
# ---------------------------------------------------------------------------
def test_event_ring_bounded_and_seq_tagged():
    ring = EventRing(capacity=4)
    for i in range(6):
        ring.emit("tick", i=i)
    assert len(ring) == 4
    assert ring.dropped == 2
    evs = ring.recent()
    assert [e["i"] for e in evs] == [2, 3, 4, 5]
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and seqs[-1] == 6
    # the tail-follow protocol: only events after `since`
    assert [e["i"] for e in ring.recent(since=seqs[1])] == [4, 5]
    assert len(ring.recent(n=2)) == 2
    lines = ring.to_jsonl().splitlines()
    assert len(lines) == 4 and json.loads(lines[0])["name"] == "tick"


def test_event_ring_since_follower_sees_wrap_gap():
    """Regression (ISSUE-13 satellite): when the ring wraps between
    polls, the tail-follow protocol must REPORT the lost events —
    ``recent_with_gap`` returns the dropped delta instead of
    silently skipping them."""
    ring = EventRing(capacity=4)
    for i in range(3):
        ring.emit("tick", i=i)
    evs, gap = ring.recent_with_gap(since=1)
    assert gap == 0 and [e["i"] for e in evs] == [1, 2]
    cursor = 3
    for i in range(3, 9):                     # wraps: seqs 1..4 gone
        ring.emit("tick", i=i)
    evs, gap = ring.recent_with_gap(since=cursor)
    # ring holds seqs 6..9; cursor 3 → seqs 4 and 5 fell off unseen
    assert gap == 2
    assert [e["seq"] for e in evs] == [6, 7, 8, 9]
    # a follower that kept up sees no gap
    evs, gap = ring.recent_with_gap(since=6)
    assert gap == 0 and [e["seq"] for e in evs] == [7, 8, 9]
    # everything expired (cursor far behind an emptied window): the
    # whole distance is the gap
    ring2 = EventRing(capacity=2)
    for i in range(10):
        ring2.emit("t")
    evs, gap = ring2.recent_with_gap(since=2)
    assert gap == 6 and [e["seq"] for e in evs] == [9, 10]
    # recent() still matches the gap-aware batch
    assert ring2.recent(since=2) == evs


def test_metrics_dump_events_prints_gap_marker(capsys, monkeypatch):
    """tools/metrics_dump.py ``events`` prints a visible
    ``[gap: N events lost]`` marker when the server reports a wrap."""
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        md = importlib.import_module("metrics_dump")
    finally:
        sys.path.pop(0)
    bodies = [json.dumps({"events": [{"name": "t", "seq": 9}],
                          "gap": 4, "dropped": 4}).encode()]
    monkeypatch.setattr(md, "_get",
                        lambda url, timeout=10.0: bodies.pop(0))

    class A:
        url = "http://x"
        n = 50
        follow = False
        interval = 0.0

    assert md.cmd_events(A()) == 0
    out = capsys.readouterr().out
    assert "[gap: 4 events lost]" in out
    assert '"seq": 9' in out


def test_ring_span_no_import_in_hot_path(monkeypatch):
    """Regression (ISSUE-13 satellite): ``EventRing.span()`` used to
    re-run ``from ..profiler.utils import ...`` inside every
    ``__enter__`` — the types must resolve once and stay pinned."""
    import builtins
    ring = EventRing()
    with ring.span("warm"):                   # resolves the types
        pass
    real_import = builtins.__import__
    hits = []

    def counting(name, *a, **kw):
        if "profiler" in name:
            hits.append(name)
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", counting)
    for _ in range(3):
        with ring.span("hot"):
            pass
    assert hits == [], ("span __enter__ re-imported profiler.utils "
                        f"on the hot path: {hits}")


def test_event_ring_chrome_export_merges_profiler_spans(tmp_path):
    from paddle_tpu.profiler.utils import (RecordEvent,
                                           _disable_collection,
                                           _drain_spans,
                                           _enable_collection)
    ring = EventRing()
    ring.emit("instant_event", detail="x")
    with ring.span("spanned_work", stage="test"):
        time.sleep(0.005)
    _enable_collection()
    try:
        with RecordEvent("profiler_span"):
            time.sleep(0.002)
        path = ring.export_chrome_trace(str(tmp_path / "trace.json"))
    finally:
        _disable_collection()
        _drain_spans()
    with open(path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"instant_event", "spanned_work", "profiler_span"} <= names
    span = next(e for e in trace["traceEvents"]
                if e["name"] == "spanned_work")
    assert span["ph"] == "X" and span["dur"] >= 4000   # >= 4ms in us
    inst = next(e for e in trace["traceEvents"]
                if e["name"] == "instant_event")
    assert inst["ph"] == "i" and inst["args"]["detail"] == "x"


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
def _cfg():
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    return LlamaPretrainConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, loss_chunks=1,
        use_pallas_attention=False)


def _params(cfg):
    from jax.sharding import Mesh
    from paddle_tpu.models.llama_pretrain import init_params
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                ("dp", "pp", "sharding", "sep", "mp"))
    return init_params(cfg, jax.random.PRNGKey(0), mesh)


def _engine(reg, num_pages=64, pages_max=8, batch=2, **kw):
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.serving_engine import ContinuousBatchingEngine
    cfg = _cfg()
    params = _params(cfg)
    cache = PagedKVCache(cfg, num_pages=num_pages, pages_max=pages_max,
                         batch=batch, page=16)
    return ContinuousBatchingEngine(cfg, params, cache,
                                    metrics_registry=reg, **kw)


def _val(reg, name):
    m = reg.get(name)
    return m.value


def test_engine_metrics_end_to_end_match_bookkeeping():
    reg = MetricsRegistry()
    eng = _engine(reg)
    rng = np.random.RandomState(5)
    n_req = 4
    for _ in range(n_req):
        eng.submit(rng.randint(1, 128, (int(rng.randint(4, 14)),)),
                   max_new_tokens=int(rng.randint(3, 7)))
    done = eng.run_to_completion()
    assert len(done) == n_req

    # counters mirror the engine's own bookkeeping exactly
    assert _val(reg, "paddle_tpu_engine_requests_submitted_total") \
        == n_req
    assert _val(reg, "paddle_tpu_engine_requests_finished_total") \
        == eng.requests_finished == n_req
    assert _val(reg, "paddle_tpu_engine_decode_steps_total") \
        == eng.decode_steps
    assert _val(reg, "paddle_tpu_engine_tokens_generated_total") \
        == eng.tokens_generated
    assert _val(reg, "paddle_tpu_engine_prefill_dispatches_total") \
        == eng.prefill_calls
    assert _val(reg, "paddle_tpu_engine_preemptions_total") \
        == eng.preemptions == 0

    # one lifecycle sample per request
    ttft = reg.get("paddle_tpu_request_ttft_seconds")
    tpot = reg.get("paddle_tpu_request_tpot_seconds")
    qw = reg.get("paddle_tpu_request_queue_wait_seconds")
    assert ttft.count == n_req and qw.count == n_req
    assert tpot.count == n_req        # every request generated > 1 tok
    assert 0 < ttft.sum < 600 and 0 < tpot.sum < 600
    dec = reg.get("paddle_tpu_engine_decode_step_seconds")
    assert dec.count == eng.decode_steps and dec.sum > 0

    # timestamps are ordered per request
    for req in done:
        assert req.t_submit <= req.t_admit <= req.t_first_token \
            <= req.t_finish

    # drained engine: callback gauges read empty
    assert _val(reg, "paddle_tpu_engine_active_requests_count") == 0
    assert _val(reg, "paddle_tpu_engine_queued_requests_count") == 0
    assert _val(reg, "paddle_tpu_engine_batch_occupancy_ratio") == 0
    assert _val(reg, "paddle_tpu_kvcache_free_pages_count") \
        == eng.cache.free_pages()
    assert _val(reg, "paddle_tpu_kvcache_page_utilization_ratio") == 0


def test_engine_metrics_preemption_counter():
    # 4 usable pages, 2 slots, two 16+20-token requests: concurrent
    # growth forces preemption (mirrors test_serving_engine's
    # pool-exhaustion scenario)
    reg = MetricsRegistry()
    eng = _engine(reg, num_pages=5, pages_max=4)
    rng = np.random.RandomState(7)
    for _ in range(2):
        eng.submit(rng.randint(1, 128, (16,)), max_new_tokens=20)
    done = eng.run_to_completion()
    assert len(done) == 2
    assert eng.preemptions >= 1
    assert _val(reg, "paddle_tpu_engine_preemptions_total") \
        == eng.preemptions
    # preemption re-admission must not double-count lifecycle samples
    assert reg.get("paddle_tpu_request_ttft_seconds").count == 2
    assert reg.get("paddle_tpu_request_queue_wait_seconds").count == 2
    names = [e["name"] for e in eng.metrics.ring.recent()]
    assert "preemption" in names


def test_engine_metrics_prefix_cache_hits():
    # packed=False: this test pins the CHUNKED prefix-caching lane's
    # instruments (prefill_chunks_total); the packed lane admits in
    # one dispatch and has its own instrument tests
    # (tests/test_packed_prefill.py)
    reg = MetricsRegistry()
    eng = _engine(reg, enable_prefix_caching=True, packed=False)
    rng = np.random.RandomState(9)
    prefix = rng.randint(1, 128, (32,))        # two full 16-tok pages
    eng.submit(prefix, max_new_tokens=3)
    eng.run_to_completion()
    eng.submit(np.concatenate([prefix, rng.randint(1, 128, (5,))]),
               max_new_tokens=3)
    eng.run_to_completion()
    assert eng.cache.prefix_hits >= 2
    assert _val(reg, "paddle_tpu_kvcache_prefix_hit_pages_total") \
        == eng.cache.prefix_hits
    assert _val(reg, "paddle_tpu_kvcache_prefix_miss_pages_total") > 0
    assert reg.get("paddle_tpu_engine_prefill_chunks_total").value > 0


def test_speculative_engine_metrics():
    from paddle_tpu.models.paged_decode import PagedKVCache
    from paddle_tpu.models.speculative import SpeculativeEngine
    reg = MetricsRegistry()
    cfg = _cfg()
    params = _params(cfg)
    cache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                         page=16)
    dcache = PagedKVCache(cfg, num_pages=64, pages_max=8, batch=2,
                          page=16)
    eng = SpeculativeEngine(cfg, params, cache, cfg, params, dcache,
                            gamma=3, metrics_registry=reg)
    rng = np.random.RandomState(11)
    eng.submit(rng.randint(1, 128, (9,)), max_new_tokens=6)
    eng.run_to_completion()
    assert eng.spec_rounds >= 1
    assert _val(reg, "paddle_tpu_engine_spec_rounds_total") \
        == eng.spec_rounds
    assert _val(reg, "paddle_tpu_engine_spec_drafted_tokens_total") \
        == eng.spec_drafted
    assert _val(reg, "paddle_tpu_engine_spec_accepted_tokens_total") \
        == eng.spec_accepted
    assert _val(reg, "paddle_tpu_engine_spec_gamma_tokens") \
        == eng.gamma
    # accept-length histogram: one observation per spec-on row per
    # round, each in [0, gamma]
    h = reg.get("paddle_tpu_engine_spec_accept_len_tokens")
    assert h.count == eng.spec_rounds
    assert h.sum == eng.spec_accepted
    # same-model draft: every draft accepted -> lifetime ratio 1.0
    acc = _val(reg, "paddle_tpu_engine_spec_acceptance_ratio")
    assert acc == pytest.approx(
        eng.spec_accepted / max(eng.spec_drafted, 1))


def test_instrumentation_overhead_small():
    """Decode-loop instrumentation is a handful of host float adds per
    step — measured well under 5% on this config; the bound here is
    loose so CI timer noise cannot flake tier-1 (the measured figure
    is recorded in docs/OBSERVABILITY.md)."""
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 128, (10,)) for _ in range(4)]

    def run(eng):
        for p in prompts:
            eng.submit(p, max_new_tokens=12)
        t0 = time.perf_counter()
        eng.run_to_completion()
        return time.perf_counter() - t0

    eng_off = _engine(False)
    eng_on = _engine(MetricsRegistry())
    assert eng_off.metrics is None and eng_on.metrics is not None
    run(eng_off), run(eng_on)                 # warm both compiles
    # interleave A/B so background-load drift hits both sides; min
    # over repeats discards GC/scheduler spikes
    offs, ons = [], []
    for _ in range(4):
        offs.append(run(eng_off))
        ons.append(run(eng_on))
    t_off, t_on = min(offs), min(ons)
    assert t_on <= t_off * 2.0, \
        f"instrumented {t_on:.4f}s vs bare {t_off:.4f}s"


# ---------------------------------------------------------------------------
# comm watchdog routing
# ---------------------------------------------------------------------------
def test_comm_watchdog_reports_through_observability():
    from paddle_tpu.distributed.communication import watchdog as W
    from paddle_tpu.flags import flags
    reg = MetricsRegistry()
    ring = EventRing()
    prev = flags.FLAGS_comm_timeout_s
    mgr = W.CommTaskManager(scan_interval=0.02)
    mgr.bind_metrics(reg, ring)
    mgr.set_abort_handler(lambda t: None)     # quiet stderr
    try:
        flags.FLAGS_comm_timeout_s = 0.05
        t = mgr.start_task("all_gather", "mp_group")
        assert _val(reg,
                    "paddle_tpu_comm_watchdog_outstanding_count") == 1
        age = _val(reg,
                   "paddle_tpu_comm_watchdog_heartbeat_age_seconds")
        assert 0 <= age < 5
        deadline = time.time() + 5
        while not t.timed_out and time.time() < deadline:
            time.sleep(0.02)
        assert t.timed_out
        assert _val(reg,
                    "paddle_tpu_comm_watchdog_timeouts_total") == 1
        ev = [e for e in ring.recent() if e["name"] == "comm_timeout"]
        assert ev and ev[0]["op"] == "all_gather" \
            and ev[0]["group"] == "mp_group"
        mgr.finish_task(t)
        assert _val(reg,
                    "paddle_tpu_comm_watchdog_outstanding_count") == 0
    finally:
        flags.FLAGS_comm_timeout_s = prev
        mgr.shutdown()


# ---------------------------------------------------------------------------
# naming-convention lint
# ---------------------------------------------------------------------------
_UNITS = ("total", "seconds", "ratio", "count", "tokens", "pages",
          "bytes", "info")
_CONVENTION = re.compile(
    r"^paddle_tpu_[a-z][a-z0-9]*(_[a-z0-9]+)+_(%s)$" % "|".join(_UNITS))


def test_metric_names_lint():
    """Every metric the stack registers follows
    ``paddle_tpu_<subsystem>_<name>_<unit>`` and is documented in
    docs/OBSERVABILITY.md."""
    import os
    from paddle_tpu.distributed.communication import watchdog as W
    from paddle_tpu.inference import serving

    reg = MetricsRegistry()
    EngineMetrics(reg)                        # engine + cache + spec
    from paddle_tpu.observability import (DisaggMetrics, FleetMetrics,
                                          TraceStore,
                                          TransportMetrics)
    FleetMetrics(reg)                         # fleet router tier
    DisaggMetrics(reg)                        # disagg handoff tier
    TransportMetrics(reg)                     # sockets transport tier
    TraceStore(metrics_registry=reg)          # tail-sampled traces
    mgr = W.CommTaskManager(scan_interval=60)
    mgr.bind_metrics(reg, EventRing())
    mgr.shutdown()
    serving._http_metrics(reg)

    doc_path = os.path.join(os.path.dirname(__file__), "..", "docs",
                            "OBSERVABILITY.md")
    with open(doc_path) as f:
        doc = f.read()
    names = reg.names()
    assert len(names) >= 20, "catalogue unexpectedly small"
    for name in names:
        assert _CONVENTION.match(name), (
            f"{name} violates paddle_tpu_<subsystem>_<name>_<unit> "
            f"(unit in {_UNITS})")
        assert name in doc, f"{name} missing from docs/OBSERVABILITY.md"
