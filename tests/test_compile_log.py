"""The program's own compile log (``paddle_tpu/observability/
compile_log.py``): what JAX says it traced, lowered, compiled or loaded
from the persistent cache, by program name and on both clocks.  The
process-wide log is the one ``import paddle_tpu`` enabled; the cases that
need exact contents feed a log of their own by hand."""

import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu  # noqa: F401  (enables the listener)
from _compile_feed import BACKEND_EV, LOWER_EV, TRACE_EV, feed
from paddle_tpu.observability import (EventRing, MetricsRegistry,
                                      compile_log, default_registry,
                                      default_ring)
from paddle_tpu.observability.compile_log import (BACKEND, LOWER, TRACE,
                                                  CompileLog)


def of(program, since_id=0):
    return [r for r in compile_log.records()
            if r["program"] == program and r["id"] > since_id]


def last_id():
    recs = compile_log.records()
    return max((r["id"] for r in recs), default=0)


def test_a_fresh_function_yields_three_records_that_share_a_cause():
    def fresh_three(x):
        return jnp.cos(x) * 3.0
    t0 = time.time()
    jax.jit(fresh_three)(jnp.ones((4, 4)))
    t1 = time.time()
    recs = of("fresh_three")
    assert [r["name"] for r in recs] == [TRACE, LOWER, BACKEND]
    assert [r["raw"] for r in recs] == [
        "fresh_three", "jit(fresh_three)", "jit(fresh_three)"]
    trace, lower, backend = recs
    assert trace["cause"] == lower["cause"] == backend["cause"] \
        == trace["id"]
    assert trace["parent"] is None and backend["cache"] == "off"
    offsets = []
    for a, b in zip(recs, recs[1:]):
        assert a["end"] <= b["start"] + 1e-6          # in order
    for r in recs:
        assert r["end"] >= r["start"]
        assert r["end_epoch_ns"] >= r["start_epoch_ns"]
        assert r["dur_s"] == pytest.approx(r["end"] - r["start"])
        assert t0 - 1e-3 <= r["start_epoch_ns"] * 1e-9 <= t1 + 1e-3
        offsets.append(r["end_epoch_ns"] * 1e-9 - r["end"])
    # the two clocks differ by one constant across records
    assert max(offsets) - min(offsets) < 5e-3


def test_a_second_call_of_the_same_function_yields_none():
    def called_twice(x):
        return x + 2.0
    f = jax.jit(called_twice)
    f(jnp.ones((3,)))
    n = len(of("called_twice"))
    f(jnp.ones((3,)))
    assert n == 3 and len(of("called_twice")) == 3


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, every
    entry kept however small; the suite's setting (off) afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_miss_then_a_hit_whose_backend_record_is_the_retrieval(
        persistent_cache):
    def cached_once(x):
        return jnp.tanh(x) @ x
    x = jnp.ones((16, 16))
    sums0 = compile_log.totals()
    jax.jit(cached_once)(x)
    jax.clear_caches()
    jax.jit(cached_once)(x)
    first, second = [r for r in of("cached_once") if r["name"] == BACKEND]
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit"
    assert second["retrieval_s"] <= second["dur_s"] + 1e-3
    assert isinstance(second["saved_s"], float)     # as it comes
    # traced and lowered again all the same: the key is made after both
    assert [r["name"] for r in of("cached_once")].count(TRACE) == 2
    assert [r["name"] for r in of("cached_once")].count(LOWER) == 2
    sums = compile_log.totals()
    assert sums["hits"] - sums0["hits"] == 1
    assert sums["misses"] - sums0["misses"] == 1


def test_a_nested_trace_names_its_parent_and_is_not_counted_twice():
    @jax.jit
    def nested_inner(x):
        return jnp.sin(x) @ x

    @jax.jit
    def nested_outer(x):
        return nested_inner(x) + 1.0
    x = jnp.ones((8, 8))
    before = compile_log.totals()
    mark = last_id()
    nested_outer(x)
    new = [r for r in compile_log.records() if r["id"] > mark]
    outer, = [r for r in new if r["program"] == "nested_outer"
              and r["name"] == TRACE]
    inner, = [r for r in new if r["program"] == "nested_inner"]
    assert inner["name"] == TRACE and inner["parent"] == outer["id"]
    assert inner["cause"] == outer["id"] and outer["parent"] is None
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    children = [r for r in new if r["parent"] == outer["id"]]
    assert outer["self_s"] == pytest.approx(
        outer["dur_s"] - sum(c["dur_s"] for c in children), abs=1e-6)
    # only the outer program is lowered and compiled
    assert [r["program"] for r in new if r["name"] != TRACE] == [
        "nested_outer", "nested_outer"]
    traced = compile_log.totals()["trace_s"] - before["trace_s"]
    tops = [r for r in new if r["name"] == TRACE and r["parent"] is None]
    assert traced == pytest.approx(sum(r["dur_s"] for r in tops), abs=1e-6)
    assert traced < sum(r["dur_s"] for r in new if r["name"] == TRACE)
    row, = [r for r in compile_log.by_program()
            if r["program"] == "nested_outer"]
    assert row["trace_s"] >= outer["dur_s"] - 1e-6 and row["programs"] == 1
    assert not [r for r in compile_log.by_program()
                if r["program"] == "nested_inner"]


def test_enabling_twice_adds_one_listener():
    import jax._src.monitoring as mon
    log = compile_log.default_log()
    assert compile_log.enable() is log and compile_log.enable() is log
    assert mon.get_event_time_span_listeners().count(log.on_span) == 1
    assert mon.get_scalar_listeners().count(log.on_scalar) == 1
    assert mon.get_event_listeners().count(log.on_event) == 1
    assert mon.get_event_duration_listeners().count(log.on_duration) == 1

    def recorded_once(x):
        return x * 5.0
    jax.jit(recorded_once)(jnp.ones((2,)))
    assert [r["name"] for r in of("recorded_once")] == [TRACE, LOWER,
                                                        BACKEND]


def test_the_list_is_bounded_and_counts_what_fell_out():
    ring = EventRing()
    log = CompileLog(capacity=4, ring=ring)
    t = 1000.0
    for i in range(3):
        t = feed(log, f"p{i}", t, trace=1.0, lower=2.0, backend=4.0)
    assert len(log.records()) == 4 and log.totals()["dropped"] == 5
    assert [r["program"] for r in log.records()] == ["p1", "p2", "p2", "p2"]
    # the lifetime sums still hold what fell out; a bounded read does not
    assert log.totals()["backend_s"] == pytest.approx(12.0)
    assert log.totals()["programs"] == 3
    assert log.totals(since_epoch_s=0.0)["programs"] == 2
    with pytest.raises(ValueError):
        CompileLog(capacity=0)


def test_the_cache_s_words_go_to_the_next_backend_record_of_the_thread():
    log = CompileLog(ring=EventRing())
    t = feed(log, "warm", 2000.0, trace=0.5, lower=0.25, backend=0.125,
             cache="hits", compile_time_saved_sec=-0.02,
             cache_retrieval_time_sec=0.1)
    feed(log, "cold", t, trace=0.5, lower=0.25, backend=8.0,
         cache="misses")
    feed(log, "plain", t + 10, trace=0.5, lower=0.25, backend=1.0)
    backends = {r["program"]: r for r in log.records()
                if r["name"] == BACKEND}
    assert backends["warm"]["cache"] == "hit"
    assert backends["warm"]["saved_s"] == -0.02
    assert backends["warm"]["retrieval_s"] == 0.1
    assert backends["cold"]["cache"] == "miss"
    assert "saved_s" not in backends["cold"]
    assert backends["plain"]["cache"] == "off"
    assert all(r["name"] != BACKEND or "cache" in r for r in log.records())
    sums = log.totals()
    assert (sums["programs"], sums["hits"], sums["misses"]) == (3, 1, 1)


def test_windows_by_program_and_self_time_on_a_hand_built_log():
    log = CompileLog(ring=EventRing())
    t = feed(log, "step", 3000.0, trace=4.0, lower=2.0, backend=1.0,
             inner=[("matmul", 0.5, 1.0), ("where", 2.0, 0.5)],
             cache="hits")
    t = feed(log, "add", t, trace=0.25, lower=0.5, backend=0.25,
             cache="hits")
    end_of_set_up = t
    feed(log, "reference_block", t + 60.0, trace=1.0, lower=1.0,
         backend=20.0, cache="misses")
    assert log.totals()["trace_s"] == pytest.approx(5.25)   # not 6.75
    before = log.totals(until_epoch_s=end_of_set_up)
    assert before == {"trace_s": 4.25, "lower_s": 2.5, "backend_s": 1.25,
                      "programs": 2, "hits": 2, "misses": 0}
    after = log.totals(since_epoch_s=end_of_set_up)
    assert after["programs"] == 1 and after["misses"] == 1
    # a record that ends after the bound is left out, whole
    assert log.totals(until_epoch_s=3006.5)["programs"] == 0
    assert log.totals(until_epoch_s=3006.5)["lower_s"] == 2.0
    rows = log.by_program(until_epoch_s=end_of_set_up)
    assert [r["program"] for r in rows] == ["step", "add"]
    assert rows[0]["trace_s"] == pytest.approx(4.0)   # nested counted here
    assert compile_log.total_s(rows[0]) == pytest.approx(7.0)
    assert [r["program"] for r in log.by_program(top=1)] == [
        "reference_block"]
    step_trace = [r for r in log.records() if r["program"] == "step"][0]
    assert step_trace["self_s"] == pytest.approx(2.5)
    matmul = [r for r in log.records() if r["program"] == "matmul"][0]
    assert matmul["parent"] == step_trace["id"] == matmul["cause"]
    for line in log.to_jsonl().splitlines():
        assert json.loads(line)["name"] in (TRACE, LOWER, BACKEND)


def test_a_program_s_records_reach_the_ring_and_nested_traces_do_not():
    ring = EventRing()
    log = CompileLog(ring=ring)
    feed(log, "step", 4000.0, trace=4.0, lower=2.0, backend=1.0,
         inner=[("matmul", 0.5, 1.0)], cache="hits",
         compile_time_saved_sec=30.0)
    evs = ring.recent()
    assert [e["name"] for e in evs] == [TRACE, LOWER, BACKEND]
    assert all(e["program"] == "step" for e in evs)
    assert len({e["cause"] for e in evs}) == 1
    assert evs[2]["cache"] == "hit" and evs[2]["saved_s"] == 30.0
    # a trace that no lowering follows stays in the log alone
    log.on_scalar(TRACE_EV, 4010.0, fun_name="add")
    log.on_span(TRACE_EV, 4010.0, 4010.5, fun_name="add")
    assert len(ring.recent()) == 3 and len(log.records()) == 5
    # each is a span that ended at its stamp, on the epoch
    assert evs[0]["epoch_ns"] == pytest.approx(4004.0e9)
    assert evs[0]["dur_s"] == 4.0
    chrome = {e["name"]: e for e in ring.chrome_events(False)}
    assert chrome[TRACE]["ts"] == pytest.approx(4000.0e6)
    assert chrome[TRACE]["dur"] == pytest.approx(4.0e6)
    assert chrome[BACKEND]["args"]["program"] == "step"


def test_a_lowering_finds_its_cause_past_programs_traced_in_between():
    """A lowering rule may call a jitted function: JAX then reports
    traces of OTHER programs between a program's trace and its
    lowering."""
    log = CompileLog(ring=EventRing())
    log.on_scalar(TRACE_EV, 10.0, fun_name="step")
    log.on_span(TRACE_EV, 10.0, 11.0, fun_name="step")
    log.on_scalar(TRACE_EV, 11.0, fun_name="less")
    log.on_span(TRACE_EV, 11.0, 11.0, fun_name="less")
    log.on_span(LOWER_EV, 11.0, 12.0, fun_name="jit(step)")
    log.on_span(BACKEND_EV, 12.0, 13.0, fun_name="jit(step)")
    step = [r for r in log.records() if r["program"] == "step"]
    assert len({r["cause"] for r in step}) == 1
    # an event of another kind, a name that is no program's: no fault
    log.on_span("/jax/checkpoint/write/durations", 1.0, 2.0)
    log.on_span(BACKEND_EV, 20.0, 21.0, fun_name=None)
    assert log.faults == 0 and log.totals()["programs"] == 2


def test_a_trace_inside_a_lowering_is_the_lowering_s_time():
    log = CompileLog(ring=EventRing())
    log.on_scalar(TRACE_EV, 30.0, fun_name="draw")
    log.on_span(TRACE_EV, 30.0, 31.0, fun_name="draw")
    log.on_scalar(LOWER_EV, 31.0, fun_name="jit(draw)")
    for k in range(3):          # the rule's jitted helpers, one nested
        log.on_scalar(TRACE_EV, 31.1 + k, fun_name="bitwise_xor")
        log.on_scalar(TRACE_EV, 31.2 + k, fun_name="add")
        log.on_span(TRACE_EV, 31.2 + k, 31.3 + k, fun_name="add")
        log.on_span(TRACE_EV, 31.1 + k, 31.5 + k, fun_name="bitwise_xor")
    log.on_span(LOWER_EV, 31.0, 35.0, fun_name="jit(draw)")
    log.on_span(BACKEND_EV, 35.0, 36.0, fun_name="jit(draw)")
    assert [r["name"] for r in log.records()] == [TRACE, LOWER, BACKEND]
    assert log.totals()["trace_s"] == 1.0 and log.totals()["lower_s"] == 4.0
    # the lowering is over: the next trace is a program's again
    feed(log, "after", 40.0, trace=0.5, lower=0.5, backend=0.5)
    assert len(log.records()) == 6
    # jax's own: drawing normals lowers through threefry's rule
    mark = last_id()
    jax.jit(lambda k: jax.random.normal(k, (4,)))(jax.random.PRNGKey(0))
    new = [r for r in compile_log.records() if r["id"] > mark]
    lowers = [r for r in new if r["name"] == LOWER]
    assert lowers
    for r in new:
        if r["name"] == TRACE:
            assert not any(lo["start"] <= r["start"] and r["end"] <= lo["end"]
                           for lo in lowers if lo["tid"] == r["tid"])


@pytest.mark.parametrize("raw,program", [
    ("jit(step)", "step"), ("step", "step"), ("pmap(step)", "step"),
    ("jit(<lambda>)", "<lambda>"), ("jit(_where)", "_where"),
    ("", ""), ("odd (name)", "odd (name)")])
def test_a_program_s_name_is_normalised(raw, program):
    assert compile_log.program_of(raw) == program


def test_eight_threads_compiling_at_once_lose_no_record():
    def make(i):
        def body(x):
            return x * float(i) + 1.0
        body.__name__ = f"threaded_{i}"
        return jax.jit(body)
    fns = [make(i) for i in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        barrier = threading.Barrier(8)

        def run(f):
            barrier.wait(timeout=60)
            f(jnp.ones((4,)))
        threads = [threading.Thread(target=run, args=(f,)) for f in fns]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    for i in range(8):
        recs = of(f"threaded_{i}")
        assert [r["name"] for r in recs] == [TRACE, LOWER, BACKEND]
        assert len({r["tid"] for r in recs}) == 1
        assert len({r["cause"] for r in recs}) == 1
    assert compile_log.default_log().faults == 0


def test_the_six_instruments_read_the_log_s_totals():
    jax.jit(lambda x: x - 7.0)(jnp.ones((5,)))
    sums = compile_log.totals()
    snap = default_registry().snapshot()
    assert len(compile_log.INSTRUMENTS) == 6
    for name, (key, _) in compile_log.INSTRUMENTS.items():
        assert snap[name] == {"type": "counter", "value": float(sums[key])}
    # any registry an engine's bundle is built on carries them too
    from paddle_tpu.observability import EngineMetrics
    reg = MetricsRegistry()
    EngineMetrics(reg)
    text = reg.render_prometheus()
    for name in compile_log.INSTRUMENTS:
        assert f"# TYPE {name} counter" in text
    assert reg.get("paddle_tpu_compile_programs_total").value \
        == sums["programs"] > 0


def test_the_package_s_import_is_one_ring_event_on_both_clocks():
    """In an interpreter of its own: this one's ring may have turned over
    since the import.  Nothing the package imports compiles."""
    import subprocess
    code = ("import json, time, paddle_tpu\n"
            "from paddle_tpu.observability import compile_log, "
            "default_ring\n"
            "print(json.dumps({'events': default_ring().recent(), "
            "'programs': compile_log.totals()['programs'], "
            "'offset': time.time() - time.monotonic()}))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True).stdout
    said = json.loads(out.strip().splitlines()[-1])
    ev, = said["events"]
    assert ev["name"] == "paddle_tpu.import" and said["programs"] == 0
    assert 0 < ev["dur_s"] < 120 and ev["jax_preloaded"] is False
    assert abs(ev["epoch_ns"] * 1e-9 - ev["ts"] - said["offset"]) < 5e-3
