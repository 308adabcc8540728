"""The five ``setup_*`` readers (``benchmark/layer_metrics/``) on a
hand-built compile log and ring beside a RECORDED trace — the three
traced steps of the dense cell, ``benchmark/tests/data``, a v5e run whose
file says when its session began —, against values worked out by hand.
Held here and not under ``benchmark/tests/`` (the driver runs ``tests/``)."""

import gzip
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import pytest

from _compile_feed import feed
from benchmark import harness, xplane, xplane_meta
from benchmark.layer_metrics import _setup_log
from paddle_tpu.observability import EventRing, compile_log, events
from paddle_tpu.observability.compile_log import CompileLog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "internlm2-1.8b.pretrain-2k"
NAMES = ("setup_step_compile_s.train", "setup_trace_lower_s.train",
         "setup_small_programs_s.train", "setup_cache_miss_pct.train",
         "setup_loader_start_s.train")
# the recorded file's own words: its session's start on the epoch
# (``Task Environment`` / ``profile_start_time``, ns) and its first
# event, a host line's, 356.45 us into the session
SESSION_NS = 1790535672104571736
SLICE = SESSION_NS * 1e-9 + 0.00035645
OK = {"compiles_in_window": 0}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture
def cell(tmp_path, monkeypatch):
    """The dense cell with the recorded trace where its run would have
    left it, and an empty log and ring in the process-wide ones' place."""
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    cell = harness.Cell(CELL)
    where = os.path.join(harness.run_dir(cell), "trace", "plugins",
                         "profile", "x")
    os.makedirs(where)
    with gzip.open(os.path.join(
            REPO, "benchmark", "tests", "data",
            "train_3steps_scoped.xplane.pb.gz")) as f, \
            open(os.path.join(where, "t.xplane.pb"), "wb") as g:
        g.write(f.read())
    ring = EventRing()
    monkeypatch.setattr(events, "_default_ring", ring)
    monkeypatch.setattr(compile_log, "_default", CompileLog(ring=ring))
    return cell


def a_set_up(end):
    """A warm set-up that ends ``end`` epoch seconds: the loader's start,
    two small programs, the step — trace 4 s with two nested traces,
    lowering 2 s, a retrieval of 1 s — and a norm; then, past the slice,
    the reference's first program, a miss of 20 s."""
    log = compile_log.default_log()
    t = end - 60.0
    events.default_ring().emit(
        "dataloader.start", at=(t + 6.5 - time.time() + time.monotonic(),
                                int((t + 6.5) * 1e9)),
        dur_s=6.5, num_workers=2, transport="shm")
    t = feed(log, "_normal", t + 7.0, trace=0.25, lower=0.5, backend=0.25,
             cache="hits")
    t = feed(log, "zeros", t, trace=0.125, lower=0.25, backend=0.125,
             cache="hits")
    t = feed(log, "step", t + 1.0, trace=4.0, lower=2.0, backend=1.0,
             inner=[("matmul", 0.5, 1.0), ("_where", 2.0, 0.5)],
             cache="hits", compile_time_saved_sec=16.0,
             cache_retrieval_time_sec=0.9)
    t = feed(log, "sum", t + 3.0, trace=0.125, lower=0.125, backend=0.25,
             cache="hits")
    assert t < end
    feed(log, "reference_block", end + 35.0, trace=1.0, lower=1.0,
         backend=20.0, cache="misses")


def read_all(cell, counters=OK):
    return {n: reader(n)(object(), counters, {}, cell) for n in NAMES}


def test_the_slice_s_start_is_the_session_s_start_plus_its_first_event(cell):
    assert _setup_log.slice_start_epoch_s(cell, object()) \
        == pytest.approx(SLICE, abs=1e-6)
    assert _setup_log.slice_start_epoch_s(cell, None) is None


def test_the_five_readers_on_a_hand_built_warm_set_up(cell):
    a_set_up(SLICE - 1.0)
    got = read_all(cell)
    assert got == {
        "setup_step_compile_s.train": pytest.approx(7.0),
        "setup_trace_lower_s.train": pytest.approx(4.5 + 2.875),
        "setup_small_programs_s.train": pytest.approx(1.0 + 0.5 + 0.5),
        "setup_cache_miss_pct.train": 0.0,
        "setup_loader_start_s.train": 6.5}
    # what the issue asks of their sum
    assert got["setup_trace_lower_s.train"] <= \
        got["setup_step_compile_s.train"] + \
        got["setup_small_programs_s.train"]


def test_a_first_set_up_reads_its_misses(cell):
    log = compile_log.default_log()
    t = feed(log, "zeros", SLICE - 50.0, trace=0.5, lower=0.5, backend=3.0,
             cache="misses")
    t = feed(log, "step", t, trace=4.0, lower=2.0, backend=18.0,
             cache="misses")
    feed(log, "ones", t, trace=0.5, lower=0.5, backend=0.0)    # jit's cache
    feed(log, "sum", t + 1.0, trace=0.5, lower=0.5, backend=1.0,
         cache="hits")
    got = read_all(cell)
    assert got["setup_cache_miss_pct.train"] == pytest.approx(200.0 / 3)
    assert got["setup_step_compile_s.train"] == pytest.approx(24.0)
    assert got["setup_small_programs_s.train"] == pytest.approx(7.0)
    assert got["setup_loader_start_s.train"] is None   # no loader ran


def test_records_that_end_after_the_slice_began_are_left_out(cell):
    a_set_up(SLICE - 1.0)
    whole = read_all(cell)
    log = compile_log.default_log()
    # traced and lowered before the slice, the compile ends half a second
    # INTO it: the first two records count, the third does not
    feed(log, "late", SLICE - 2.0, trace=0.5, lower=1.0, backend=1.0,
         cache="misses")
    got = read_all(cell)
    assert got["setup_trace_lower_s.train"] == pytest.approx(
        whole["setup_trace_lower_s.train"] + 1.5)
    assert got["setup_small_programs_s.train"] == pytest.approx(
        whole["setup_small_programs_s.train"] + 1.5)
    assert got["setup_cache_miss_pct.train"] == 0.0
    assert got["setup_step_compile_s.train"] == \
        whole["setup_step_compile_s.train"]
    # a loader that started after the slice (the reference has none; a
    # second epoch's would) is not the set-up's
    events.default_ring().emit(
        "dataloader.start", at=(0.0, int((SLICE + 9.0) * 1e9)), dur_s=3.0)
    assert read_all(cell)["setup_loader_start_s.train"] == 6.5


@pytest.mark.parametrize("counters", [{"compiles_in_window": 2}, {}])
def test_a_compile_between_the_window_s_start_and_the_slice_reads_nothing(
        cell, counters):
    a_set_up(SLICE - 1.0)
    got = read_all(cell, counters)
    assert [got[n] for n in NAMES[:4]] == [None] * 4
    assert got["setup_loader_start_s.train"] == 6.5   # needs no split


def test_a_log_that_has_dropped_records_reads_nothing(cell, monkeypatch):
    small = CompileLog(capacity=8, ring=EventRing())
    monkeypatch.setattr(compile_log, "_default", small)
    a_set_up(SLICE - 1.0)                   # 17 records into room for 8
    assert small.totals()["dropped"] == 9
    assert [read_all(cell)[n] for n in NAMES[:4]] == [None] * 4


def test_where_no_program_is_named_step_the_largest_is_taken(cell):
    log = compile_log.default_log()
    t = feed(log, "train_fn", SLICE - 30.0, trace=3.0, lower=1.0,
             backend=2.0, cache="hits")
    feed(log, "zeros", t, trace=0.25, lower=0.25, backend=0.5,
         cache="hits")
    got = read_all(cell)
    assert got["setup_step_compile_s.train"] == pytest.approx(6.0)
    assert got["setup_small_programs_s.train"] == pytest.approx(1.0)


def test_nothing_to_read_is_none_and_never_an_error(cell, monkeypatch):
    assert read_all(cell) == dict.fromkeys(NAMES)       # an empty log
    # no trace was recorded (an untraced run hands the readers None)
    a_set_up(SLICE - 1.0)
    assert {n: reader(n)(None, OK, {}, cell) for n in NAMES} \
        == dict.fromkeys(NAMES)
    # a file with no ``Task Environment`` plane: its clock cannot be placed
    monkeypatch.setattr(_setup_log, "TASK_PLANE", "no such plane")
    _setup_log._profile_start_s.cache_clear()
    assert read_all(cell) == dict.fromkeys(NAMES)
    _setup_log._profile_start_s.cache_clear()
    # the file the harness loads beside the readers is passed over
    assert reader("_setup_log")(object(), OK, {}, cell) is None


def test_a_live_trace_places_a_ring_span_where_the_host_plane_has_it(
        tmp_path, monkeypatch):
    """The clocks agree: a span's start on the host plane plus the
    file's ``profile_start_time`` is its epoch start in the ring, and the
    slice's start lies between the clock reads around ``start_trace``."""
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    cell = harness.Cell(CELL)
    ring = EventRing()
    x = jnp.ones((8, 8))
    before = time.time()
    jax.profiler.start_trace(harness.run_dir(cell) + "/trace")
    try:
        after = time.time()
        with ring.span("engine.step", i=1):
            (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    start = _setup_log.slice_start_epoch_s(cell, object())
    assert before - 1e-3 <= start <= after + 5e-3
    path = xplane.find_xplane(harness.run_dir(cell) + "/trace")
    st = os.stat(path)
    session = _setup_log._profile_start_s(path, st.st_mtime_ns, st.st_size)
    span, = xplane_meta.load(path).spans(("engine.step",))
    ev, = ring.recent()
    assert abs(session + span.start_s
               - (ev["epoch_ns"] * 1e-9 - ev["dur_s"])) < 1e-3
