"""The trace reducer on a recorded trace: three steps of the
``internlm2-1.8b.pretrain-2k`` cell on one TPU v5e (my chip run, PR 23,
kept gzipped), and on hand-built events."""

import gzip
import os

import pytest

from benchmark import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "train_3steps.xplane.pb.gz")) as f:
        raw.write_bytes(f.read())
    return xplane.reduce(str(raw))


def test_recorded_trace_modules_and_busy(trace):
    assert len(trace.devices) == 1
    steps = trace.module_durations("jit_step")
    assert len(steps) == 3
    assert all(1.41 < s < 1.43 for s in steps)
    assert trace.module_median_ms("jit_step") == pytest.approx(1417.47,
                                                               abs=0.01)
    assert trace.module_durations("jit_run") == []
    # one program a step and nothing between: busy within 0.5 % of the
    # slice, and never over it
    assert 0.995 * trace.window_s < trace.busy_s() <= trace.window_s
    assert trace.window_s == pytest.approx(4.2638, abs=1e-3)


def test_recorded_trace_breakdown(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    total = sum(t for _, t in b["device_ops"])
    assert 0 < total <= trace.busy_s()
    assert all(len(n) < 100 for n, _ in b["device_ops"])
    # self time: the scan's while holds nearly everything and keeps
    # almost nothing for itself
    whiles = [t for n, t in trace.devices[0].op_totals().items()
              if n.endswith(" while")]
    assert whiles and sum(whiles) < 0.05 * trace.busy_s()


def test_busy_idle_and_exposed_collective_on_hand_built_events():
    ops = [("%fusion.1 = f32[] fusion(x)", 0.0, 1.0),
           ("%all-reduce.1 = f32[] all-reduce(x)", 0.5, 2.0),
           ("%fusion.2 = f32[] fusion(x)", 3.0, 4.0)]
    ops = [("all-reduce.1" if "all-reduce" in n else n, s, e)
           for n, s, e in ops]
    d = xplane.DeviceTrace(0, ops, [("jit_step(7)", 0.0, 2.0),
                                    ("jit_step(7)", 3.0, 4.0),
                                    ("jit_run(9)", 2.0, 2.5)])
    assert d.busy_s() == pytest.approx(3.0)
    assert d.busy_s(lo=0.5, hi=3.5) == pytest.approx(2.0)
    assert d.idle_gaps(0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert d.exposed_s() == pytest.approx(1.0)      # 1.0 .. 2.0
    assert d.module_durations("jit_step") == [2.0, 1.0]
    t = xplane.Trace([d], [("main", "schedule", 1.9, 3.1)])
    assert t.window_s == pytest.approx(4.0)
    assert t.breakdown()["idle_gaps"][0][0] == "schedule"


def test_short_name():
    assert xplane.short_name(
        "%fusion.12 = bf16[8,2048]{1,0} fusion(bf16[8] %p), kind=kLoop"
    ) == "%fusion.12 fusion"
    assert xplane.short_name("%while.21 = (u32[], f32[2]) while(%t)") \
        == "%while.21 while"


def test_train_readers_on_the_recorded_trace(trace):
    """The cell's per-layer readers on the recorded three steps: the
    MFU is the step's operations over its device time, under 100 %.
    PR 23's trace is from before the program named anything, so the
    readers of scopes and kernel names find nothing in it and are left
    out (``test_xplane_meta.py`` holds all ten to PR 24's trace)."""
    from benchmark import harness
    cell = harness.Cell("internlm2-1.8b.pretrain-2k")
    got = harness.read_layer_metrics(
        cell, trace,
        {"tokens_per_step": 8 * 2048, "chips": 1,
         "device_kind": "TPU v5 lite"},
        {"input_wait_s": 0.06, "window_s": 30.0})
    assert {"train_step_ms.train", "mfu_pct.train", "device_idle_pct.train",
            "input_wait_pct.train"} <= set(got) \
        <= {m["name"] for m in cell.per_layer()}
    assert got["train_step_ms.train"]["value"] == pytest.approx(1417.47,
                                                                abs=0.01)
    assert got["mfu_pct.train"]["value"] == pytest.approx(49.2, abs=0.05)
    assert got["device_idle_pct.train"]["value"] == pytest.approx(0.27,
                                                                  abs=0.01)
    assert got["input_wait_pct.train"]["value"] == pytest.approx(0.2)
