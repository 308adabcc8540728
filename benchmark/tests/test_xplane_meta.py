"""``xplane_meta`` on the recorded traces (three steps of the
``internlm2-1.8b.pretrain-2k`` cell on one TPU v5e: PR 23's, from
before the program named anything, and PR 24's, with scopes and kernel
names) and on hand-built bytes."""

import gzip
import os
import struct

import pytest

from benchmark import harness, xplane, xplane_meta

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "internlm2-1.8b.pretrain-2k"


def recorded(name: str) -> xplane_meta.MetaTrace:
    with gzip.open(os.path.join(DATA, name)) as f:
        return xplane_meta.parse(f.read())


@pytest.fixture(scope="module")
def old():
    return recorded("train_3steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def new():
    return recorded("train_3steps_scoped.xplane.pb.gz")


def shares(mt, key):
    by = mt.self_time_by(key)
    total = sum(by.values())
    return {k: 100.0 * v / total for k, v in by.items()}


# -- PR 23's trace: what the metadata says about anonymous ops ------------
def test_old_trace_matches_the_name_and_time_reducer(old, tmp_path):
    raw = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "train_3steps.xplane.pb.gz")) as f:
        raw.write_bytes(f.read())
    tr = xplane.reduce(str(raw))
    assert len(old.ops[0]) == len(tr.devices[0].ops) == 18054
    assert old.device_self_s() == pytest.approx(tr.busy_s(), rel=1e-6)
    assert old.executions("jit_step") == 3
    by_name = {}
    for op in old.ops[0]:
        k = xplane.short_name(op.name)
        by_name[k] = by_name.get(k, 0.0) + op.self_s
    want = tr.devices[0].op_totals()
    assert by_name.keys() == want.keys()
    # the same nesting rule on picoseconds: ``ProfileData`` rounds to
    # whole nanoseconds, where ops that abut can seem to nest (and
    # ``op_totals`` then reads a few small ops below zero), so single
    # ops agree closely, not exactly
    top = sorted(want, key=want.get)[-10:]
    assert all(by_name[k] == pytest.approx(want[k], rel=0.03) for k in top)
    assert min(by_name.values()) >= 0.0 > min(want.values())
    assert sum(by_name.values()) == pytest.approx(tr.busy_s(), rel=1e-6)
    assert sum(want.values()) == pytest.approx(tr.busy_s(), rel=2e-3)


def test_old_trace_split_by_category_and_phase(old):
    cat = shares(old, "category")
    assert cat["convolution fusion"] == pytest.approx(76.4, abs=0.05)
    assert cat["custom-call"] == pytest.approx(11.2, abs=0.05)
    assert cat["loop fusion"] == pytest.approx(7.1, abs=0.05)
    assert cat["data formatting"] == pytest.approx(3.0, abs=0.05)
    ph = shares(old, "phase")
    assert ph["backward"] == pytest.approx(55.5, abs=0.05)
    assert ph["forward"] == pytest.approx(22.3, abs=0.05)
    assert ph["recompute"] == pytest.approx(18.0, abs=0.05)
    assert ph["other"] == pytest.approx(4.3, abs=0.05)
    # nothing was named then
    assert set(old.self_time_by("scope")) == {xplane_meta.UNSCOPED}
    assert old.self_time_by("kernel") == {}


def test_the_dynamic_update_slice_fusions_are_weight_gradient_matmuls(old):
    """PR 23 read ``bitcast_dynamic-update-slice_fusion.20`` as a
    gradient write-back; its metadata says matrix product."""
    f20 = [op for op in old.ops[0]
           if op.name.startswith("%bitcast_dynamic-update-slice_fusion.20 ")]
    assert len(f20) == 54
    assert {op.tf_op.rsplit("/", 1)[-1] for op in f20} == {"dot_general"}
    assert {op.category for op in f20} == {"convolution fusion"}
    assert {op.flops for op in f20} == {552020738048.0}
    assert f20[0].source.endswith("paddle_tpu/models/llama_pretrain.py:477")
    mean_s = sum(op.end_s - op.start_s for op in f20) / 54
    assert 140e12 < 552020738048.0 / mean_s < 155e12     # of 197e12


# -- hand-built bytes -----------------------------------------------------
def varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def fld(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


STAT_IDS = {"tf_op": 1, "hlo_category": 2, "flops": 3, "n_requests": 4,
            "lane": 5}


def stat(name: str, value) -> bytes:
    num = {int: 3, float: 2, str: 5}[type(value)]
    return fld(1, STAT_IDS[name]) + fld(num, value)


def plane(name: str, lines: list, metas: dict) -> bytes:
    """``lines``: (name, timestamp_ns, [(meta id, offset_ps, dur_ps,
    {stat: value})]); ``metas``: id -> (name, {stat: value})."""
    out = fld(2, name)
    for lname, t0, events in lines:
        body = fld(2, lname) + fld(3, t0)
        for mid, off, dur, stats in events:
            ev = fld(1, mid) + fld(2, off) + fld(3, dur)
            for k, v in stats.items():
                ev += fld(4, stat(k, v))
            body += fld(4, ev)
        out += fld(3, body)
    for mid, (mname, stats) in metas.items():
        meta = fld(1, mid) + fld(2, mname)
        for k, v in stats.items():
            meta += fld(5, stat(k, v))
        out += fld(4, fld(1, mid) + fld(2, meta))
    for sname, sid in STAT_IDS.items():
        out += fld(5, fld(1, sid) + fld(2, fld(1, sid) + fld(2, sname)))
    return out


MS = 10 ** 9                                    # picoseconds


@pytest.fixture(scope="module")
def built():
    """One chip: a ``while`` of 10 ms holding a matmul (2-5 ms, under
    ``block/mlp`` in the backward pass) and a Pallas kernel (6-8 ms);
    then idle 10-20 ms; then an unscoped op 20-21 ms; idle to 30 ms
    closed by a last op.  Host: ``engine.step`` 9-19.5 ms holding
    ``engine.admit`` 11-15 ms holding ``admit.write_pages`` 12-14 ms;
    on another thread ``server.http`` 13-19 ms, a handler waiting for
    the engine; a frame of the Python tracer over everything."""
    dev = plane("/device:TPU:0", [
        ("XLA Ops", 1000, [
            (1, 0, 10 * MS, {}), (2, 2 * MS, 3 * MS, {}),
            (3, 6 * MS, 2 * MS, {}), (4, 20 * MS, 1 * MS, {}),
            (4, 30 * MS, 1 * MS, {})]),
        ("XLA Modules", 1000, [(5, 0, 10 * MS, {})])],
        {1: ("%while.1 = (f32[]) while(%t)",
             {"tf_op": "jit(step)/transpose(jvp(layer_scan))/while:",
              "hlo_category": "while"}),
         2: ("%fusion.7 = f32[8] fusion(%p)",
             {"tf_op": "jit(step)/transpose(jvp(layer_scan))/while/body/"
                       "closed_call/checkpoint/block/mlp/dot_general:",
              "hlo_category": "convolution fusion", "flops": 4096}),
         3: ("%closed_call.3 = f32[8] custom-call(%p)",
             {"tf_op": "jit(step)/jvp(layer_scan)/while/body/closed_call/"
                       "block/attn/flash_fwd/pallas_call:",
              "hlo_category": "custom-call"}),
         4: ("%copy.9 = f32[8] copy(%p)", {"hlo_category": "data formatting"}),
         5: ("jit_step(77)", {})})
    host = plane("/host:CPU", [
        ("engine/1", 1000, [
            (1, 9 * MS, int(10.5 * MS), {}),
            (2, 11 * MS, 4 * MS, {"n_requests": 3, "lane": "packed"}),
            (3, 12 * MS, 2 * MS, {})]),
        ("handler/2", 1000, [(5, 13 * MS, 6 * MS, {})]),
        ("python3", 1000, [(4, 0, 31 * MS, {})])],
        {1: ("engine.step", {}), 2: ("engine.admit", {}),
         3: ("admit.write_pages", {}),
         4: ("$threading.py:323 wait", {}), 5: ("server.http", {})})
    return xplane_meta.parse(fld(1, dev) + fld(1, host))


def test_built_self_time_nests(built):
    ops = {xplane.short_name(op.name): op for op in built.ops[0][:4]}
    assert ops["%while.1 while"].self_s == pytest.approx(5e-3)
    assert ops["%fusion.7 fusion"].self_s == pytest.approx(3e-3)
    assert ops["%fusion.7 fusion"].flops == 4096.0
    assert built.ops[0][0].start_s == pytest.approx(1e-6)
    assert built.device_self_s() == pytest.approx(12e-3)
    assert built.executions("jit_step") == 1


def test_built_split_by_every_key(built):
    assert built.self_time_by("scope") == pytest.approx(
        {"layer_scan": 5e-3, "mlp": 3e-3, "attn": 2e-3,
         xplane_meta.UNSCOPED: 2e-3})
    assert built.self_time_by("phase") == pytest.approx(
        {"backward": 8e-3, "forward": 2e-3, "other": 2e-3})
    assert built.self_time_by("kernel") == pytest.approx(
        {"flash_fwd": 2e-3})
    assert built.self_time_by("category")["convolution fusion"] == \
        pytest.approx(3e-3)
    assert built.scope_ms_per("mlp", "jit_step") == pytest.approx(3.0)
    assert built.scope_ms_per("optimizer", "jit_step") is None
    assert built.scope_ms_per("mlp", "jit_run") is None


def test_built_host_events_keep_their_attributes(built):
    assert len(built.host) == 5                 # nothing filtered
    admit = built.spans(("engine.admit",))[0]
    assert admit.attrs == {"n_requests": 3, "lane": "packed"}
    assert admit.thread == "engine/1#0"
    assert [h.name for h in built.spans()] == [
        "engine.step", "engine.admit", "admit.write_pages", "server.http"]


def test_built_idle_goes_to_the_innermost_span(built):
    lo = built.ops[0][0].start_s
    hi = lo + 31e-3
    gaps = built.device.idle_gaps(lo, hi)
    assert [b - a for a, b in gaps] == pytest.approx([10e-3, 9e-3])
    assert gaps[0][0] == pytest.approx(lo + 10e-3)
    idle = built.idle_by_span(lo, hi)
    # 10-11 and 15-19.5 under engine.step alone, 11-12 and 14-15 under
    # engine.admit, 12-14 under admit.write_pages, the rest under none:
    # the Python frame is no program span, and the handler that opened
    # last (13 ms) waits for the engine, it does not feed the device
    assert idle == pytest.approx(
        {"engine.step": 5.5e-3, "engine.admit": 2e-3,
         "admit.write_pages": 2e-3,
         xplane_meta.UNATTRIBUTED: 9.5e-3})
    assert built.device.busy_s(lo + 9e-3, lo + 19.5e-3) == \
        pytest.approx(1e-3)


def test_wire_reader_types():
    msg = fld(1, 300) + fld(2, -5) + fld(3, 1.5) + fld(4, "abc")
    got = dict(xplane_meta.fields(msg))
    assert got[1] == 300 and xplane_meta._signed(got[2]) == -5
    assert struct.unpack("<d", struct.pack("<Q", got[3]))[0] == 1.5
    assert bytes(got[4]) == b"abc"
    with pytest.raises(ValueError):
        list(xplane_meta.fields(varint(1 << 3 | 3)))      # a group


# -- PR 24's trace: the six new readers -----------------------------------
def test_new_trace_carries_the_names(new):
    # the innermost scope takes the time: ``block`` holds only scopes
    scopes = set(new.self_time_by("scope"))
    assert scopes == {"embed", "layer_scan", "attn_qkv", "rope", "attn",
                      "attn_out", "mlp", "loss_head", "optimizer",
                      xplane_meta.UNSCOPED}
    mlp = [op.tf_op for op in new.ops[0]
           if xplane_meta.scope_of(op.tf_op) == "mlp"]
    assert all("/block/mlp/" in p for p in mlp)
    assert {xplane_meta.phase_of(p) for p in mlp} == {
        "forward", "backward", "recompute"}
    assert set(new.self_time_by("kernel")) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rope"}
    assert {h.name for h in new.spans()} == {
        "dataloader.next", "dataloader.wait", "dataloader.to_device"}


def test_train_readers_on_the_new_trace(new, tmp_path, monkeypatch):
    """The cell's readers on the recorded three steps, against what the
    chip run that recorded them printed (my chip run, PR 24)."""
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    cell = harness.Cell(CELL)
    where = os.path.join(harness.run_dir(cell), "trace", "plugins",
                         "profile", "x")
    os.makedirs(where)
    raw = os.path.join(where, "t.xplane.pb")
    with gzip.open(os.path.join(
            DATA, "train_3steps_scoped.xplane.pb.gz")) as f, \
            open(raw, "wb") as g:
        g.write(f.read())
    got = harness.read_layer_metrics(
        cell, xplane.reduce(raw),
        {"tokens_per_step": 8 * 2048, "chips": 1,
         "device_kind": "TPU v5 lite"},
        {"input_wait_s": 0.06, "window_s": 30.0})
    assert set(got) == {m["name"] for m in cell.per_layer()}
    assert len(got) == 10
    want = PRINTED_BY_THE_CHIP_RUN
    for name, value in want.items():
        assert got[name]["value"] == pytest.approx(value, rel=1e-6), name
    assert got["unscoped_pct.train"]["value"] < 3.0
    assert got["flash_attn_roofline_pct.train"]["value"] < 100.0


# the result line of the run that recorded the trace (my chip run,
# PR 24, seed 2400000022; ``input_wait_pct.train`` is the benchmark's
# own span, not in the trace)
PRINTED_BY_THE_CHIP_RUN = {
    "train_step_ms.train": 1417.4847030000003,
    "mfu_pct.train": 49.19661106157011,
    "device_idle_pct.train": 0.25352037541148675,
    "recompute_pct.train": 17.999334820706594,
    "backward_pct.train": 55.4685738418304,
    "flash_attn_roofline_pct.train": 27.08471658043885,
    "loss_head_ms.train": 188.09440549533386,
    "optimizer_ms.train": 54.900179114667026,
    "unscoped_pct.train": 0.3835620869378455,
}
