"""What ``jax.profiler.ProfileData`` leaves out of a ``.xplane.pb``.

``xplane.py`` reads names and times.  The same file also holds, on each
device op's EVENT METADATA, the JAX op path (``tf_op``: ``jit(step)/
transpose(jvp(layer_scan))/while/body/closed_call/checkpoint/
rematted_computation/block/mlp/dot_general``), the source line, the
compiler's category (``convolution fusion``, ``custom-call`` ...) and
its own ``flops`` and ``bytes_accessed``; and on the ``/host:CPU``
plane every ``TraceAnnotation`` of the program with its keyword
arguments.  This module decodes the five messages of the schema (XSpace,
XPlane, XLine/XEvent, XEventMetadata, XStat/XStatMetadata) with a
standard-library wire reader and joins them to self time by the nesting
rule of ``xplane.DeviceTrace.op_totals``.

The names the program gives its work are a closed vocabulary
(``docs/OBSERVABILITY.md``, "Profiler spans and scopes"): ``SCOPES`` are
``jax.named_scope`` names inside the step programs, ``KERNELS`` the
``name=`` of the ``pallas_call`` sites, ``SPANS`` the ``RecordEvent``
spans of the host code.  They are part of the yardstick.  The three
tuples here are the BASE vocabulary, what every architecture's program
shares; a block family whose program names more adds them as ``SCOPES``
/ ``KERNELS`` of its module under ``benchmark/models/``, and a trace
read for a cell (:func:`of_cell`) charges by the union.  The vocabulary
belongs to the reading, not to the parsed file: one file read for two
cells is parsed once and charged twice.
"""

from __future__ import annotations

import copy
import functools
import os
import re
import struct
from collections import defaultdict
from typing import NamedTuple

from .xplane import DEVICE_PLANE, MODULES_LINE, OPS_LINE, DeviceTrace

SCOPES = ("embed", "layer_scan", "block", "attn_qkv", "rope", "attn",
          "attn_out", "mlp", "loss_head", "optimizer", "grad_accum",
          "kv_write", "paged_attn", "varlen_attn", "logits", "sample",
          "pool_carry")
SPANS = ("dataloader.next", "dataloader.wait", "dataloader.to_device",
         "engine.step", "engine.sweep", "engine.admit", "engine.dispatch",
         "engine.fetch", "engine.drain", "admit.first_token_tail",
         "admit.write_pages", "server.http", "server.deliver")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
           "flash_varlen_fwd", "flash_varlen_bwd_dq",
           "flash_varlen_bwd_dkv", "paged_attn", "paged_attn_q8", "rope",
           "rms_norm", "rms_norm_bwd", "swiglu", "swiglu_bwd",
           "int8_matmul", "fused_adamw", "rmsnorm_matmul")
HOST_PLANE = "/host:CPU"
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"
_WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9.\-]*")


# -- the wire format ------------------------------------------------------
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(number, value)`` of every field of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited
    one (string, bytes, sub-message, packed)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """One XStat as ``(name, value)``."""
    name = value = None
    for num, v in fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:                          # a reference to a name
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = val = None
    for num, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf):
    """``(name, lines, event_metadata, stat_names)`` with the lines and
    the metadata still encoded: a plane nobody asks for costs nothing
    more."""
    name, lines, meta, stat_names = "", [], {}, {}
    for num, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            key, val = _map_entry(v)
            meta[key] = val
        elif num == 5:
            key, val = _map_entry(v)
            for n2, v2 in fields(val):
                if n2 == 2:
                    stat_names[key] = _text(v2)
    return name, lines, meta, stat_names


def _event_metadata(buf, stat_names: dict):
    """``(name, {stat name: value})`` of one XEventMetadata."""
    name, stats = "", {}
    for num, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 5:
            k, val = _stat(v, stat_names)
            stats[k] = val
    return name, stats


def _line(buf):
    """``(name#id, timestamp_ns, [encoded events])``: Python's threads
    all carry the process's name, the id tells them apart."""
    name, ident, t0, events = "", 0, 0, []
    for num, v in fields(buf):
        if num == 1:
            ident = v
        elif num == 2:
            name = _text(v)
        elif num == 3:
            t0 = _signed(v)
        elif num == 4:
            events.append(v)
    return f"{name}#{ident}", t0, events


def _event(buf, t0_ns: int):
    """``(metadata_id, start_s, end_s, [encoded stats])``; times on the
    clock ``xplane.reduce`` uses (the line's timestamp plus the offset)."""
    mid = off = dur = 0
    stats = []
    for num, v in fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            off = _signed(v)
        elif num == 3:
            dur = _signed(v)
        elif num == 4:
            stats.append(v)
    s = (t0_ns + off * 1e-3) * 1e-9
    return mid, s, s + dur * 1e-12, stats


# -- what the readers get -------------------------------------------------
class Op(NamedTuple):
    """One operation that ran on a chip, with its self time."""
    name: str
    start_s: float
    end_s: float
    self_s: float
    tf_op: str
    source: str
    category: str
    flops: float
    bytes_accessed: float


class HostEvent(NamedTuple):
    thread: str
    name: str
    start_s: float
    end_s: float
    attrs: dict


def path_words(tf_op: str) -> list:
    """The identifiers of an op path in order, transformation wrappers
    opened: ``transpose(jvp(layer_scan))/while`` -> transpose, jvp,
    layer_scan, while."""
    return _WORD.findall(tf_op)


def scope_of(tf_op: str, scopes=SCOPES) -> str:
    """The innermost name of ``scopes`` on the path."""
    for w in reversed(path_words(tf_op)):
        if w in scopes:
            return w
    return UNSCOPED


def phase_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "recompute"
    if "transpose(jvp(" in tf_op:
        return "backward"
    if "jvp(" in tf_op:
        return "forward"
    return "other"


def kernel_of(tf_op: str, kernels=KERNELS) -> str:
    """A Pallas kernel's ``name=`` (one of ``kernels``): ``pallas_call``
    binds under a scope of that name, so it is the component before
    ``pallas_call``; a program that names no kernel has ``closed_call``
    or ``checkpoint`` there."""
    parts = tf_op.split("/")
    if len(parts) >= 2 and parts[-1] == "pallas_call" \
            and parts[-2] in kernels:
        return parts[-2]
    return ""


_KEYS = {"scope": lambda op, mt: scope_of(op.tf_op, mt.scopes),
         "phase": lambda op, mt: phase_of(op.tf_op),
         "category": lambda op, mt: op.category,
         "kernel": lambda op, mt: kernel_of(op.tf_op, mt.kernels)}


class MetaTrace:
    """``ops``: chip index -> list of ``Op`` sorted by start;
    ``modules``: chip index -> ``(name, start_s, end_s)``; ``host``:
    every event of the host plane; ``device``: the first chip as
    ``xplane.DeviceTrace`` (busy time, idle gaps, module durations);
    ``scopes``, ``kernels``: the names ops are charged to, the base
    vocabulary unless :meth:`named` gave another."""

    scopes, kernels = SCOPES, KERNELS

    def __init__(self, ops: dict, modules: dict, host: list):
        self.ops, self.modules, self.host = ops, modules, host
        chip = self.chip()
        self.device = DeviceTrace(
            chip, [(op.name, op.start_s, op.end_s)
                   for op in ops.get(chip, [])], modules.get(chip, []))

    def named(self, scopes, kernels) -> "MetaTrace":
        """The same events charged by another vocabulary.  A view: the
        parsed events are shared, this trace keeps its own names."""
        view = copy.copy(self)
        view.scopes, view.kernels = tuple(scopes), tuple(kernels)
        return view

    def chip(self) -> int:
        return min(self.ops) if self.ops else 0

    def device_self_s(self) -> float:
        return sum(op.self_s for op in self.ops.get(self.chip(), []))

    def self_time_by(self, key: str) -> dict:
        """Self seconds of chip 0's ops by ``scope`` (innermost name of
        this trace's vocabulary on the op path), ``phase`` (forward / backward /
        recompute / other), ``category`` (the compiler's) or ``kernel``
        (a Pallas kernel's name; ops that are no kernel are left out)."""
        fn = _KEYS[key]
        out = defaultdict(float)
        for op in self.ops.get(self.chip(), []):
            k = fn(op, self)
            if k or key != "kernel":
                out[k] += op.self_s
        return dict(out)

    def executions(self, base: str) -> int:
        """How many times the program ``jit_<name>`` ran on chip 0."""
        return len(self.device.module_durations(base))

    def scope_ms_per(self, scope: str, base: str):
        """Milliseconds of self time under ``scope`` for each execution
        of the program ``base``; None where either is missing."""
        busy = self.self_time_by("scope").get(scope)
        runs = self.executions(base)
        return busy * 1e3 / runs if busy and runs else None

    def spans(self, names=SPANS) -> list:
        return [h for h in self.host if h.name in names]

    def idle_by_span(self, lo: float, hi: float) -> dict:
        """Chip 0's idle seconds inside [lo, hi] by the innermost
        program span (a name of ``SPANS``) that covers them: of the
        spans open at an instant, the one opened last — on a thread
        that feeds the device (``engine.*``, ``admit.*``,
        ``dataloader.*``) if one is open, since an HTTP handler that
        waits on the engine's lock is open all through the engine's
        stall and did not cause it.  Idle time no span covers is
        ``unattributed``."""
        marks = []                      # (time, order, kind, payload)
        for i, h in enumerate(self.spans()):
            if h.end_s > lo and h.start_s < hi:
                marks.append((h.start_s, 1, "open", (i, h)))
                marks.append((h.end_s, 0, "close", (i, h)))
        for g in self.device.idle_gaps(lo, hi):
            marks.append((g[0], 2, "idle", None))
            marks.append((g[1], 2, "busy", None))
        marks.sort(key=lambda m: (m[0], m[1]))
        out = defaultdict(float)
        open_, idle, prev = {}, False, lo
        for t, _, kind, payload in marks:
            if idle and t > prev:
                inner = max(open_.values(), default=None,
                            key=lambda h: (not h.name.startswith("server."),
                                           h.start_s, -h.end_s))
                out[inner.name if inner else UNATTRIBUTED] += t - prev
            prev = t
            if kind == "open":
                open_[payload[0]] = payload[1]
            elif kind == "close":
                open_.pop(payload[0], None)
            else:
                idle = kind == "idle"
        return dict(out)


def _self_times(events: list) -> list:
    """Self seconds of ``(start, end)`` events that nest and do not
    cross, in the order given (sorted by start, longer first)."""
    out = [0.0] * len(events)
    stack = []                                  # [index, end]
    for i, (s, e) in enumerate(events):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e - s
        out[i] += e - s
        stack.append([i, e])
    return out


def _device_ops(line_events, t0, meta, stat_names) -> list:
    decoded = {}
    raw = []
    for ev in line_events:
        mid, s, e, _ = _event(ev, t0)
        if mid not in decoded:
            decoded[mid] = _event_metadata(meta.get(mid, b""), stat_names)
        raw.append((s, e, mid))
    raw.sort(key=lambda t: (t[0], -t[1]))
    selfs = _self_times([(s, e) for s, e, _ in raw])
    ops = []
    for (s, e, mid), self_s in zip(raw, selfs):
        name, st = decoded[mid]
        # the stat is "<op path>:<op type>", the type empty under JAX
        path, sep, _ = str(st.get("tf_op", "")).rpartition(":")
        ops.append(Op(name, s, e, self_s,
                      path if sep else str(st.get("tf_op", "")),
                      str(st.get("source", "")),
                      str(st.get("hlo_category", "")),
                      float(st.get("flops") or 0),
                      float(st.get("bytes_accessed") or 0)))
    return ops


def parse(data: bytes) -> MetaTrace:
    ops, modules, host = {}, {}, []
    for num, plane in fields(data):
        if num != 1:
            continue
        name, lines, meta, stat_names = _plane(plane)
        m = DEVICE_PLANE.match(name)
        if m:
            chip = int(m.group(1))
            for line in lines:
                lname, t0, events = _line(line)
                lname = lname.rpartition("#")[0]
                if lname == OPS_LINE:
                    ops[chip] = _device_ops(events, t0, meta, stat_names)
                elif lname == MODULES_LINE:
                    mods = []
                    for ev in events:
                        mid, s, e, _ = _event(ev, t0)
                        mods.append((_event_metadata(
                            meta.get(mid, b""), stat_names)[0], s, e))
                    modules[chip] = sorted(mods, key=lambda t: t[1])
        elif name.startswith(HOST_PLANE):
            names = {}
            for line in lines:
                lname, t0, events = _line(line)
                for ev in events:
                    mid, s, e, stats = _event(ev, t0)
                    if mid not in names:
                        names[mid] = _event_metadata(
                            meta.get(mid, b""), stat_names)[0]
                    attrs = dict(_stat(st, stat_names) for st in stats)
                    host.append(HostEvent(lname, names[mid], s, e, attrs))
    host.sort(key=lambda h: h.start_s)
    return MetaTrace(ops, modules, host)


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int, size: int) -> MetaTrace:
    with open(path, "rb") as f:
        return parse(f.read())


def load(path: str) -> MetaTrace:
    """The file parsed once, however many readers ask."""
    st = os.stat(path)
    return _load(path, st.st_mtime_ns, st.st_size)


def names_of(cell):
    """``(scopes, kernels)`` that a trace of ``cell`` is charged by:
    the base vocabulary and what the cell's family adds to it."""
    fam = cell.family
    return (SCOPES + tuple(getattr(fam, "SCOPES", ())),
            KERNELS + tuple(getattr(fam, "KERNELS", ())))


def of_cell(cell, trace):
    """The file behind ``trace``, which the run of ``cell`` has just
    recorded and ``xplane.reduce`` has read, charged by the names of
    the cell's family; None where it recorded none (an older run's
    file may lie there still)."""
    from . import harness, xplane
    if trace is None:
        return None
    try:
        mt = load(xplane.find_xplane(harness.run_dir(cell) + "/trace"))
    except FileNotFoundError:
        return None
    return mt.named(*names_of(cell))
