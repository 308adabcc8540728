"""Profiler over jax.profiler (reference: python/paddle/profiler/profiler.py:346).

Architecture
------------
The reference profiler drives a C++ tracer (CUPTI/host tracer) through a
state schedule (CLOSED/READY/RECORD/RECORD_AND_RETURN) and exports chrome
traces plus a statistical summary.  On TPU the device tracer *is* XLA's —
``jax.profiler.start_trace``/``stop_trace`` captures a full device+host
timeline viewable in TensorBoard/Perfetto (including every fused HLO, DMA
and collective).  This class therefore:

  * keeps the reference's scheduling/state machine and ``step()`` protocol,
  * delegates device tracing to ``jax.profiler`` per RECORD window,
  * collects host-side ``RecordEvent`` spans + per-step wall times itself,
    for the ``summary()`` table and standalone chrome-trace export.
"""

from __future__ import annotations

import json
import os
import socket
import timeit
from collections import defaultdict
from enum import Enum

import jax

from .utils import (RecordEvent, TracerEventType, _disable_collection,
                    _drain_spans, _enable_collection)


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


class ProfilerState(Enum):
    """Reference profiler.py:79 — schedule states."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    """Reference profiler.py:99.  GPU/XPU/CUSTOM_DEVICE map onto the single
    XLA device tracer here; kept for API compat."""
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(Enum):
    """Sort keys for the summary table (reference profiler_statistic.py)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Build a step->ProfilerState function (reference profiler.py:117).

    The cycle is ``skip_first`` CLOSED steps, then repeats of
    [closed CLOSED, ready READY, record RECORD] with the last record step of
    each cycle RECORD_AND_RETURN.  ``repeat=0`` repeats forever.
    """
    if closed < 0 or ready < 0 or record <= 0 or repeat < 0 or skip_first < 0:
        raise ValueError("closed/ready >= 0, record > 0, "
                         "repeat/skip_first >= 0 required")
    span = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step // span >= repeat:
            return ProfilerState.CLOSED
        pos = step % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step):
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready factory writing chrome-trace JSON (reference :215)."""
    os.makedirs(dir_name, exist_ok=True)

    def handle(prof):
        nonlocal worker_name
        if not worker_name:
            worker_name = f"host_{socket.gethostname()}_pid_{os.getpid()}"
        t = int(timeit.default_timer() * 1000)
        filename = f"{worker_name}_time_{t}.paddle_trace.json"
        prof.export(os.path.join(dir_name, filename), "json")

    return handle


def export_protobuf(dir_name, worker_name=None):
    """API-compat alias: the TPU trace artifact is the jax.profiler capture
    directory (TensorBoard protobuf format) plus our chrome JSON."""
    return export_chrome_tracing(dir_name, worker_name)


def _get_supported_targets():
    targets = [ProfilerTarget.CPU]
    try:
        if any(d.platform != "cpu" for d in jax.devices()):
            targets += [ProfilerTarget.TPU, ProfilerTarget.GPU]
    except Exception:
        pass
    return targets


class _StatRecord:
    __slots__ = ("total", "max", "min", "count")

    def __init__(self):
        self.total = 0.0
        self.max = 0.0
        self.min = float("inf")
        self.count = 0

    def add(self, dur):
        self.total += dur
        self.count += 1
        if dur > self.max:
            self.max = dur
        if dur < self.min:
            self.min = dur


class Profiler:
    """Performance profiler (reference profiler.py:346).

    Args:
        targets: iterable of ProfilerTarget (device tracing is enabled when
            any non-CPU target is requested and a non-CPU backend exists).
        scheduler: (start, end) tuple, a callable step->ProfilerState, or
            None (always RECORD).
        on_trace_ready: callable(prof) invoked at each RECORD_AND_RETURN
            boundary; default exports chrome tracing to ./profiler_log.
        trace_dir: directory for the jax.profiler device capture
            (TensorBoard-readable). Defaults to on_trace_ready's dir or
            ./profiler_log.

    Usage::

        p = paddle.profiler.Profiler(scheduler=(2, 5))
        p.start()
        for it, batch in enumerate(loader):
            train_step(batch)
            p.step()
        p.stop()
        p.summary()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False,
                 trace_dir=None):
        supported = _get_supported_targets()
        if targets:
            self.targets = set(targets) & set(supported) or {ProfilerTarget.CPU}
        else:
            self.targets = set(supported)
        self.timer_only = timer_only

        if scheduler is None:
            self.scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            start = max(start, 0)
            self.scheduler = make_scheduler(
                closed=max(start - 1, 0), ready=1 if start > 0 else 0,
                record=end - start, repeat=1)
        else:
            self.scheduler = scheduler

        self.on_trace_ready = on_trace_ready
        self.trace_dir = trace_dir or "./profiler_log"
        self._device_trace = any(t != ProfilerTarget.CPU for t in self.targets)

        self.step_num = 0
        self.previous_state = ProfilerState.CLOSED
        self.current_state = ProfilerState.CLOSED
        self._tracing = False           # jax.profiler capture live
        self._spans = []                # accumulated host spans
        self._step_marks = []           # (step_num, start, end)
        self._step_open = None
        self._record_step_event = None

    # -- state transitions ------------------------------------------------

    def _start_device_trace(self):
        if self._device_trace and not self._tracing and not self.timer_only:
            os.makedirs(self.trace_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(self.trace_dir)
                self._tracing = True
            except Exception:
                # a capture may already be live (e.g. nested profilers);
                # host-span collection still works
                self._tracing = False

    def _stop_device_trace(self):
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            finally:
                self._tracing = False

    def start(self):
        """Enter the schedule at step 0 (reference profiler.py:580)."""
        from .timer import benchmark
        benchmark().begin()
        self.current_state = self.scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN,
                                  ProfilerState.READY):
            _enable_collection()
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._start_device_trace()
        self._open_step()

    def stop(self):
        """Tear down; flush a live capture and fire on_trace_ready."""
        from .timer import benchmark
        benchmark().end()
        self._close_step()
        self._spans.extend(_drain_spans())
        _disable_collection()
        recorded = self.current_state in (ProfilerState.RECORD,
                                          ProfilerState.RECORD_AND_RETURN)
        self._stop_device_trace()
        if recorded:
            if self.on_trace_ready:
                self.on_trace_ready(self)
            elif not self.timer_only:
                export_chrome_tracing(self.trace_dir)(self)

    def step(self, num_samples=None):
        """Advance the schedule by one iteration (reference profiler.py:633)."""
        from .timer import benchmark
        benchmark().after_step(num_samples)
        self._close_step()
        self._spans.extend(_drain_spans())

        self.previous_state = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        self._transition()
        self._open_step()

    def step_info(self, unit='samples'):
        from .timer import benchmark
        return benchmark().step_info(unit)

    def _transition(self):
        prev, cur = self.previous_state, self.current_state
        was_rec = prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        is_rec = cur in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if is_rec or cur == ProfilerState.READY:
            _enable_collection()
        else:
            _disable_collection()
        if is_rec and not was_rec:
            self._start_device_trace()
        if was_rec and not is_rec or prev == ProfilerState.RECORD_AND_RETURN:
            self._stop_device_trace()
            if prev == ProfilerState.RECORD_AND_RETURN and self.on_trace_ready:
                self.on_trace_ready(self)

    def _open_step(self):
        self._step_open = timeit.default_timer()

    def _close_step(self):
        if self._step_open is not None:
            end = timeit.default_timer()
            self._step_marks.append((self.step_num, self._step_open, end))
            self._step_open = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        return False

    # -- export / summary -------------------------------------------------

    def export(self, path, format="json"):
        """Write collected host spans + step marks as a chrome trace."""
        events = []
        pid = os.getpid()
        for step, start, end in self._step_marks:
            events.append({
                "name": f"ProfileStep#{step}", "ph": "X", "cat": "ProfileStep",
                "ts": start * 1e6, "dur": (end - start) * 1e6,
                "pid": pid, "tid": 0,
            })
        for name, etype, start, end, tid, _epoch in self._spans:
            events.append({
                "name": name, "ph": "X", "cat": etype,
                "ts": start * 1e6, "dur": (end - start) * 1e6,
                "pid": pid, "tid": tid,
            })
        trace = {"traceEvents": events,
                 "displayTimeUnit": "ms",
                 "metadata": {"device_trace_dir": self.trace_dir
                              if self._device_trace else None}}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(trace, f)
        return path

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit='ms', views=None):
        """Print (and return) the statistical table (reference :840)."""
        scale = {'s': 1.0, 'ms': 1e3, 'us': 1e6, 'ns': 1e9}[time_unit]
        stats = defaultdict(_StatRecord)
        for name, etype, start, end, _tid, _epoch in self._spans:
            stats[(etype, name)].add(end - start)
        step_stat = _StatRecord()
        for _s, start, end in self._step_marks:
            step_stat.add(end - start)

        lines = []
        header = (f"{'Name':<44}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                  f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"
                  f"{'Min(' + time_unit + ')':>12}")
        sep = "-" * len(header)
        lines += [sep, header, sep]
        if step_stat.count:
            lines.append(
                f"{'ProfileStep':<44}{step_stat.count:>8}"
                f"{step_stat.total * scale:>14.3f}"
                f"{step_stat.total / step_stat.count * scale:>12.3f}"
                f"{step_stat.max * scale:>12.3f}{step_stat.min * scale:>12.3f}")
        key_idx = {SortedKeys.CPUTotal: lambda kv: kv[1].total,
                   SortedKeys.CPUAvg: lambda kv: kv[1].total / kv[1].count,
                   SortedKeys.CPUMax: lambda kv: kv[1].max,
                   SortedKeys.CPUMin: lambda kv: kv[1].min}
        sort_fn = key_idx.get(sorted_by, key_idx[SortedKeys.CPUTotal])
        for (etype, name), rec in sorted(stats.items(), key=sort_fn,
                                         reverse=True):
            label = f"{name} [{etype}]"
            if len(label) > 43:
                label = label[:40] + "..."
            lines.append(
                f"{label:<44}{rec.count:>8}{rec.total * scale:>14.3f}"
                f"{rec.total / rec.count * scale:>12.3f}"
                f"{rec.max * scale:>12.3f}{rec.min * scale:>12.3f}")
        lines.append(sep)
        if self._device_trace:
            lines.append(f"Device timeline: jax.profiler capture in "
                         f"{self.trace_dir!r} (open with TensorBoard or "
                         f"Perfetto).")
        table = "\n".join(lines)
        print(table)
        return table

    # mean step time over recorded steps
    def step_time_ms(self, skip_first=1):
        marks = self._step_marks[skip_first:]
        if not marks:
            return 0.0
        return sum((e - s) for _n, s, e in marks) / len(marks) * 1e3


def get_profiler(config_path=None):
    """Reference profiler.py:917 — config-file driven construction."""
    if config_path and os.path.exists(config_path):
        with open(config_path) as f:
            cfg = json.load(f)
        sched = cfg.get("scheduler")
        return Profiler(scheduler=tuple(sched) if sched else None,
                        timer_only=cfg.get("timer_only", False))
    return Profiler()
