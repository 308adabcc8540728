"""The one reducer from a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  A
device plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation that ran on that chip and its ``XLA Modules``
line one event per executed program (named ``jit_<function>(<id>)``).
Times are nanoseconds on one clock per trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from .stats import interval_union, median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_base(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def short_name(op: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's own name and opcode: ``%fusion.12 = ... fusion(...)``
    -> ``%fusion.12 fusion``."""
    head, _, rest = op.partition(" = ")
    m = re.search(r"\s([a-z][a-z\-]*)\(", " " + rest)
    return (head + " " + m.group(1)) if m else head[:80]


class DeviceTrace:
    """One chip's events: ``ops`` and ``modules`` are lists of
    ``(name, start_s, end_s)``, sorted by start."""

    def __init__(self, index, ops, modules):
        self.index, self.ops, self.modules = index, ops, modules

    def busy_s(self, lo=None, hi=None) -> float:
        iv = [(max(s, lo) if lo is not None else s,
               min(e, hi) if hi is not None else e)
              for _, s, e in self.ops]
        return interval_union([(s, e) for s, e in iv if e > s])

    def module_durations(self, base: str) -> list:
        return [e - s for n, s, e in self.modules
                if module_base(n) == base]

    def op_totals(self) -> dict:
        """SELF seconds by operation: an op that holds others (a
        ``while`` and its body) is charged only the time in which none
        of those ran.  Events of one chip nest, they do not cross."""
        out = defaultdict(float)
        stack = []                              # [name, end, self_s]
        for n, s, e in sorted(self.ops, key=lambda t: (t[1], -t[2])):
            while stack and stack[-1][1] <= s:
                top = stack.pop()
                out[top[0]] += top[2]
            if stack:
                stack[-1][2] -= e - s
            stack.append([short_name(n), e, e - s])
        for top in stack:
            out[top[0]] += top[2]
        return dict(out)

    def idle_gaps(self, lo, hi) -> list:
        """``(start, end)`` of every stretch inside [lo, hi] in which
        no operation ran on this chip."""
        gaps, cur = [], lo
        for _, s, e in self.ops:
            if e <= lo:
                continue
            if s >= hi:
                break
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        return gaps

    def exposed_s(self, pattern=COLLECTIVE) -> float:
        """Seconds of the matching ops during which no OTHER op ran on
        this chip (a collective the compute did not hide)."""
        mine = [(s, e) for n, s, e in self.ops if pattern.match(n)]
        rest = [(s, e) for n, s, e in self.ops if not pattern.match(n)]
        both = interval_union(mine + rest)
        return both - interval_union(rest)


class Trace:
    def __init__(self, devices, host_spans):
        self.devices = devices          # list of DeviceTrace
        self.host_spans = host_spans    # (thread, name, start_s, end_s)
        starts = [d.ops[0][1] for d in devices if d.ops]
        ends = [max(e for _, _, e in d.ops) for d in devices if d.ops]
        self.lo = min(starts) if starts else 0.0
        self.hi = max(ends) if ends else 0.0

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran anything."""
        used = [d for d in self.devices if d.ops]
        return sum(d.busy_s() for d in used) / max(len(used), 1)

    def idle_pct(self):
        """Share of the traced slice in which no operation ran on the
        device: 1 - busy / slice, averaged over the chips."""
        if not self.window_s:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def module_durations(self, base: str) -> list:
        """Durations of one program's executions on chip 0."""
        return self.devices[0].module_durations(base) \
            if self.devices else []

    def module_median_ms(self, base: str):
        d = self.module_durations(base)
        return median(d) * 1e3 if d else None

    def breakdown(self, top: int = 10) -> dict:
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        dev = self.devices[0]
        ops = sorted(dev.op_totals().items(), key=lambda kv: -kv[1])
        gaps = sorted(dev.idle_gaps(self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        named = [[self._host_during(s, e), e - s] for s, e in gaps]
        return {"device_ops": [[n, t] for n, t in ops[:top]],
                "idle_gaps": named}

    def _host_during(self, s, e) -> str:
        """The host span that covers most of [s, e]; the program has no
        TraceAnnotation yet, so this is a Python or runtime frame."""
        best, best_cov = "unattributed", 0.0
        for _, name, hs, he in self.host_spans:
            cov = min(e, he) - max(s, hs)
            if cov > best_cov:
                best, best_cov = name, cov
        return best


def reduce(path: str, host_span_min_s: float = 2e-4) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops
                elif line.name == MODULES_LINE:
                    dst = modules
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dst.append((ev.name, s, s + ev.duration_ns * 1e-9))
            ops.sort(key=lambda t: t[1])
            modules.sort(key=lambda t: t[1])
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    d = ev.duration_ns * 1e-9
                    if d >= host_span_min_s:
                        s = ev.start_ns * 1e-9
                        host.append((line.name, ev.name, s, s + d))
    devices.sort(key=lambda d: d.index)
    return Trace(devices, host)


def describe(path: str, limit: int = 12) -> str:
    """A by-hand look at a trace: planes, lines, and the first names."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = defaultdict(float)
            for ev in evs:
                names[ev.name] += ev.duration_ns * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            out.append(f"  LINE {line.name}: {len(evs)} events; top "
                       + "; ".join(f"{n}={t:.4f}s" for n, t in top))
    return "\n".join(out)
