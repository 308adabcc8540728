"""What every cell's run shares: finding the cell, its configuration,
traffic and per-layer readers by name; the chip check; the compile
cache and compile counter; the profiler slice; the result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

from . import models

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
T_PROCESS_START = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


class Cell:
    """One entry of ``workloads`` with everything it names: the
    configuration file, the traffic file, the family plug-in."""

    def __init__(self, workload: str):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        found = [w for w in self.bench["workloads"]
                 if w["name"] == workload]
        if not found:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        entry = [c for c in self.bench["configs"]
                 if c["name"] == found[0]["config"]][0]
        self._fill(workload, found[0]["chips"],
                   load_json(os.path.join(ROOT, entry["file"])),
                   load_traffic(found[0]["traffic"]))

    def _fill(self, name, chips, conf, traffic):
        self.name, self.chips = name, chips
        self.conf, self.traffic = conf, traffic
        self.family = models.family(conf)

    @property
    def block_reference(self):
        """The plain reference of the family's block, imported on first
        use (it imports jax; a process that only launches runs never
        asks)."""
        return models.block_reference(self.conf)

    @classmethod
    def detached(cls, name: str, chips: int, conf: dict, traffic: dict):
        """A cell that BENCHMARK.json does not list (a rehearsal, a
        sweep before the cell is entered): it reports no metric by
        name, and runs EVERY reader under ``layer_metrics/``."""
        self = cls.__new__(cls)
        self.bench = None
        self._fill(name, chips, conf, traffic)
        return self

    def _mine(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        if self.bench is None:
            return []
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list:
        if self.bench is None:
            return [{"name": f[:-3], "unit": "-"} for f in
                    sorted(os.listdir(os.path.join(HERE, "layer_metrics")))
                    if f.endswith(".py")]
        return [m for m in self.bench["per_layer"] if self._mine(m)]


def find_cell(spec: str) -> Cell:
    """A workload of BENCHMARK.json by name, or — for a cell that is
    not entered yet — ``<config>:<traffic>:<chips>`` from the files
    under ``configs/`` and ``traffic/``."""
    if ":" not in spec:
        return Cell(spec)
    config, traffic, chips = spec.split(":")
    return Cell.detached(
        f"{config}.{traffic}", int(chips),
        load_json(os.path.join(HERE, "configs", config + ".json")),
        load_traffic(traffic))


def read_layer_metrics(cell: Cell, trace, counters: dict,
                       spans: dict) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``benchmark/layer_metrics/<name>.py``: ``read(trace, counters,
    spans, cell)`` returns a number, or None where it finds nothing to
    read (the metric is then left out of the line)."""
    out = {}
    for m in cell.per_layer():
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(trace, counters, spans, cell)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def require_chips(chips: int):
    """The devices the cell runs on; exits non-zero, with no result
    line, where JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: needs a TPU, jax found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(1)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), jax found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(1)
    return devices[:chips]


def enable_compile_cache() -> str:
    """The program's own rule (``JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), with every program kept, however short
    its compile: the admission lane compiles many sub-second ones."""
    import jax
    from paddle_tpu.framework.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileClock:
    """Counts JAX's own compile events (the idea of ``chip_smoke.py``):
    backend compiles (a program built or loaded from the persistent
    cache), their seconds, and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = self.hits = self.misses = 0
        self.backend_s = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.backend_s += secs

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self) -> dict:
        return {"compiles": self.compiles, "backend_s": self.backend_s,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, snap: dict) -> dict:
        now = self.snap()
        return {k: now[k] - snap[k] for k in now}


def run_dir(cell: Cell) -> str:
    d = os.path.join(OUT, cell.name)
    os.makedirs(d, exist_ok=True)
    return d


class TraceSlice:
    """The profiler over a slice of the window, in the process that
    holds the chip."""

    def __init__(self, where: str):
        self.where = where
        self.on = self.recorded = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.where, ignore_errors=True)
        jax.profiler.start_trace(self.where)
        self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False
            self.recorded = True

    def result(self):
        """The reduced trace; read after the window, the file is large."""
        from . import xplane
        if not self.recorded:
            return None
        return xplane.reduce(xplane.find_xplane(self.where))


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats()
        if st is None:
            raise RuntimeError(f"{d} reports no memory statistics")
        peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks)


class Checks:
    """The numbers ``correct`` is decided by, each printed beside its
    limit in every run."""

    def __init__(self):
        self.ok = True

    def add(self, name: str, value: float, limit: float,
            at_least: bool = False) -> None:
        good = value >= limit if at_least else value <= limit
        good = good and value == value          # NaN fails
        self.ok = self.ok and bool(good)
        rel = ">=" if at_least else "<="
        log(f"check {name}: {value:.6g} (limit {rel} {limit:.6g}) "
            f"{'ok' if good else 'FAILED'}")


def result_line(cell: Cell, devices, traced: bool, correct: bool,
                attempted: int, failed: int, metrics: dict,
                memory_peak: int, trace=None) -> None:
    """The run's last line of standard output.  ``memory_peak`` is the
    PROGRAM's: the cell reads it (:func:`memory_peak_bytes`) when the
    window has closed, before the reference touches the chip — a
    process's peak never falls again."""
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        doc["breakdown"] = trace.breakdown()
    want = cell.per_layer() if traced else cell.end_to_end()
    missing = [m["name"] for m in want if m["name"] not in metrics]
    if missing:
        log(f"metrics not read in this run: {missing}")
    sys.stdout.flush()
    print(json.dumps(doc), flush=True)
