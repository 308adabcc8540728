#!/usr/bin/env python3
"""Where one cell's set-up goes, from the program's own log
(``paddle_tpu.observability.compile_log``) and its ring:

    python3 tools/setup_breakdown.py <tag> <workload>:<seed>:<seconds>:<trace>[:cold] ...

Each run is ``benchmark/run.py``'s own, in a process of its own (this
parent never touches JAX); ``:cold`` empties the persistent compile
cache's directory first.  After the run's result line the child prints
one line ``SETUP {json}`` and writes the whole log, gzipped JSON lines,
to ``chiprun_out/<tag>/``:

* ``setup_s`` as the run logged it, and the STAGES it falls into, by the
  log's own marks — process start .. ``paddle_tpu.import`` ..
  ``dataloader.start`` .. the train step's trace .. its backend record's
  end .. the window — each with its wall seconds and the compile seconds
  (trace + lower + backend, self time) inside it: the rest of a stage is
  what no record covers (a program's EXECUTION, host code, the chip's
  start);
* ``programs`` / ``hits`` / ``misses`` before the window, and after it
  (the reference's);
* the ten largest (program, phase) by self seconds before the window;
* what the five ``setup_*`` readers took (a traced run), the log's size.

The window's start is ``setup_s`` after ``harness.T_PROCESS_START``, on
the monotonic clock every record carries.
"""

from __future__ import annotations

import gzip
import io
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK = "SETUP "


def stages(recs, ring, t_start, t_window) -> list:
    """``[name, wall_s, compile_s, programs]`` of each stretch between
    the marks the log holds, on the monotonic clock."""
    def span(name):
        ev = next((e for e in ring if e["name"] == name
                   and e["ts"] <= t_window), None)
        return (ev["ts"] - ev["dur_s"], ev["ts"]) if ev else None
    step = [r for r in recs if r["program"] == "step"
            and r["parent"] is None and r["end"] <= t_window]
    marks = [("process start", t_start)]
    for name in ("paddle_tpu.import", "dataloader.start"):
        got = span(name)
        if got:
            marks += [(name, got[0]), ("after " + name, got[1])]
    if step:
        marks += [("the step's compile", min(r["start"] for r in step)),
                  ("after the step's compile",
                   max(r["end"] for r in step))]
    marks.append(("window", t_window))
    out = []
    for (name, lo), (_, hi) in zip(marks, marks[1:]):
        inside = [r for r in recs if lo <= r["start"] and r["end"] <= hi]
        out.append([name, hi - lo, sum(r["self_s"] for r in inside),
                    sum(r["name"] == "compile.backend" for r in inside)])
    return out


def child(spec: str, out_dir: str) -> int:
    wl, seed, seconds, trace = spec.split(":")[:4]
    sys.path.insert(0, ROOT)
    from benchmark import harness, run
    reader_s = {}
    plain = harness.read_layer_metrics

    def timed(cell, trace_, counters, spans):
        got = {}
        for m in cell.per_layer():
            cell.per_layer = lambda m=m: [m]
            t0 = time.monotonic()
            try:
                got.update(plain(cell, trace_, counters, spans))
            finally:
                del cell.per_layer
            reader_s[m["name"]] = time.monotonic() - t0
        return got
    harness.read_layer_metrics = timed

    class Tee(io.TextIOBase):
        def __init__(self, under):
            self.under, self.kept = under, []

        def write(self, s):
            self.kept.append(s)
            return self.under.write(s)

        def flush(self):
            self.under.flush()
    tee = sys.stdout = Tee(sys.stdout)
    try:
        rc = run.main(["--workload", wl, "--seed", seed, "--seconds",
                       seconds, "--trace", trace])
    finally:
        sys.stdout = tee.under
    said = "".join(tee.kept)
    m = re.search(r"setup_s[' :]+(?:\{'value': )?([0-9.]+)", said)
    if rc != 0 or not m:
        return rc or 1
    setup_s = float(m.group(1))
    from paddle_tpu.observability import compile_log, default_ring
    recs, ring = compile_log.records(), default_ring().recent()
    t_window = harness.T_PROCESS_START + setup_s
    before = [r for r in recs if r["end"] <= t_window]
    cut = max((r["end_epoch_ns"] for r in before), default=0) * 1e-9
    by = {(row["program"], phase): row[key]
          for row in compile_log.by_program(until_epoch_s=cut)
          for phase, key in (("compile.trace", "trace_s"),
                             ("compile.lower", "lower_s"),
                             ("compile.backend", "backend_s"))}
    doc = {
        "spec": spec, "setup_s": setup_s,
        "stages": stages(recs, ring, harness.T_PROCESS_START, t_window),
        "before": compile_log.totals(until_epoch_s=cut),
        "after": compile_log.totals(since_epoch_s=cut + 1e-9),
        "nested_traces": sum(r["parent"] is not None for r in before),
        "largest": [[p, n, s] for (p, n), s in
                    sorted(by.items(), key=lambda kv: -kv[1])[:10]],
        "import": [{k: e[k] for k in ("dur_s", "jax_preloaded")}
                   for e in ring if e["name"] == "paddle_tpu.import"],
        "loader_start": [{k: e.get(k) for k in
                          ("dur_s", "num_workers", "transport")}
                         for e in ring if e["name"] == "dataloader.start"],
        "reader_s": {k: v for k, v in reader_s.items()
                     if k.startswith("setup_")},
        "all_readers_s": sum(reader_s.values()),
        "log": dict(compile_log.totals(), records=len(recs),
                    jsonl_bytes=len(compile_log.to_jsonl())),
    }
    name = spec.replace(":", "_")
    with gzip.open(os.path.join(out_dir, name + ".compile_log.jsonl.gz"),
                   "wt") as f:
        f.write(compile_log.to_jsonl())
        f.write("\n" + "\n".join(
            json.dumps(e) for e in ring
            if e["name"] in ("paddle_tpu.import", "dataloader.start")))
    print(MARK + json.dumps(doc), flush=True)
    return 0


def main(argv) -> int:
    if argv[1] == "--child":
        return child(argv[2], argv[3])
    tag, specs = argv[1], argv[2:]
    out = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out, exist_ok=True)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    for k, spec in enumerate(specs):
        if spec.endswith(":cold"):
            shutil.rmtree(cache, ignore_errors=True)
        t0 = time.time()
        p = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--child", spec, out], cwd=ROOT,
                           capture_output=True, text=True)
        with open(os.path.join(out, f"run{k}.log"), "w") as f:
            f.write(p.stdout + "\n--- stderr ---\n" + p.stderr)
        lines = [l for l in p.stdout.splitlines() if l.strip()]
        print(f"=== run {k}: {spec} rc={p.returncode} "
              f"wall={time.time() - t0:.0f}s")
        for l in lines:
            if l.startswith(MARK) or l.startswith('{"correct"') or \
                    "train step ready" in l or "window:" in l or \
                    "reference followed" in l:
                print("   ", l if l.startswith(MARK)
                      else l.split(', "breakdown"')[0][:1500])
        if p.returncode != 0:
            print("    stderr:", p.stderr[-2000:])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
