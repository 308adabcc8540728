#!/usr/bin/env python3
"""One TRACED run of a cell (entered, or ``<config>:<traffic>:<chips>``)
on the chip, with the trace kept and laid out by the program's names:

    python3 benchmark/tests/traced_slice.py <workload> <seed> <seconds> <tag> [<trace_after_s> <trace_seconds>]

The run itself is the cell's own (every reader under ``layer_metrics/``
for a cell not entered yet); the two last arguments replace, in memory,
where a serving traffic file puts its traced slice (a span is recorded
only if it opens AND closes inside the slice, and an engine step that
compiles takes seconds).  Afterwards the ``.xplane.pb`` is gzipped
into ``chiprun_out/<tag>/`` and a by-hand summary is printed: device
self time by scope, phase, category and kernel, every program span's
count and seconds, and the device's idle time by the innermost span.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import types
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def summary(path: str, cell) -> dict:
    from benchmark import xplane, xplane_meta
    mt = xplane_meta.load(path).named(*xplane_meta.names_of(cell))
    tr = xplane.reduce(path)
    out = {"device_self_s": mt.device_self_s(),
           "slice_s": tr.window_s,
           "programs": defaultdict(int)}
    for name, _, _ in mt.modules.get(mt.chip(), []):
        out["programs"][xplane.module_base(name)] += 1
    for key in ("scope", "phase", "category", "kernel"):
        out[key] = dict(sorted(mt.self_time_by(key).items(),
                               key=lambda kv: -kv[1])[:16])
    spans = defaultdict(lambda: [0, 0.0])
    for h in mt.spans():
        spans[h.name][0] += 1
        spans[h.name][1] += h.end_s - h.start_s
    out["spans"] = {k: {"n": n, "s": s} for k, (n, s) in spans.items()}
    out["idle_by_span"] = mt.idle_by_span(tr.lo, tr.hi)
    admits = [dict(h.attrs, s=h.end_s - h.start_s)
              for h in mt.spans(("engine.admit",))]
    out["admits"] = admits[:12]
    return out


def main(argv) -> int:
    from benchmark import harness
    cell = harness.find_cell(argv[1])
    args = types.SimpleNamespace(workload=cell.name, seed=int(argv[2]),
                                 seconds=float(argv[3]), trace=1)
    if len(argv) > 6:
        cell.traffic["trace_after_s"] = float(argv[5])
        cell.traffic["trace_seconds"] = float(argv[6])
    train = cell.traffic["kind"] == "train_job"
    if train:
        from benchmark import train_cell as runner
    else:
        from benchmark import serve_cell as runner
    if cell.bench is None:
        # a cell not entered runs every reader file; the other kind's
        # readers expect their own traffic keys (``mfu_pct.train`` reads
        # ``seq`` once it finds a module ``jit_step``, which a decode
        # step is too)
        mine = (".train",) if train else (".serve", ".batch")
        readers = [m for m in cell.per_layer()
                   if m["name"].endswith(mine)]
        cell.per_layer = lambda: readers
    rc = runner.run(args, cell)
    from benchmark import xplane
    src = xplane.find_xplane(harness.run_dir(cell) + "/trace")
    out = os.path.join(ROOT, "chiprun_out", argv[4])
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, cell.name + ".xplane.pb.gz")
    with open(src, "rb") as f, gzip.open(dst, "wb", 6) as g:
        shutil.copyfileobj(f, g)
    print(f"[slice] trace kept: {dst} "
          f"({os.path.getsize(dst) / 2**20:.1f} MiB gzipped)")
    print("[slice] SUMMARY " + json.dumps(summary(src, cell)))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
