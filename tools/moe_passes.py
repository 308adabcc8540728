#!/usr/bin/env python3
"""The routed experts' device time by scope AND pass, from a device
trace of a training cell:

    python3 tools/moe_passes.py <trace.xplane.pb[.gz]> <cell>

``benchmark/tests/traced_slice.py`` splits a step by scope and by phase
apart; what full remat repeats of an expert layer shows only in the
two together (``moe_dispatch`` in the RECOMPUTE is the plan run again
and the second gather of x; PERF.md section 5).  Prints one JSON line:
device self ms a step of each ``moe_*`` scope in each pass, the same of
the compiler's ``sort`` and ``pad`` ops wherever they run, and the
largest ops of the recompute under the ``moe_*`` scopes."""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def by_pass(mt) -> dict:
    """``mt``: a ``benchmark.xplane_meta.MetaTrace`` named for its cell.
    Seconds over the whole trace, keys ``<scope>|<pass>``."""
    from benchmark import xplane_meta
    scopes, sorts, largest = (defaultdict(float) for _ in range(3))
    for op in mt.ops.get(mt.chip(), []):
        scope = xplane_meta.scope_of(op.tf_op, mt.scopes)
        phase = xplane_meta.phase_of(op.tf_op)
        if op.category in ("sort", "pad"):
            sorts[f"{op.category}|{scope}|{phase}"] += op.self_s
        if not scope.startswith("moe_"):
            continue
        scopes[f"{scope}|{phase}"] += op.self_s
        if phase == "recompute":
            largest[f"{scope}|{op.category}|{op.name[:64]}"] += op.self_s
    return {"scope_pass": dict(scopes), "sort_pad": dict(sorts),
            "recompute_ops": dict(sorted(
                largest.items(), key=lambda kv: -kv[1])[:12])}


def read(path: str, cell_name: str) -> dict:
    from benchmark import harness, xplane_meta
    from tools.moe_bounds import unzipped
    cell = harness.find_cell(cell_name)
    with unzipped(path) as plain:
        mt = xplane_meta.load(plain).named(*xplane_meta.names_of(cell))
    steps = max(mt.executions("jit_step"), 1)

    def ms_a_step(part):
        return {k: round(1e3 * v / steps, 3) for k, v in sorted(
            part.items(), key=lambda kv: -kv[1])}
    return {"steps": steps, "ms_a_step": {
        name: ms_a_step(part) for name, part in by_pass(mt).items()}}


if __name__ == "__main__":
    print(json.dumps(read(sys.argv[1], sys.argv[2])))
