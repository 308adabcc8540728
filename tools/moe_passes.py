#!/usr/bin/env python3
"""The routed experts' device time by scope AND pass, from a device
trace of a training cell:

    python3 tools/moe_passes.py <trace.xplane.pb[.gz]> <cell>

``benchmark/tests/traced_slice.py`` splits a step by scope and by phase
apart; what full remat repeats of an expert layer shows only in the
two together (``moe_dispatch`` in the RECOMPUTE is the plan run again
and the second gather of x; PERF.md section 5).  Prints one JSON line:
device self ms a step of each ``moe_*`` scope in each pass, the same of
the compiler's ``sort`` and ``pad`` ops wherever they run, the largest
ops of the recompute under the ``moe_*`` scopes, and — ``calls`` — the
two grouped products BY CALL SITE: what a run takes beyond its tiles'
products, and how much of that each change of expert costs
(docs/OBSERVABILITY.md, "The grouped products by call site")."""

from __future__ import annotations

import json
import os
import re
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


# the chip the cells' traces come from (a trace does not name its kind)
DEVICE_KIND = "TPU v5 lite"
_SHAPE = re.compile(r"(?:bf16|f32|s32)\[([0-9,]+)\]")


def by_call(mt, cell, steps: int, peak_flops: float) -> list:
    """One row a call site of ``grouped_mm`` / ``grouped_mm_dw``, from
    the instruction's own text (its shapes) and its runs: ``[K, N]`` of
    the product, runs a step, ms a run, the grid's tiles, the tiles in
    use — EXPECTED, a trace holds no load: the family's pairs a token at
    a balanced load over TILE_M rows, and half a tile an expert —, the
    ms those tiles' products take at the bf16 peak, the (expert, block)
    changes a run makes, and the us a change of what a run takes beyond
    the products."""
    from benchmark import xplane_meta
    from paddle_tpu.ops.pallas.grouped_mm import TILE_M, _cols
    from tools.moe_bounds import BOUNDS
    pairs = cell.family.expected_pairs_per_token(cell.conf) \
        * cell.traffic["batch"] * cell.traffic["seq"]
    sites = defaultdict(lambda: [0, 0.0])
    for op in mt.ops.get(mt.chip(), []):
        kernel = xplane_meta.kernel_of(op.tf_op, ("grouped_mm",
                                                  "grouped_mm_dw"))
        if kernel:
            site = sites[kernel, op.name, xplane_meta.phase_of(op.tf_op),
                         xplane_meta.scope_of(op.tf_op, BOUNDS)]
            site[0] += 1
            site[1] += op.self_s
    rows = []
    for (kernel, text, phase, bound), (runs, seconds) in sites.items():
        out, (grid,), _, (_, K), last = (
            tuple(map(int, m.split(","))) for m in _SHAPE.findall(text)[:5])
        if kernel == "grouped_mm_dw":
            held, N = out[0], last[1]
            blocks = (K // _cols(K, 1792)) * (N // _cols(N, 1792))
        else:
            held, N = last[0], out[1]
            blocks = N // _cols(N)
        in_use = min(pairs / TILE_M + held / 2, grid)
        at_peak = 2e3 * in_use * TILE_M * K * N / peak_flops
        ms = 1e3 * seconds / runs
        rows.append({
            "site": text.split(" ")[0].lstrip("%"), "pass": phase,
            "bound": bound, "K": K, "N": N, "runs_a_step": runs / steps,
            "ms_a_run": round(ms, 4), "tiles": grid,
            "tiles_in_use": round(in_use, 1),
            "ms_at_peak": round(at_peak, 4), "changes": held * blocks,
            "us_a_change": round(1e3 * (ms - at_peak) / (held * blocks), 2)})
    return sorted(rows, key=lambda r: r["site"])


def read(path: str, cell_name: str) -> dict:
    from benchmark import harness, peaks, xplane_meta
    from tools.moe_bounds import unzipped
    cell = harness.find_cell(cell_name)
    with unzipped(path) as plain:
        mt = xplane_meta.load(plain).named(*xplane_meta.names_of(cell))
    steps = max(mt.executions("jit_step"), 1)
    peak = peaks.chip_peaks(DEVICE_KIND).flops

    def ms_a_step(part):
        return {k: round(1e3 * v / steps, 3) for k, v in sorted(
            part.items(), key=lambda kv: -kv[1])}
    return {"steps": steps, "ms_a_step": {
        name: ms_a_step(part) for name, part in by_pass(mt).items()},
        "calls": by_call(mt, cell, steps, peak)}


if __name__ == "__main__":
    print(json.dumps(read(sys.argv[1], sys.argv[2])))
