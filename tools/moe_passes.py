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
ops of the recompute under the ``moe_*`` scopes, — ``calls`` — the
two grouped products BY CALL SITE: what a run takes beyond its tiles'
products, and how much of that each change of expert costs
(docs/OBSERVABILITY.md, "The grouped products by call site"), and —
``layer_scan`` — what the layer loops themselves hold, BY KIND: the ops
no inner scope claims (a layer's leaves sliced out of their stack, a
layer's gradient written into its stack, the zeros those stacks start
from), count, ms and GB a step each, and how much of that moves the
fp32 EXPERT stacks, read side and write side apart."""

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
        # the result, the tiles' experts, then — past the scalars: the
        # count of tiles, a stack's layer — the rows and what they meet:
        # dy, or the experts ``[layers, held, ., .]``
        out, (grid,), *operands = (
            tuple(map(int, m.split(","))) for m in _SHAPE.findall(text))
        (_, K), last = [shape for shape in operands if len(shape) > 1][:2]
        if kernel == "grouped_mm_dw":
            held, N = out[0], last[1]
            blocks = (K // _cols(K, 1792)) * (N // _cols(N, 1792))
        else:
            held, N = last[-3], out[1]
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


LOOPS = "layer_scan"
_RESULT = re.compile(r" = (\(.*?\)|\S+) [a-z\-]+\(")
_ARRAY = re.compile(r"([a-z]+\d+)\[([0-9,]*)\]")
# what an op that computes nothing does to an array, by the words XLA
# made its name of and the primitive its path ends in: a COPY of what is
# there (a layer sliced out of a stack for a kernel that takes whole
# arrays, a run's slice of a stack) or the WRITE of what was computed
# (a layer's gradient into its stack, the runs' stacks joined, the zeros
# a stack starts from) — or a PREFETCH, the asynchronous copy XLA itself
# schedules into its fast memory for the op that follows
_PREFETCHES = ("copy-start", "copy-done", "slice-start", "slice-done")
_WRITES = ("dynamic-update-slice", "concatenate", "broadcast")
_COPIES = ("slice", "squeeze", "copy")


def moves_of(stem: str, primitive: str) -> str:
    if stem in _PREFETCHES:
        return "prefetch"
    words = f"{stem}/{primitive}".replace("_", "-")
    for moves, names in (("write", _WRITES), ("copy", _COPIES)):
        if any(name in words for name in names):
            return moves
    return ""


def under_the_loops(mt, steps: int, experts=()) -> dict:
    """The ops charged to ``layer_scan`` itself (no inner scope claims
    them), a row a kind: the instruction's name without its number, the
    primitive its path ends in, the pass, its result(s) — count, ms and
    GB (``bytes_accessed``) a step.  ``experts``: the shapes of a
    layer's fp32 expert leaves; an op whose result is one, or a run's
    or a stack's multiple of one, is ``of_experts``, and ``experts``
    sums those that only move data: ``copy`` — 0 where the grouped
    products read the stacks in place —, ``write`` and ``prefetch``."""
    from benchmark import xplane_meta
    kinds = defaultdict(lambda: [0, 0.0, 0.0])
    for op in mt.ops.get(mt.chip(), []):
        if xplane_meta.scope_of(op.tf_op, mt.scopes) != LOOPS:
            continue
        stem = re.sub(r"[.\d]+$", "", op.name.split(" ", 1)[0].lstrip("%"))
        result = _RESULT.search(op.name)
        kind = kinds[stem, op.tf_op.rsplit("/", 1)[-1],
                     xplane_meta.phase_of(op.tf_op),
                     " ".join(f"{t}[{dims}]" for t, dims in _ARRAY.findall(
                         result.group(1))) if result else ""]
        kind[0] += 1
        kind[1] += op.self_s
        kind[2] += op.bytes_accessed
    tails = {tuple(shape) for shape in experts}
    rows, moved = [], {m: [0.0, 0.0, 0.0]
                       for m in ("copy", "write", "prefetch")}
    for (stem, primitive, phase, result), (n, s, b) in kinds.items():
        of_experts = any(
            t == "f32" and tuple(map(int, dims.split(",")[-3:])) in tails
            for t, dims in _ARRAY.findall(result) if dims.count(",") >= 2)
        moves = moves_of(stem, primitive)
        rows.append({"op": stem, "of": primitive, "pass": phase,
                     "result": result, "moves": moves,
                     "of_experts": of_experts,
                     "count": round(n / steps, 2),
                     "ms": round(1e3 * s / steps, 3),
                     "GB": round(b / steps / 1e9, 3)})
        if of_experts and moves:
            for i, v in enumerate((n, 1e3 * s, b / 1e9)):
                moved[moves][i] += v / steps
    rows.sort(key=lambda r: -r["ms"])
    return {"ms": round(sum(r["ms"] for r in rows), 3),
            "experts": {m: dict(zip(("count", "ms", "GB"),
                                    (round(v, 3) for v in got)))
                        for m, got in moved.items()},
            "kinds": [r for r in rows if r["ms"] >= 0.05 or r["of_experts"]]}


def expert_leaves(cell) -> list:
    """The shapes of a layer's expert leaves in the cell's program, gate
    | up ``[held, C, 2 F]`` and down ``[held, F, C]`` (none where no
    kind routes)."""
    from paddle_tpu.models import hybrid_trunk
    cfg = cell.family.build_cfg(cell.conf, True, cell.traffic)
    routed = set(cfg.layer_types or ()) & set(hybrid_trunk.ROUTED_KINDS)
    return sorted({hybrid_trunk.kind_shapes(cfg, kind)[leaf]
                   for kind in routed
                   for leaf in ("we_gate_up", "we_down")}, reverse=True)


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
        "calls": by_call(mt, cell, steps, peak),
        LOOPS: under_the_loops(mt, steps, expert_leaves(cell))}


if __name__ == "__main__":
    print(json.dumps(read(sys.argv[1], sys.argv[2])))
