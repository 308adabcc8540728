#!/usr/bin/env python3
"""What the residual streams' mixers ran as, from a device trace:

    python3 tools/hc_kernels.py <trace.xplane.pb[.gz]> [<tokens> <hidden>]

The four kernels of ``ops/pallas/hc_mix.py`` are AHEAD of the readers'
copy of the vocabulary (``benchmark/models/xing_mhc_moe.KERNELS`` is a
benchmark file: ROADMAP D14), so ``traced_slice.py``'s by-kernel split
leaves them out; the trace is read here with the four as its kernels
and ``hc_pre`` / ``hc_post`` as its scopes.  Prints one JSON line, a
step of ``jit_step`` at a time: each kernel's runs, milliseconds and
the GB/s its declared bytes make of them; the two scopes' time by
phase; and what is left under the scopes that is no kernel — its
largest ops with the bytes XLA states for them, in widths (one
``[tokens, hidden]`` bf16 array; the expert cell's 16,384 x 3,584 by
default), the way to see a fusion that moves the streams again."""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
KERNELS = ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd")
SCOPES = ("hc_pre", "hc_post")


def count(mt, width_bytes: float) -> dict:
    """``mt``: a ``benchmark.xplane_meta.MetaTrace``."""
    from benchmark import xplane_meta
    steps = mt.executions("jit_step") or 1
    kernels = {k: {"runs": 0, "ms": 0.0, "bytes": 0.0} for k in KERNELS}
    scopes = defaultdict(lambda: defaultdict(float))
    rest = defaultdict(lambda: [0, 0.0, 0.0])
    for op in mt.ops.get(mt.chip(), []):
        scope = xplane_meta.scope_of(op.tf_op, SCOPES)
        if scope not in SCOPES:
            continue
        scopes[scope][xplane_meta.phase_of(op.tf_op)] += op.self_s
        kernel = xplane_meta.kernel_of(op.tf_op, KERNELS)
        # a copy that XLA puts behind a kernel's result carries its path
        if kernel and "custom-call(" in op.name:
            k = kernels[kernel]
            k["runs"] += 1
            k["ms"] += op.self_s * 1e3
            k["bytes"] += op.bytes_accessed
        else:
            # the instruction's name and result, not its operands
            r = rest[(scope, op.name.split(" fusion(")[0][:120])]
            r[0] += 1
            r[1] += op.self_s
            r[2] = max(r[2], op.bytes_accessed)
    out = {"steps": steps, "kernels": {}, "scopes_ms_a_step": {
        s: {p: round(v * 1e3 / steps, 3) for p, v in by.items()}
        for s, by in scopes.items()}}
    for name, k in kernels.items():
        out["kernels"][name] = {
            "runs_a_step": k["runs"] / steps,
            "ms_a_step": round(k["ms"] / steps, 3),
            "ms_a_run": round(k["ms"] / k["runs"], 4) if k["runs"] else None,
            "gb_s": round(k["bytes"] / k["ms"] / 1e6, 1) if k["ms"] else None}
    out["not_kernels_ms_a_step"] = round(
        sum(r[1] for r in rest.values()) * 1e3 / steps, 3)
    out["largest_not_kernels"] = [
        {"scope": scope, "op": name, "runs_a_step": r[0] / steps,
         "ms_a_step": round(r[1] * 1e3 / steps, 3),
         "widths": round(r[2] / width_bytes, 2)}
        for (scope, name), r in sorted(rest.items(),
                                       key=lambda kv: -kv[1][1])[:12]]
    return out


def read(path: str, width_bytes: float) -> dict:
    from benchmark import xplane_meta
    from tools.moe_bounds import unzipped
    with unzipped(path) as plain:
        return count(xplane_meta.load(plain), width_bytes)


if __name__ == "__main__":
    tokens, hidden = (int(a) for a in sys.argv[2:4]) \
        if len(sys.argv) > 3 else (16384, 3584)
    print(json.dumps(read(sys.argv[1], 2.0 * tokens * hidden)))
