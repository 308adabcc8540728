#!/usr/bin/env python3
"""The flash backward alone, on the chip, at a cell's attention shapes:

    python3 tools/time_flash_bwd.py [<cell> ...]

Times ``flash_attention._flash_bwd_vjp`` — one layer's backward, the
forward's results handed in — as the rule of shapes builds it and as the
TWO kernels (both one-pass budgets at 0 bytes while that program is
traced: the module's constants, as the parity tests set them), in one
process, in turn, twice.  One JSON line a cell: the kernels each form
calls with the scoped VMEM each asks, ms a layer of each form, and how
far apart the two forms' gradients lie (0.0: the same bits).  No cell
named: the split form's two cells."""

from __future__ import annotations

import importlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# cell: batch, row, query heads, KV heads, d, d2 of a split score, window
CELLS = {
    "kanana-2-30b-a3b.pretrain-16k-mla-moe": (1, 16384, 32, 32, 128, 64, None),
    "xing4.0-29b-a4b.pretrain-8k-moe": (2, 8192, 32, 32, 128, 64, None),
    "smallthinker-21b-a3b.pretrain-16k-moe": (1, 16384, 28, 4, 128, 0, 4096),
    "granite-4.0-h-micro.pretrain-8k": (2, 8192, 32, 8, 64, 0, None),
    "internlm2-1.8b.pretrain-2k": (8, 2048, 16, 8, 128, 0, None)}
RUNS = 10
_NAME = re.compile(r'(flash_\w+?)\)*/pallas_call')
_VMEM = re.compile(r'scoped_memory_configs":\[\{[^\]]*?"size":"(\d+)"')


def kernels_of(text: str) -> list:
    """(kernel, scoped VMEM it asks | None: Mosaic's own limit) of an
    optimized module's Pallas calls, in the module's order."""
    found = []
    for line in text.splitlines():
        name = "tpu_custom_call" in line and _NAME.search(line)
        if name:
            asked = _VMEM.search(line)
            found.append((name.group(1), asked and int(asked.group(1))))
    return found


def main(cells):
    import paddle_tpu  # noqa: F401  (x64 before any array)
    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if jax.default_backend() != "tpu":
        raise SystemExit("time_flash_bwd: needs the chip")
    for cell in cells:
        b, s, h, nkv, d, d2, window = CELLS[cell]
        ks = jax.random.split(jax.random.PRNGKey(s + h), 6)
        normal = lambda key, *shape: jax.random.normal(
            key, shape, jnp.bfloat16)
        q, dout = normal(ks[0], b, s, h, d), normal(ks[1], b, s, h, d)
        k, v = normal(ks[2], b, s, nkv, d), normal(ks[3], b, s, nkv, d)
        q2, k2 = (normal(ks[4], b, s, h, d2), normal(ks[5], b, s, d2)) \
            if d2 else (None, None)
        scale = (d + d2) ** -0.5
        res = jax.jit(lambda *a: fa._flash_fwd(
            *a, True, scale, window)[1])(q, k, v, q2, k2)

        def form(budgets):
            """The backward compiled under ``budgets`` (None: the
            module's own) -> (program, [(kernel, VMEM asked)])."""
            was = fa.ONE_PASS_DQ_BYTES, fa.ONE_PASS_DKV_BYTES
            if budgets is not None:
                fa.ONE_PASS_DQ_BYTES, fa.ONE_PASS_DKV_BYTES = budgets
            try:
                program = jax.jit(lambda res, dout: fa._flash_bwd_vjp(
                    True, scale, window, res, dout)).lower(res, dout).compile()
            finally:
                fa.ONE_PASS_DQ_BYTES, fa.ONE_PASS_DKV_BYTES = was
            return program, kernels_of(program.as_text())
        forms = {"rule": form(None), "two_kernels": form((0, 0))}
        ms = {name: [] for name in forms}
        grads = {}
        for _ in range(2):
            for name, (program, _) in forms.items():
                grads[name] = jax.block_until_ready(program(res, dout))
                t = time.perf_counter()
                for _ in range(RUNS):
                    out = program(res, dout)
                jax.block_until_ready(out)
                ms[name].append(round(
                    1e3 * (time.perf_counter() - t) / RUNS, 3))
        apart = max(
            float(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32))
                  .max())
            for a, b_ in zip(*(jax.tree_util.tree_leaves(grads[name])
                               for name in forms)))
        print(json.dumps({
            "cell": cell, "b": b, "s": s, "heads": h, "kv_heads": nkv,
            "d": d, "d2": d2, "window": window,
            "kernels": {name: kernels for name, (_, kernels)
                        in forms.items()},
            "ms_a_layer": ms, "max_abs_apart": apart}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or [c for c, shape in CELLS.items() if shape[5]])
