#!/usr/bin/env python3
"""The grouped products alone, on the chip, at the three expert cells'
shapes and loads:

    python3 tools/time_grouped_mm.py [<another grouped_mm.py> ...]

Times ``grouped_mm`` (forward and ``trans_w``) and ``grouped_mm_dw`` of
this checkout and of every other ``grouped_mm.py`` named (a parent's, a
variant's: loaded beside this checkout's, so one process and one layout
time them all, in turn, twice).  The rows are laid out as
``ops/moe.plan`` lays a balanced load on the bound that follows it
(``load_bound``: half the tiles in use).  One JSON line a product: ms a
call of each file, the ms its tiles' products take at the bf16 peak, and
the (expert, panel) changes a call makes."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# cell: experts held, picks a token, [K, N] of gate | up and of down
CELLS = {"convolution": (16, 4, ((2048, 3072), (1536, 2048))),
         "window": (16, 6, ((2560, 1536), (768, 2560))),
         "expert": (8, 4, ((3584, 2048), (1024, 3584)))}
TOKENS, PUBLISHED, RUNS = 16384, 64, 20


def load(path: str):
    name = "paddle_tpu.ops.pallas._timed_" + str(abs(hash(path)))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def main(paths):
    import numpy as np
    import paddle_tpu  # noqa: F401  (x64 before any array)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.device import peaks
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import grouped_mm as own
    if jax.default_backend() != "tpu":
        raise SystemExit("time_grouped_mm: needs the chip")
    peak = peaks.chip_peaks().flops
    files = {"own": own, **{p: load(p) for p in paths}}
    rng = np.random.default_rng(49)
    for cell, (held, k, shapes) in CELLS.items():
        M = moe.load_bound(TOKENS, k, held, PUBLISHED)
        sizes = rng.multinomial(TOKENS * k * held // PUBLISHED,
                                np.full(held, 1 / held))
        tiles = -(-sizes // own.TILE_M)
        te = np.full(M // own.TILE_M, held - 1, np.int32)
        te[:tiles.sum()] = np.repeat(np.arange(held), tiles)
        te, n = jnp.asarray(te), jnp.asarray([tiles.sum()], jnp.int32)
        for K, N in shapes:
            ks = jax.random.split(jax.random.PRNGKey(K), 3)
            x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
            dy = jax.random.normal(ks[1], (M, N), jnp.bfloat16)
            w = jax.random.normal(ks[2], (held, K, N), jnp.float32)
            # the arrays are ARGUMENTS: closed over, they would be
            # constants of the program, 300 MB to compile each
            calls = {
                "grouped_mm": lambda m: (
                    jax.jit(lambda x, dy, w: m.grouped_mm(x, w, te, n)),
                    N // m._cols(N)),
                "grouped_mm.trans_w": lambda m: (
                    jax.jit(lambda x, dy, w: m.grouped_mm(
                        dy, w, te, n, trans_w=True)),
                    K // m._cols(K)),
                "grouped_mm_dw": lambda m: (
                    jax.jit(lambda x, dy, w: m.grouped_mm_dw(
                        x, dy, te, n, held)),
                    (K // m._cols(K, 1792)) * (N // m._cols(N, 1792)))}
            for what, build in calls.items():
                built = {name: build(m) for name, m in files.items()}
                ms = {name: [] for name in files}
                for _ in range(2):
                    for name, (f, _) in built.items():
                        f(x, dy, w).block_until_ready()
                        t = time.perf_counter()
                        for _ in range(RUNS):
                            out = f(x, dy, w)
                        out.block_until_ready()
                        ms[name].append(round(
                            1e3 * (time.perf_counter() - t) / RUNS, 4))
                print(json.dumps({
                    "cell": cell, "product": what, "K": K, "N": N,
                    "tiles": int(tiles.sum()), "of": M // own.TILE_M,
                    "changes": held * built["own"][1],
                    "ms_at_peak": round(
                        2e3 * int(tiles.sum()) * own.TILE_M * K * N / peak,
                        4),
                    "ms_a_call": ms}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
