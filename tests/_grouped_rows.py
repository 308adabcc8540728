"""Rows laid out for the grouped products as ``ops/moe.plan`` lays them,
for the kernels' tests on the CPU (``test_pallas_kernels.py``) and on
the chip (``test_pallas_tpu.py``)."""

import numpy as np

import jax.numpy as jnp


def laid_out(sizes, spare):
    """Groups of ``sizes`` rows: each at a multiple of TILE_M, one tile
    at least, ``spare`` tiles never used behind them.  -> (M,
    tile_expert, n_tiles, starts, tiles, valid [M, 1])."""
    from paddle_tpu.ops.pallas.grouped_mm import TILE_M
    tiles = [max(-(-n // TILE_M), 1) for n in sizes]
    M = (sum(tiles) + spare) * TILE_M
    te = np.full((M // TILE_M,), len(sizes) - 1, np.int32)
    te[:sum(tiles)] = np.repeat(np.arange(len(sizes)), tiles)
    starts = np.concatenate([[0], np.cumsum(tiles)[:-1]]) * TILE_M
    valid = np.zeros((M, 1), bool)
    for e0, n in zip(starts, sizes):
        valid[e0:e0 + n] = True
    return (M, jnp.asarray(te), jnp.asarray([sum(tiles)], jnp.int32),
            starts, tiles, valid)
