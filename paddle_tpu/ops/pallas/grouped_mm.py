"""Grouped matrix products over the experts a device holds, as Pallas
TPU kernels: rows sorted by expert, one weight matrix a group.

The rows arrive as ``ops/moe.py`` lays them out: ``[M, K]`` with every
group starting at a multiple of ``TILE_M`` rows, so that a row tile
belongs to ONE expert.  ``tile_expert [M / TILE_M]`` names it and
``n_tiles`` says how many tiles hold rows at all; both are data (scalar
prefetch), the shapes are static.  ``M`` is the static bound of the
routing (every pair of every token on this device), most of it empty at
a balanced load: a tile past ``n_tiles`` costs one empty grid step — its
index maps stay on the last tile that holds rows, so nothing is fetched
or written for it.

    grouped_mm    out[rows of e] = x[rows of e] @ w[e]        [M, N]
                  (``trans_w``: @ w[e].T, the product's dx)
    grouped_mm_dw dw[e] = x[rows of e].T @ dy[rows of e]      [E, K, N]

A weight block is the expert's whole ``[K, tn]`` panel, and the panels
are the OUTER grid axis: consecutive tiles of one expert find theirs in
VMEM already, so a product reads each expert's weights once.  The
weights come AS THE OPTIMIZER HOLDS THEM (fp32): an expert's first tile
casts its panel to the rows' dtype into VMEM scratch, so no bf16 copy of
the stack exists in HBM, and ``grouped_mm_dw`` adds a group's tiles up
in the fp32 output block itself, which leaves for HBM when the next tile
is another expert's — the gradient in fp32, no rounding on the way.  An
expert WITHOUT A TILE is never visited and its block of dw is never
written: the layout gives every expert one (``ops/moe.plan``).  Rows of
a tile past the group's own count are whatever the layout put there:
the caller keeps them finite going in and zero coming back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["TILE_M", "grouped_mm", "grouped_mm_dw"]

TILE_M = 256
# the widest panel of columns a grid step takes: an expert's [K, tn]
# fp32 weight block twice (the pipeline's two buffers) and its cast are
# 37 MiB at K 3584
_MAX_COLS = 1024
_VMEM_LIMIT = 64 << 20


def _cols(n: int, most: int = _MAX_COLS) -> int:
    """The widest divisor of ``n`` that is a multiple of 128 and at most
    ``most``; ``n`` itself where it is small or has none."""
    if n <= most:
        return n
    for c in range(most - most % 128, 0, -128):
        if n % c == 0:
            return c
    return n


def _held(i, n_ref):
    """The tile a grid step reads and writes: its own while it holds
    rows, else the last one that does (no block changes, no DMA)."""
    last = jnp.maximum(n_ref[0] - 1, 0)
    return jnp.minimum(jnp.int32(i), last)


def _mm_kernel(te_ref, n_ref, x_ref, w_ref, o_ref, w_cast, *,
               trans_w: bool):
    i = pl.program_id(1).astype(jnp.int32)

    @pl.when(i < n_ref[0])
    def _():
        @pl.when((i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != te_ref[i]))
        def _():
            w_cast[:] = w_ref[:].astype(w_cast.dtype)
        dims = (((1,), (1 if trans_w else 0,)), ((), ()))
        o_ref[:] = jax.lax.dot_general(
            x_ref[:], w_cast[:], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_mm(x, w, tile_expert, n_tiles, trans_w: bool = False,
               out_rows: int = 0):
    """x [M, K] @ w[e] ([E, K, N]; ``trans_w``: [E, N, K]; any float
    dtype) -> [M, N] in x's dtype, ``e`` the expert of the row's tile.
    Tiles past ``n_tiles`` are left unwritten — and so are the rows past
    M of a result asked for at ``out_rows`` > M rows (a caller that has
    to hand on a longer array gets it without a copy)."""
    M, K = x.shape
    N = w.shape[1] if trans_w else w.shape[2]
    tn = _cols(N)
    w_block = (None, tn, K) if trans_w else (None, K, tn)

    def w_index(j, i, te, n):
        e = te[_held(i, n)]
        return idx32(e, j, 0) if trans_w else idx32(e, 0, j)
    # the BOUND the call is launched at: every tile of M holding rows
    # (how many do is data).  x again for every column panel, every
    # expert's weights once (a panel at a time, as the optimizer holds
    # them), the result once
    cost = pl.CostEstimate(
        flops=2 * M * K * N, transcendentals=0,
        bytes_accessed=(N // tn) * _common.nbytes((M, K), x.dtype)
        + _common.nbytes(w.shape, w.dtype)
        + _common.nbytes((M, N), x.dtype))
    return pl.pallas_call(
        functools.partial(_mm_kernel, trans_w=trans_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, M // TILE_M),
            in_specs=[
                pl.BlockSpec((TILE_M, K),
                             lambda j, i, te, n: idx32(_held(i, n), 0)),
                pl.BlockSpec(w_block, w_index)],
            out_specs=pl.BlockSpec(
                (TILE_M, tn), lambda j, i, te, n: idx32(_held(i, n), j)),
            scratch_shapes=[pltpu.VMEM(w_block[1:], x.dtype)]),
        out_shape=jax.ShapeDtypeStruct((max(M, out_rows), N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_mm",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(tile_expert, n_tiles, x, w)


def _dw_kernel(te_ref, n_ref, x_ref, dy_ref, dw_ref):
    i = pl.program_id(2).astype(jnp.int32)

    @pl.when(i < n_ref[0])
    def _():
        @pl.when((i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != te_ref[i]))
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)
        dw_ref[:] += jax.lax.dot_general(
            x_ref[:], dy_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def grouped_mm_dw(x, dy, tile_expert, n_tiles, experts: int):
    """dw[e] = x[rows of e].T @ dy[rows of e]: x [M, K], dy [M, N] ->
    [experts, K, N] in fp32.  An expert with no tile is left unwritten."""
    M, K = x.shape
    N = dy.shape[1]
    tk, tn = _cols(K, 1792), _cols(N, 1792)
    # the bound, as grouped_mm's: every tile of M.  x again for every
    # panel of dy's columns, dy for every panel of x's, every expert's
    # fp32 block out once
    cost = pl.CostEstimate(
        flops=2 * M * K * N, transcendentals=0,
        bytes_accessed=(N // tn) * _common.nbytes((M, K), x.dtype)
        + (K // tk) * _common.nbytes((M, N), dy.dtype)
        + 4 * experts * K * N)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tk, N // tn, M // TILE_M),
            in_specs=[
                pl.BlockSpec((TILE_M, tk),
                             lambda a, b, i, te, n: idx32(_held(i, n), a)),
                pl.BlockSpec((TILE_M, tn),
                             lambda a, b, i, te, n: idx32(_held(i, n), b))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda a, b, i, te, n: idx32(te[_held(i, n)], a, b))),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_mm_dw",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(tile_expert, n_tiles, x, dy)
