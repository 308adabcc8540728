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
in an fp32 block in VMEM, which leaves for HBM when the next tile is
another expert's — the gradient in fp32, no rounding on the way.  An
expert WITHOUT A TILE is never visited and its block of dw is never
written: the layout gives every expert one (``ops/moe.plan``).  Rows of
a tile past the group's own count are whatever the layout put there:
the caller keeps them finite going in and zero coming back.

WHEN AN EXPERT'S BLOCK CROSSES between HBM and VMEM is the kernels' own
business, not the pipeline's that ``pallas_call`` builds around blocked
operands: a row tile's product takes ``512 K tn / 197e12`` s and the
fp32 block ``4 K tn / 819e9`` s — 1.88 grid steps whatever K and tn —
and that pipeline asks for a block ONE step ahead, in the same queue as
the next row tile, and has an output block back one step behind.  So
``w`` and ``dw`` stay in HBM (``pl.ANY``), with two fp32 slots in VMEM
each:

    grouped_mm     a group's FIRST tile waits for the group's panel,
                   asks for the NEXT group's into the other slot — the
                   next expert's, after the last expert the first one's
                   of the next column panel: the layout lists the
                   experts in order — at the DMA's low priority, behind
                   the row tiles, and casts its own.  The next panel has
                   the whole group to arrive (4–7 tiles at the cells'
                   loads); behind a group of ONE tile it is late and is
                   waited for.  Exposed: a call's first panel, and every
                   panel's cast (tried under the last product of the
                   group before: no faster, PERF.md section 6, PR 49).
    grouped_mm_dw  a group's first tile WRITES its product over the
                   slot (no zero-fill), the later ones add; its LAST
                   tile starts the block's copy to HBM, which is waited
                   for before the slot is written again, a group later —
                   and the last two at the call's last grid step.

Tiles past ``n_tiles`` start and wait for nothing.  The arithmetic and
its order are what blocked operands gave: bit for bit.

Because the kernel forms a panel's address itself, ``grouped_mm`` reads
it out of the experts of SEVERAL LAYERS as they lie stacked, ``[L, E, K,
N]``, at a layer's index — a third scalar, data: ``w_hbm.at[l, e, :,
cols]``.  A loop over layers hands every iteration the same array; a
kernel handed ``w[l]`` is handed a copy of the layer's experts that XLA
writes first (a custom call takes whole arrays).  One layer's ``[E, K,
N]`` is the stack of one, read at 0.  The product and the work it
declares are one layer's.  ``grouped_mm_dw`` writes a layer's ``[E, K,
N]``.
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

I32 = jnp.int32
TILE_M = 256
# the widest panel of columns a grid step takes: an expert's [K, tn]
# fp32 weight block twice (the two slots) and its cast are 37 MiB at
# K 3584
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


def _group(te_ref, n_ref, i):
    """Of the tile ``i`` (one that holds rows): whether it is its
    group's first, whether its last, its expert, the first and the last
    expert of the call.  ``ops/moe.plan`` gives every expert a tile and
    lays them in order, so the experts of a walk over the tiles are
    ``first .. last`` one after the other."""
    n, e = n_ref[0], te_ref[i]
    starts = (i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != e)
    ends = (i == n - 1) | (te_ref[jnp.minimum(i + 1, n - 1)] != e)
    return starts, ends, e, te_ref[0], te_ref[jnp.maximum(n - 1, 0)]


def _mm_kernel(te_ref, n_ref, l_ref, x_ref, w_hbm, o_ref, w_f32, w_cast, sem,
               *, trans_w: bool):
    j = pl.program_id(0).astype(I32)
    i = pl.program_id(1).astype(I32)
    tn = o_ref.shape[1]

    def fetch(e, j, slot):
        cols = pl.ds(pl.multiple_of(j * I32(tn), tn), tn)
        l = l_ref[0]
        panel = w_hbm.at[l, e, cols, :] if trans_w else w_hbm.at[l, e, :, cols]
        return pltpu.make_async_copy(panel, w_f32.at[slot], sem.at[slot])

    @pl.when(i < n_ref[0])
    def _():
        starts, _, e, first, last = _group(te_ref, n_ref, i)

        @pl.when(starts)
        def _():
            slot = (j * (last - first + 1) + e - first) % I32(2)

            @pl.when((j == 0) & (i == 0))
            def _():
                fetch(e, j, slot).start()     # the one fetch left exposed

            fetch(e, j, slot).wait()

            # the NEXT group's panel, a whole group ahead of its first
            # product: the next expert's, or the first one's of the next
            # column panel; the other slot was cast a group ago.  Behind
            # the row tiles in the DMA queues (priority 1): at the same
            # priority the panel's 1.88 steps of traffic hold up the
            # tiles that the next products wait for (timed: PERF.md
            # section 6, PR 49)
            @pl.when((e < last) | (j + 1 < pl.num_programs(0)))
            def _():
                fetch(jnp.where(e < last, e + 1, first),
                      jnp.where(e < last, j, j + 1),
                      I32(1) - slot).start(priority=1)
            w_cast[:] = w_f32[slot].astype(w_cast.dtype)
        dims = (((1,), (1 if trans_w else 0,)), ((), ()))
        o_ref[:] = jax.lax.dot_general(
            x_ref[:], w_cast[:], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_mm(x, w, tile_expert, n_tiles, trans_w: bool = False,
               out_rows: int = 0, layer=None):
    """x [M, K] @ w[layer, e] ([L, E, K, N]; ``trans_w``: [L, E, N, K];
    any float dtype) -> [M, N] in x's dtype, ``e`` the expert of the
    row's tile, ``layer`` (int32 ``[1]``, data) the layer of the stack
    whose experts are read — where they lie: a caller in a loop over the
    layers hands over the whole stack, not ``w[l]``.  ``w [E, K, N]`` is
    the stack of one layer, read at 0.  Tiles past ``n_tiles`` are left
    unwritten — and so are the rows past M of a result asked for at
    ``out_rows`` > M rows (a caller that has to hand on a longer array
    gets it without a copy)."""
    if (w.ndim == 4) != (layer is not None):
        raise ValueError(f"grouped_mm: w of rank {w.ndim} with"
                         f"{'out' if layer is None else ''} a layer")
    if layer is None:
        w, layer = w[None], jnp.zeros((1,), I32)
    M, K = x.shape
    N = w.shape[2] if trans_w else w.shape[3]
    tn = _cols(N)
    w_block = (tn, K) if trans_w else (K, tn)
    # the BOUND the call is launched at: every tile of M holding rows
    # (how many do is data).  x again for every column panel, every
    # expert's weights once (a panel at a time, as the optimizer holds
    # them: ONE layer's of the stack), the result once
    cost = pl.CostEstimate(
        flops=2 * M * K * N, transcendentals=0,
        bytes_accessed=(N // tn) * _common.nbytes((M, K), x.dtype)
        + _common.nbytes(w.shape[1:], w.dtype)
        + _common.nbytes((M, N), x.dtype))
    return pl.pallas_call(
        functools.partial(_mm_kernel, trans_w=trans_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, M // TILE_M),
            in_specs=[
                pl.BlockSpec((TILE_M, K),
                             lambda j, i, te, n, l: idx32(_held(i, n), 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (TILE_M, tn), lambda j, i, te, n, l: idx32(_held(i, n), j)),
            scratch_shapes=[pltpu.VMEM((2,) + w_block, w.dtype),
                            pltpu.VMEM(w_block, x.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((max(M, out_rows), N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_mm",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(tile_expert, n_tiles, layer, x, w)


def _dw_kernel(te_ref, n_ref, x_ref, dy_ref, dw_hbm, acc, sem):
    a, b, i = (pl.program_id(d).astype(I32) for d in range(3))
    panels = pl.num_programs(0) * pl.num_programs(1)
    tk, tn = acc.shape[1:]

    def leave(e, a, b, slot):
        rows = pl.ds(pl.multiple_of(a * I32(tk), tk), tk)
        cols = pl.ds(pl.multiple_of(b * I32(tn), tn), tn)
        return pltpu.make_async_copy(acc.at[slot], dw_hbm.at[e, rows, cols],
                                     sem.at[slot])

    @pl.when(i < n_ref[0])
    def _():
        starts, ends, e, first, last = _group(te_ref, n_ref, i)
        g = (a * pl.num_programs(1) + b) * (last - first + 1) + e - first
        slot = g % I32(2)
        # the block that was summed here two groups ago has had the whole
        # of the group between to leave
        @pl.when(starts & (g >= 2))
        def _():
            leave(e, a, b, slot).wait()
        # a group's first tile writes its product over whatever the slot
        # holds (nothing is zero-filled, nothing that is there is added)
        acc[slot] = jnp.where(starts, 0.0, acc[slot]) + jax.lax.dot_general(
            x_ref[:], dy_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ends)
        def _():
            leave(e, a, b, slot).start()

    # the call's end: the last two groups' blocks are still on their way
    @pl.when((a * pl.num_programs(1) + b == panels - 1)
             & (i == pl.num_programs(2) - 1) & (n_ref[0] > 0))
    def _():
        _, _, e, first, last = _group(te_ref, n_ref, n_ref[0] - 1)
        groups = panels * (last - first + 1)
        leave(e, a, b, (groups - 1) % I32(2)).wait()

        @pl.when(groups >= 2)
        def _():
            leave(e, a, b, groups % I32(2)).wait()


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
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tk, tn), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_mm_dw",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(tile_expert, n_tiles, x, dy)
