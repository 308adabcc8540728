"""Pallas TPU kernel for the token side of the routed experts: a token
sums the rows of the pairs it has.

The rows arrive in TOKEN ORDER, as ``ops/moe.py`` lists the kept pairs
(its slots): ``[P, C]``, a token's rows next to each other, ``P`` the
static bound of the pairs, the slots past the last pair holding
anything finite.  ``TOKENS`` tokens so own ONE RUN of slots, ``lo ..
hi`` — data, scalar prefetch — and a grid step copies the ``CHUNK``-row
pieces its run touches (two buffers: the next piece flies while this
one is summed) and nothing else: the work follows the pairs there are,
not ``P`` and not ``tokens * k``.  A piece is added to the step's fp32
accumulator as a 0/1 product on the MXU, ``S[t, s] = (slot s is token
t's)``: every product is the row's own value, the sum is fp32, and the
one rounding is the store.

Why the rows are not fetched one by one from the experts' buffer: a
bf16 row of an ``[M, C]`` array shares its 32-bit words with its
neighbour, Mosaic copies whole 8-row tiles of such an array, and the
``[M, 2, C / 2]`` view that makes a row its own tile is a relayout of
the buffer with vector registers an eighth full (timed, and not kept:
PERF.md section 6, PR 34).  XLA's gather puts the rows in order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["TOKENS", "CHUNK", "moe_sum_pairs"]

I32 = jnp.int32
# tokens a grid step, slots a copy: the accumulator [TOKENS, C] fp32, two
# [CHUNK, C] pieces and the output block twice are 11 MiB at C 3584
TOKENS = CHUNK = 256
_VMEM_LIMIT = 48 << 20


def _kernel(lo_ref, tok_ref, rows_hbm, o_ref, acc, piece, sem):
    i = pl.program_id(0).astype(I32)
    lo, hi = lo_ref[i], lo_ref[i + 1]
    first = lo // I32(CHUNK)
    n = jnp.where(hi > lo, (hi + I32(CHUNK - 1)) // I32(CHUNK) - first,
                  I32(0))

    def copy(c, slot):
        at = pl.multiple_of((first + c) * I32(CHUNK), CHUNK)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(at, CHUNK)],
                                     piece.at[slot], sem.at[slot])

    acc[:] = jnp.zeros_like(acc)

    @pl.when(n > 0)
    def _():
        copy(I32(0), I32(0)).start()

    def add(c, _):
        slot = c % I32(2)

        @pl.when(c + 1 < n)
        def _():
            copy(c + 1, I32(1) - slot).start()
        copy(c, slot).wait()
        token = tok_ref[pl.ds(first + c, 1), :]             # [1, CHUNK]
        mine = token == i * I32(TOKENS) + jax.lax.broadcasted_iota(
            I32, (TOKENS, CHUNK), 0)
        acc[:] += jnp.dot(
            mine.astype(jnp.float32).astype(piece.dtype), piece[slot],
            preferred_element_type=jnp.float32,
            # fp32 rows (the tests'): no bf16 pass may round them
            precision=jax.lax.Precision.HIGHEST
            if piece.dtype == jnp.float32 else None)
        return I32(0)
    jax.lax.fori_loop(I32(0), n, add, I32(0))
    o_ref[:] = acc[:].astype(o_ref.dtype)


def moe_sum_pairs(rows, slot_token, first_slot):
    """rows [P, C] in token order, slot_token [P] (the token of a slot,
    -1 where it holds no pair), first_slot [T + 1] (a token's first
    slot; the last entry: the pairs there are) -> [T, C] in rows'
    dtype: the fp32 sum of each token's rows, zeros where it has none."""
    P, C = rows.shape
    T = first_slot.shape[0] - 1
    assert P % CHUNK == 0, (P, CHUNK)
    steps = -(-T // TOKENS)
    # a step's run of slots starts where its first token's does
    lo = jnp.concatenate(
        [first_slot[:T:TOKENS], first_slot[T:]]).astype(I32)
    # the bound: every CHUNK of P copied once and one more a grid step
    # (a run starts inside a piece), each added as a [TOKENS, CHUNK] 0/1
    # product; the slots' tokens once, the sums out once
    pieces = P // CHUNK + steps
    cost = pl.CostEstimate(
        flops=2 * pieces * TOKENS * CHUNK * C, transcendentals=0,
        bytes_accessed=pieces * _common.nbytes((CHUNK, C), rows.dtype)
        + 4 * P + _common.nbytes((steps * TOKENS, C), rows.dtype))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[
                pl.BlockSpec((P // CHUNK, CHUNK), lambda i, lo: idx32(0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TOKENS, C), lambda i, lo: idx32(i, 0)),
            scratch_shapes=[pltpu.VMEM((TOKENS, C), jnp.float32),
                            pltpu.VMEM((2, CHUNK, C), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((steps * TOKENS, C), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_sum_pairs",
        cost_estimate=cost,
        interpret=_common.interpret(),
    )(lo, slot_token.astype(I32).reshape(P // CHUNK, CHUNK), rows)
    return out[:T]
