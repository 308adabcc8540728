"""Pallas kernels for the chunked Kimi-Delta-Attention recurrence
(``ops/kda.py`` has the equations and :func:`~paddle_tpu.ops.kda.chunk_step`,
one head's chunk, which both kernels run on tiles in VMEM).

``kda_chunk_fwd`` walks a row's chunks in order with every head's state
``[K, V]`` (fp32) in VMEM scratch; ``kda_chunk_bwd`` walks them backwards
with the state's gradient there.  The ``[Q, Q]`` pair matrices, the
inverse and the ``[Q, K]`` decays are formed in VMEM and dropped: none
reaches HBM, forward or backward.

A grid step is one head's BLOCK of ``BLOCK_CHUNKS`` chunks (256
positions at Q 64).  What the backward needs of the forward is the state
that ENTERED each block, ``[b, blocks, H, K, V]`` fp32, which the forward
writes as it goes — at one state a block and not one a chunk it is 268 MB
a layer for a row of 16,384 and 64 heads, not 1.07 GB; the backward runs
its block's chunks forward again from that state (it forms every matrix
again anyway) and pulls the cotangents back through them: the derivative
is jax's own of ``chunk_step``, taken inside the kernel's body.

The forward's two results carry ``jax.ad_checkpoint.checkpoint_name``s,
:data:`FWD_OUTPUT_NAMES`: o (the out gate's input) and ``entering`` (the
backward kernel's) are all that a later pass reads of the call, so a
checkpoint boundary whose policy keeps BOTH has no ``kda_chunk_fwd`` in
its recompute; keeping one alone buys nothing, the other still needs the
run.  :func:`kept_bytes` says what the two take, from shapes alone
(``models/hybrid_trunk.kept_outputs`` decides by it).

Layout.  q, k and v are read where a convolution leaves them, side by
side in ONE array ``[b, s, 3 H K]`` with ``K = V = 128``: a head's q, k
and v are the lane tiles ``h``, ``H + h`` and ``2 H + h``, which the
index maps address; o is ``[b, s, H V]``.  The backward writes dq | dk |
dv into one array of qkv's shape, the cotangent whole: its innermost grid
axis has three steps, the first computes and stores dq (dk and dv wait in
scratch), the other two store dk and dv.  g ``[b, s, H K]`` fp32 is the
log decay (its running sum inside a chunk is ``chunk_step``'s own: no
pass of XLA's over a decay-sized array); beta ``[b, s, H]`` fp32 comes
as a ``[rows, H]`` block a
block of chunks, a head's column taken by a masked sum over the lanes;
d beta goes back the same way, summed over the heads in the block the
head axis revisits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kda
from . import _common
from ._common import idx32

__all__ = ["kda_chunked", "takes", "block_rows", "kept_bytes",
           "BLOCK_CHUNKS", "FWD_OUTPUT_NAMES"]

F32 = jnp.float32
LANES = 128
BLOCK_CHUNKS = 4
# a block's tiles, the state scratch (4 MB at 64 heads) and, in the
# backward, what autodiff keeps of a block's four chunks
VMEM_LIMIT = 64 << 20
# ``kda_chunk_fwd``'s two results, o and the states that entered each
# block, under ``checkpoint_name`` (the module docstring says why both)
FWD_OUTPUT_NAMES = ("kda_out", "kda_entering")


def takes(qkv, heads: int, chunk: int) -> bool:
    """Whether the kernels take qkv ``[b, s, 3 H K]``: heads of ONE lane
    tile (K = V = 128), chunks of whole sublane tiles."""
    return (qkv.shape[-1] == 3 * heads * LANES and chunk % 16 == 0
            and qkv.dtype in (jnp.bfloat16, jnp.float32))


def block_rows(s: int, chunk: int) -> int:
    """The positions of a grid step's block: ``BLOCK_CHUNKS`` chunks, or
    all of a shorter row's."""
    return min(BLOCK_CHUNKS, -(-s // chunk)) * chunk


def kept_bytes(b: int, s: int, heads: int, dtype, chunk: int) -> int:
    """The bytes of ``kda_chunk_fwd``'s two results for ``b`` rows of
    ``s`` positions (padded to whole blocks, as ``ops/kda.kda_chunk``
    pads them): o ``[b, s', H V]`` in ``dtype`` and the entering states
    ``[b, s' / block_rows, H, K, V]`` fp32."""
    rows = block_rows(s, chunk)
    blocks = -(-s // rows)
    o = b * blocks * rows * heads * LANES * jnp.dtype(dtype).itemsize
    return o + b * blocks * heads * LANES * LANES * 4


def _column(table, head):
    """Column ``head`` of ``table`` ``[rows, H]`` as ``[rows, 1]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
    return jnp.sum(jnp.where(lane == head, table, 0.0), axis=1,
                   keepdims=True)


def _chunks(rows: int, chunk: int):
    return [slice(i, i + chunk) for i in range(0, rows, chunk)]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, entering_ref,
                state_ref, *, chunk: int, dt):
    blk, head = pl.program_id(1), pl.program_id(2)

    @pl.when(blk == 0)
    def _start_row():
        state_ref[head] = jnp.zeros(state_ref.shape[1:], F32)

    state = state_ref[head]
    entering_ref[...] = state
    beta = _column(beta_ref[...], head)
    for at in _chunks(q_ref.shape[0], chunk):
        o, state = kda.chunk_step(q_ref[at, :], k_ref[at, :], v_ref[at, :],
                                  g_ref[at, :], beta[at], state, dt)
        o_ref[at, :] = o.astype(o_ref.dtype)
    state_ref[head] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, entering_ref, do_ref,
                dqkv_ref, dg_ref, dbeta_ref, dstate_ref, dkv_ref, *,
                chunk: int, dt):
    step, head, part = (pl.program_id(i) for i in (1, 2, 3))

    def block(chunks, state):
        outs = []
        for args in chunks:
            o, state = kda.chunk_step(*args, state, dt)
            outs.append(o)
        return tuple(outs), state

    @pl.when(part == 0)
    def _pull_back():
        @pl.when(step == 0)                 # step 0: the row's last block
        def _start_row():
            dstate_ref[head] = jnp.zeros(dstate_ref.shape[1:], F32)

        @pl.when(head == 0)
        def _start_block():
            dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

        cuts = _chunks(q_ref.shape[0], chunk)
        beta = _column(beta_ref[...], head)
        _, pull = jax.vjp(
            block, tuple((q_ref[at, :], k_ref[at, :], v_ref[at, :],
                          g_ref[at, :], beta[at]) for at in cuts),
            entering_ref[...])
        grads, dstate = pull((tuple(do_ref[at, :].astype(F32)
                                    for at in cuts), dstate_ref[head]))
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (chunk, dbeta_ref.shape[1]), 1)
        for at, (dq, dk, dv, dg, dbeta) in zip(cuts, grads):
            dqkv_ref[at, :] = dq.astype(dqkv_ref.dtype)
            dkv_ref[0, at, :] = dk.astype(dkv_ref.dtype)
            dkv_ref[1, at, :] = dv.astype(dkv_ref.dtype)
            dg_ref[at, :] = dg
            dbeta_ref[at, :] += jnp.where(lane == head, dbeta, 0.0)
        dstate_ref[head] = dstate

    for i in (1, 2):
        @pl.when(part == i)
        def _store(i=i):
            dqkv_ref[...] = dkv_ref[i - 1]


def cost(b: int, blocks: int, rows: int, heads: int, chunk: int,
         itemsize: int, backward: bool) -> pl.CostEstimate:
    """What a kernel EXECUTES on its (row, block, head) grid, K = V = 128.
    A head's chunk, forward (``chunk_step``): the anchors' pair products
    (two ``[Q, K] x [K, Q]`` a later sub-block), the running sum of g (a
    triangle of ones times ``[Q, K]``), the channel-by-channel
    pairs inside a sub-block (``SUB`` rounds of five passes over ``[Q,
    K]`` for k and three more for q), the inverse (the sub-blocks by
    ``SUB - 1`` eliminations on the VPU, their spread to ``[Q, Q]`` and
    four ``[Q, Q]^2`` products at four sub-blocks), T applied to ``[Q,
    2 K]``, the four products
    with state-sized operands, and a dozen passes over ``[Q, K]`` around
    them; one exp an entry of every decay formed: the sub-blocks' ``SUB
    Q K``, two an anchor, Gamma and the decay to the chunk's end.  The
    backward runs that forward again and pulls back through it: every
    product twice more, every pass twice more, no further exp.  Bytes by
    the BlockSpecs: q, k, v in and o out (forward) or q, k, v, do in and
    dq, dk, dv out (backward) a tile each, g (and d g) fp32, beta a
    ``[rows, H]`` block a block of chunks (d beta out too), a state ``[K,
    V]`` fp32 a head a block."""
    q, k = chunk, LANES
    sub = kda.SUB if q % kda.SUB == 0 else q
    later = q // sub - 1
    squares = 2 + 2 * (later.bit_length() - 1) if later else 0
    products = 2 * q * q * k * (2 * later + 3) \
        + 2 * q * q * (q * squares + sub) + 2 * q * k * k * 3 + 2 * q * q * k
    passes = q * k * (8 * sub + 12) + 6 * q * sub * (sub - 1)
    exps = q * k * (sub + 2 * later + 2) + k
    chunks = b * blocks * heads * (rows // chunk)
    tiles = (7 if backward else 4) * itemsize + (8 if backward else 4)
    return pl.CostEstimate(
        flops=chunks * (products + passes) * (3 if backward else 1),
        transcendentals=chunks * exps,
        bytes_accessed=b * blocks * rows * heads * k * tiles
        + b * blocks * rows * heads * 4 * (2 if backward else 1)
        + b * blocks * heads * k * k * 4)


def _specs(rows: int, heads: int, at):
    """BlockSpecs on the (row, block step, head[, part]) grid; ``at`` maps
    the block step to the block."""
    tile = lambda group: pl.BlockSpec(
        (None, rows, LANES),
        lambda i, c, h, *_: idx32(i, at(c), group * heads + h))
    beta = pl.BlockSpec((None, rows, heads),
                        lambda i, c, h, *_: idx32(i, at(c), 0))
    state = pl.BlockSpec((None, None, None, LANES, LANES),
                         lambda i, c, h, *_: idx32(i, at(c), h, 0, 0))
    return tile, beta, state


def _run_fwd(qkv, g, beta, chunk):
    b, s, _ = qkv.shape
    heads = beta.shape[-1]
    rows = block_rows(s, chunk)
    blocks = s // rows
    tile, beta_spec, state = _specs(rows, heads, lambda c: c)
    o, entering = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, dt=qkv.dtype),
        out_shape=(jax.ShapeDtypeStruct((b, s, heads * LANES), qkv.dtype),
                   jax.ShapeDtypeStruct((b, blocks, heads, LANES, LANES),
                                        F32)),
        grid=(b, blocks, heads),
        in_specs=[tile(0), tile(1), tile(2), tile(0), beta_spec],
        out_specs=(tile(0), state),
        scratch_shapes=[pltpu.VMEM((heads, LANES, LANES), F32)],
        # a row's blocks in turn (the state is carried); only the rows
        # are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="kda_chunk_fwd",
        cost_estimate=cost(b, blocks, rows, heads, chunk,
                           qkv.dtype.itemsize, backward=False),
        interpret=_common.interpret(),
    )(qkv, qkv, qkv, g, beta)
    return (checkpoint_name(o, FWD_OUTPUT_NAMES[0]),
            checkpoint_name(entering, FWD_OUTPUT_NAMES[1]))


def _run_bwd(qkv, g, beta, entering, do, chunk):
    b, s, _ = qkv.shape
    heads = beta.shape[-1]
    rows = block_rows(s, chunk)
    blocks = s // rows
    at = lambda c: blocks - 1 - c
    tile, beta_spec, state = _specs(rows, heads, at)
    into = pl.BlockSpec(
        (None, rows, LANES),
        lambda i, c, h, part: idx32(i, at(c), part * heads + h))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, dt=qkv.dtype),
        out_shape=(like(qkv), like(g), like(beta)),
        grid=(b, blocks, heads, 3),
        in_specs=[tile(0), tile(1), tile(2), tile(0), beta_spec, state,
                  tile(0)],
        out_specs=(into, tile(0), beta_spec),
        scratch_shapes=[pltpu.VMEM((heads, LANES, LANES), F32),
                        pltpu.VMEM((2, rows, LANES), qkv.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="kda_chunk_bwd",
        cost_estimate=cost(b, blocks, rows, heads, chunk,
                           qkv.dtype.itemsize, backward=True),
        interpret=_common.interpret(),
    )(qkv, qkv, qkv, g, beta, entering, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def kda_chunked(qkv, g, beta, chunk: int):
    """qkv ``[b, s, 3 H K]`` (q | k | v raw), g ``[b, s, H K]`` fp32 (the
    log decay), beta ``[b, s, H]`` fp32, s a whole number of blocks (:func:`block_rows`) -> o ``[b,
    s, H K]`` in qkv's dtype (:func:`takes` says which shapes)."""
    return _run_fwd(qkv, g, beta, chunk)[0]


def _fwd(qkv, g, beta, chunk):
    o, entering = _run_fwd(qkv, g, beta, chunk)
    return o, (qkv, g, beta, entering)


def _bwd(chunk, res, do):
    qkv, g, beta, entering = res
    return _run_bwd(qkv, g, beta, entering, do.astype(qkv.dtype), chunk)


kda_chunked.defvjp(_fwd, _bwd)
