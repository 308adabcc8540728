"""Pallas kernels for the chunked selective scan (``ops/ssd_scan.py``):
for a row cut into chunks of ``Q`` positions, a head's state ``S`` (``[N,
P]``, fp32) and ``cum`` the running sum of ``A * dt`` inside a chunk,

    y_i   = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
            + exp(cum_i) (C_i . S_entering)
    S_end = exp(cum_end) S_entering + sum_j exp(cum_end - cum_j) dt_j B_j (x) x_j

``ssd_scan_fwd`` walks a row's chunks in order with the state of every
head in VMEM scratch; ``ssd_scan_bwd`` walks them backwards with the
state's gradient there.  The ``[Q, Q]`` matrices of a head — the decay
``exp(cum_i - cum_j)`` and its product with the shared scores ``C B^T``
— are formed in VMEM, used by the MXU and dropped: none reaches HBM,
forward or backward.  What the backward needs of the forward is the
state that ENTERED each chunk (``[b, chunks, N, H*P]`` fp32, 1/Q of a
``[Q, Q]`` matrix a head), which the forward writes as it goes.

Layout.  x, y and their gradients are addressed where the projections
leave them, ``[b, s, H*P]``: a grid step takes the 128 lanes of ``128 //
P`` heads of one chunk, and the state lies the same way, ``[N, H*P]``.
Those heads share the tile but not the matrices, so each head's products
run on the tile with the other heads' lanes zeroed — at head width 64
that costs what a 64-wide product costs on a 128-wide MXU.  The grid is
(row, chunk, lane tile) with the lane tile innermost: the scores ``C
B^T`` (one B/C group, shared by every head) are formed once a chunk into
scratch, and the backward sums the gradients of B and C over the heads
there and writes them on the chunk's last tile.

``cum`` and ``dt`` are needed along both axes of a matrix.  XLA hands
them over in both layouts (``[.., heads of the tile, Q]`` rows and ``[..,
Q, H]`` columns; a few MB): a head's column is taken from the latter by
a masked sum over its lanes, so the kernels never transpose, and their
gradients go back the same two ways.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import idx32

__all__ = ["ssd_chunked", "takes"]

F32 = jnp.float32
LANES = 128


def takes(x, B) -> bool:
    """Whether the kernels take these chunked shapes (x ``[b, c, Q, H,
    P]``, B ``[b, c, Q, N]``): whole heads to a 128-lane tile, whole
    tiles to the heads, the chunk a whole number of lane tiles."""
    q, h, p = x.shape[2:]
    return (p <= LANES and LANES % p == 0 and (h * p) % LANES == 0
            and q % LANES == 0 and B.shape[-1] % 8 == 0
            and x.dtype == B.dtype)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=F32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _seen(q):
    """Lower triangle, diagonal included: position j is behind i."""
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    return i >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _column(table, head):
    """Column ``head`` of ``table`` ``[Q, H]`` as ``[Q, 1]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
    return jnp.sum(jnp.where(lane == head, table, 0.0), axis=1,
                   keepdims=True)


def _head_lanes(tile, k, p):
    """``tile`` ``[rows, 128]`` with the lanes of all but the tile's
    k-th head zeroed."""
    if p == LANES:
        return tile
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    mine = jnp.logical_and(lane >= k * p, lane < (k + 1) * p)
    return jnp.where(mine, tile, jnp.zeros_like(tile))


class _Head:
    """What a head's products are made of, from the two layouts."""

    def __init__(self, k, head, cumr_ref, dtr_ref, cumt, dtt):
        self.cum_row = cumr_ref[k:k + 1, :]                 # [1, Q]
        self.dt_row = dtr_ref[k:k + 1, :]
        self.cum_col = _column(cumt, head)                  # [Q, 1]
        self.dt_col = _column(dtt, head)
        # cum only falls along a chunk (A < 0 <= dt): its end is its least
        self.end = jnp.min(self.cum_row, axis=1, keepdims=True)   # [1, 1]
        # exp(cum_i - cum_j); above the diagonal, where the difference
        # is positive and the entry is masked by its user, 1
        self.decay = jnp.exp(jnp.minimum(self.cum_col - self.cum_row, 0.0))
        self.from_start = jnp.exp(self.cum_col)             # [Q, 1]
        self.to_end = jnp.exp(self.end - self.cum_col)      # [Q, 1]
        self.keep = jnp.exp(self.end)                       # [1, 1]


def _scores(b_ref, c_ref, q):
    return jnp.where(_seen(q), _dot(c_ref[...], b_ref[...], _NT), 0.0)


def _fwd_kernel(x_ref, b_ref, c_ref, cumr_ref, dtr_ref, cumt_ref, dtt_ref,
                y_ref, entering_ref, cb_ref, state_ref, *, p: int):
    chunk, tile = pl.program_id(1), pl.program_id(2)
    q = x_ref.shape[0]
    per = LANES // p

    @pl.when(tile == 0)
    def _start_chunk():
        cb_ref[...] = _scores(b_ref, c_ref, q)

    @pl.when(chunk == 0)
    def _start_row():
        state_ref[tile] = jnp.zeros(state_ref.shape[1:], F32)

    x, cb = x_ref[...], cb_ref[...]
    b, c = b_ref[...].astype(F32), c_ref[...].astype(F32)
    cumt, dtt = cumt_ref[...], dtt_ref[...]
    entering = state_ref[tile]                              # [N, 128]
    entering_ref[...] = entering
    y = jnp.zeros((q, LANES), F32)
    leaving = jnp.zeros_like(entering)
    for k in range(per):
        h = _Head(k, tile * per + k, cumr_ref, dtr_ref, cumt, dtt)
        xk, sk = _head_lanes(x, k, p), _head_lanes(entering, k, p)
        m = (cb * h.decay * h.dt_row).astype(x.dtype)
        y += _dot(m, xk, _NN)
        y += _dot((c * h.from_start).astype(x.dtype), sk.astype(x.dtype),
                  _NN)
        own = _dot((b * (h.to_end * h.dt_col)).astype(x.dtype), xk, _TN)
        leaving += h.keep * sk + own
    y_ref[...] = y.astype(y_ref.dtype)
    state_ref[tile] = leaving


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, cumr_ref, dtr_ref, cumt_ref,
                dtt_ref, entering_ref,
                dx_ref, dcumr_ref, ddtr_ref, dcumt_ref, ddtt_ref, db_ref,
                dc_ref,
                cb_ref, dcb_ref, dbx_ref, dcx_ref, dstate_ref, *, p: int):
    step, tile = pl.program_id(1), pl.program_id(2)     # step 0: last chunk
    q = x_ref.shape[0]
    per = LANES // p

    @pl.when(tile == 0)
    def _start_chunk():
        cb_ref[...] = _scores(b_ref, c_ref, q)
        for ref in (dcb_ref, dbx_ref, dcx_ref, dcumt_ref, ddtt_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(step == 0)
    def _start_row():
        dstate_ref[tile] = jnp.zeros(dstate_ref.shape[1:], F32)

    x, dy, cb = x_ref[...], dy_ref[...], cb_ref[...]
    b, c = b_ref[...].astype(F32), c_ref[...].astype(F32)
    cumt, dtt = cumt_ref[...], dtt_ref[...]
    entering, dleaving = entering_ref[...], dstate_ref[tile]
    lane = jax.lax.broadcasted_iota(jnp.int32, cumt.shape, 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    rows = lambda a: jnp.sum(a, axis=1, keepdims=True)      # [Q, 1]
    cols = lambda a: jnp.sum(a, axis=0, keepdims=True)      # [1, Q]
    dx = jnp.zeros((q, LANES), F32)
    dentering = jnp.zeros_like(entering)
    dcb, dbx, dcx = dcb_ref[...], dbx_ref[...], dcx_ref[...]
    dcumt, ddtt = dcumt_ref[...], ddtt_ref[...]
    for k in range(per):
        head = tile * per + k
        h = _Head(k, head, cumr_ref, dtr_ref, cumt, dtt)
        dyk = _head_lanes(dy, k, p)
        sk = _head_lanes(entering, k, p)
        dsk = _head_lanes(dleaving, k, p)
        # within the chunk: dM[i, j] = dy_i . x_j over the head's lanes
        # (dyk holds no other)
        g = _dot(dyk, x, _NT) * h.decay
        dcb += g * h.dt_row
        v = g * cb                          # dM * scores * decay, masked
        ddtr_ref[k:k + 1, :] = cols(v)
        w = v * h.dt_row                    # dM * M: what cum's ends see
        dcumr_ref[k:k + 1, :] = -cols(w)
        dcum = rows(w)
        m = (cb * h.decay * h.dt_row).astype(x.dtype)
        dx += _dot(m, dyk, _TN)             # M^T dy: the head's lanes only
        # what the entering state added: y_i += exp(cum_i) C_i . S
        t1 = _dot(dyk, sk.astype(x.dtype), _NT)             # [Q, N]
        dcx += h.from_start * t1
        dcum += rows(t1 * c) * h.from_start
        dsk_in = _dot((c * h.from_start).astype(x.dtype), dyk, _TN)
        # the chunk's own end state: sum_j to_end_j dt_j B_j (x) x_j
        weight = h.to_end * h.dt_col                        # [Q, 1]
        dsk16 = dsk.astype(x.dtype)
        t2 = _dot(x, dsk16, _NT)                            # [Q, N]
        dbx += weight * t2
        dweight = rows(t2 * b)
        dx += _dot((b * weight).astype(x.dtype), dsk16, _NN)
        ddt = dweight * h.to_end
        dcum -= dweight * weight
        # cum_end: in every to_end, and in what the state keeps
        dend = jnp.sum(dweight * weight, axis=0, keepdims=True) + \
            h.keep * jnp.sum(rows(sk * dsk), axis=0, keepdims=True)
        dcum += jnp.where(last, dend, 0.0)
        dentering += dsk_in + h.keep * dsk
        dcumt += jnp.where(lane == head, dcum, 0.0)
        ddtt += jnp.where(lane == head, ddt, 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dstate_ref[tile] = dentering
    dcb_ref[...], dbx_ref[...], dcx_ref[...] = dcb, dbx, dcx
    dcumt_ref[...], ddtt_ref[...] = dcumt, ddtt

    @pl.when(tile == pl.num_programs(2) - 1)
    def _finish_chunk():
        ds = jnp.where(_seen(q), dcb, 0.0).astype(b_ref.dtype)
        dc_ref[...] = (_dot(ds, b_ref[...], _NN) + dcx).astype(dc_ref.dtype)
        db_ref[...] = (_dot(ds, c_ref[...], _TN) + dbx).astype(db_ref.dtype)


def _views(x, dt, cum, B, C):
    """The kernels' operands from the chunked arrays."""
    b, nc, q, h, p = x.shape
    per = LANES // p
    rows = lambda a: jnp.swapaxes(a, 2, 3).reshape(b, nc, h // per, per, q)
    flat = lambda a: a.reshape(b, nc * q, -1)
    return flat(x), flat(B), flat(C), rows(cum), rows(dt), cum, dt


def _specs(q, n, h, per, at):
    """BlockSpecs on the (row, chunk step, lane tile) grid; ``at`` maps
    the chunk step to the chunk."""
    tile = pl.BlockSpec((None, q, LANES),
                        lambda i, c, t: idx32(i, at(c), t))
    group = pl.BlockSpec((None, q, n), lambda i, c, t: idx32(i, at(c), 0))
    rows = pl.BlockSpec((None, None, None, per, q),
                        lambda i, c, t: idx32(i, at(c), t, 0, 0))
    cols = pl.BlockSpec((None, None, q, h),
                        lambda i, c, t: idx32(i, at(c), 0, 0))
    state = pl.BlockSpec((None, None, n, LANES),
                         lambda i, c, t: idx32(i, at(c), 0, t))
    return tile, group, rows, cols, state


# a row's chunks in turn (the state is carried), a chunk's tiles in turn
# (the scores are shared): only the rows are independent
_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@jax.custom_vjp
def ssd_chunked(x, dt, cum, B, C):
    """x ``[b, c, Q, H, P]``; dt, cum ``[b, c, Q, H]`` fp32; B, C ``[b,
    c, Q, N]`` -> y like x (:func:`takes` says which shapes)."""
    return _fwd(x, dt, cum, B, C)[0]


def _fwd(x, dt, cum, B, C):
    b, nc, q, h, p = x.shape
    n, per, tiles = B.shape[-1], LANES // p, h * p // LANES
    xf, bf, cf, cumr, dtr, cumt, dtt = _views(x, dt, cum, B, C)
    tile, group, rows, cols, state = _specs(q, n, h, per, lambda c: c)
    y, entering = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        out_shape=(jax.ShapeDtypeStruct(xf.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, n, h * p), F32)),
        grid=(b, nc, tiles),
        in_specs=[tile, group, group, rows, rows, cols, cols],
        out_specs=(tile, state),
        scratch_shapes=[pltpu.VMEM((q, q), F32),
                        pltpu.VMEM((tiles, n, LANES), F32)],
        compiler_params=_ORDER,
        name="ssd_scan_fwd",
        interpret=_common.interpret(),
    )(xf, bf, cf, cumr, dtr, cumt, dtt)
    return y.reshape(x.shape), (x, dt, cum, B, C, entering)


def _bwd(res, dy):
    x, dt, cum, B, C, entering = res
    b, nc, q, h, p = x.shape
    n, per, tiles = B.shape[-1], LANES // p, h * p // LANES
    xf, bf, cf, cumr, dtr, cumt, dtt = _views(x, dt, cum, B, C)
    tile, group, rows, cols, state = _specs(q, n, h, per,
                                            lambda c: nc - 1 - c)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    dx, dcumr, ddtr, dcumt, ddtt, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        out_shape=(like(xf), like(cumr), like(dtr), like(cumt), like(dtt),
                   like(bf), like(cf)),
        grid=(b, nc, tiles),
        in_specs=[tile, tile, group, group, rows, rows, cols, cols, state],
        out_specs=(tile, rows, rows, cols, cols, group, group),
        scratch_shapes=[pltpu.VMEM((q, q), F32), pltpu.VMEM((q, q), F32),
                        pltpu.VMEM((q, n), F32), pltpu.VMEM((q, n), F32),
                        pltpu.VMEM((tiles, n, LANES), F32)],
        compiler_params=_ORDER,
        name="ssd_scan_bwd",
        interpret=_common.interpret(),
    )(xf, dy.reshape(xf.shape).astype(x.dtype), bf, cf, cumr, dtr, cumt,
      dtt, entering)
    cols_of = lambda a: jnp.swapaxes(a.reshape(b, nc, h, q), 2, 3)
    return (dx.reshape(x.shape), cols_of(ddtr) + ddtt,
            cols_of(dcumr) + dcumt, db.reshape(B.shape),
            dc.reshape(C.shape))


ssd_chunked.defvjp(_fwd, _bwd)
