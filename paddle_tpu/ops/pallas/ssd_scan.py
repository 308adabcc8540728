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
there and writes them once the chunk's last tile is done.

Where x, B and C lie.  Either in three arrays, or — the convolution
before a Mamba-2 scan leaves them so — side by side in ONE, ``[b, s, H*P
+ 2N]`` (:func:`ssd_chunked_xbc`): x's lane tiles first, B and C a
``[Q, N]`` block each behind them, addressed there by the index maps (a
kernel is a custom call and takes whole arrays: a slice handed to it
would be written out first).  The backward then writes dx, dB and dC
into one array of that shape, the cotangent whole: its innermost axis
runs two steps past the lane tiles, the first stores dB, the second dC
(in either form).  Whole only if nothing else is owed to that array: so
the one-array form hands x's channels back beside y, for whoever reads
x again (the mixer's skip ``D * x``), and its backward takes their
cotangent in and adds it to dx as it stores — as XLA would have, two
bf16 numbers summed in fp32, but in no pass of its own.

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

__all__ = ["ssd_chunked", "ssd_chunked_xbc", "takes", "takes_xbc"]

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


def takes_xbc(xbc, heads: int, state: int) -> bool:
    """Whether the kernels read x, B and C out of ``xbc`` ``[b, c, Q,
    H*P + 2N]`` where they lie: what :func:`takes` asks of the three,
    and the state ONE lane tile, so that B and C are the two tiles
    behind x's (and dB, dC the backward's two last)."""
    d = xbc.shape[-1] - 2 * state
    if state != LANES or d <= 0 or d % heads:
        return False
    part = lambda *last: jax.ShapeDtypeStruct(xbc.shape[:3] + last,
                                              xbc.dtype)
    return takes(part(heads, d // heads), part(state))


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
                dtt_ref, entering_ref, *rest, p: int, tiles: int,
                one: bool):
    # dx, dB and dC go into three arrays; or into blocks of ONE, dx
    # joined by what x's other readers sent back
    if one:
        owed_ref, dx_ref, *rest = rest
        db_ref = dc_ref = dx_ref
    else:
        owed_ref, (dx_ref, db_ref, dc_ref, *rest) = None, rest
    dcumr_ref, ddtr_ref, dcumt_ref, ddtt_ref, \
        cb_ref, dcb_ref, dbx_ref, dcx_ref, dstate_ref = rest
    step, tile = pl.program_id(1), pl.program_id(2)     # step 0: last chunk
    q = x_ref.shape[0]
    per = LANES // p

    @pl.when(tile == 0)
    def _start_chunk():
        cb_ref[...] = _scores(b_ref, c_ref, q)
        for ref in (dcb_ref, dbx_ref, dcx_ref, dcumt_ref, ddtt_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(jnp.logical_and(step == 0, tile < tiles))
    def _start_row():
        dstate_ref[tile] = jnp.zeros(dstate_ref.shape[1:], F32)

    @pl.when(tile < tiles)
    def _heads():
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
            # within the chunk: dM[i, j] = dy_i . x_j over the head's
            # lanes (dyk holds no other)
            g = _dot(dyk, x, _NT) * h.decay
            dcb += g * h.dt_row
            v = g * cb                      # dM * scores * decay, masked
            ddtr_ref[k:k + 1, :] = cols(v)
            w = v * h.dt_row                # dM * M: what cum's ends see
            dcumr_ref[k:k + 1, :] = -cols(w)
            dcum = rows(w)
            m = (cb * h.decay * h.dt_row).astype(x.dtype)
            dx += _dot(m, dyk, _TN)         # M^T dy: the head's lanes only
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
        dx = dx.astype(dx_ref.dtype)
        if one:
            dx = (dx.astype(F32) + owed_ref[...].astype(F32)).astype(
                dx_ref.dtype)
        dx_ref[...] = dx
        dstate_ref[tile] = dentering
        dcb_ref[...], dbx_ref[...], dcx_ref[...] = dcb, dbx, dcx
        dcumt_ref[...], ddtt_ref[...] = dcumt, ddtt

    # the heads' sums are whole: dB on the step after them, dC on the next
    masked = lambda: jnp.where(_seen(q), dcb_ref[...], 0.0).astype(
        b_ref.dtype)

    @pl.when(tile == tiles)
    def _store_db():
        db_ref[...] = (_dot(masked(), c_ref[...], _TN)
                       + dbx_ref[...]).astype(db_ref.dtype)

    @pl.when(tile == tiles + 1)
    def _store_dc():
        dc_ref[...] = (_dot(masked(), b_ref[...], _NN)
                       + dcx_ref[...]).astype(dc_ref.dtype)


def _layouts(dt, cum, per):
    """``cum`` and ``dt`` ``[b, c, Q, H]`` in the kernels' two layouts."""
    b, nc, q, h = dt.shape
    rows = lambda a: jnp.swapaxes(a, 2, 3).reshape(b, nc, h // per, per, q)
    return rows(cum), rows(dt), cum, dt


def _specs(q, n, h, per, tiles, at, places=(0, 0)):
    """BlockSpecs on the (row, chunk step, lane tile) grid; ``at`` maps
    the chunk step to the chunk, ``places`` are B's and C's blocks of
    ``n`` channels in their arrays.  A step past the lane tiles (the
    backward's two) stays on the last tile's blocks: nothing moves."""
    head = lambda t: jnp.minimum(t, tiles - 1)
    tile = pl.BlockSpec((None, q, LANES),
                        lambda i, c, t: idx32(i, at(c), head(t)))
    groups = [pl.BlockSpec((None, q, n),
                           lambda i, c, t, g=g: idx32(i, at(c), g))
              for g in places]
    rows = pl.BlockSpec((None, None, None, per, q),
                        lambda i, c, t: idx32(i, at(c), head(t), 0, 0))
    cols = pl.BlockSpec((None, None, q, h),
                        lambda i, c, t: idx32(i, at(c), 0, 0))
    state = pl.BlockSpec((None, None, n, LANES),
                         lambda i, c, t: idx32(i, at(c), 0, head(t)))
    return tile, groups, rows, cols, state


# a row's chunks in turn (the state is carried), a chunk's tiles in turn
# (the scores are shared): only the rows are independent
_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _cost(b, nc, q, h, p, n, itemsize, backward: bool, owed: bool = False):
    """What a kernel EXECUTES on its (row, chunk, lane tile) grid.  A
    head's products run on its tile's 128 lanes, the other heads'
    zeroed: 128 wide, not ``p``.  Forward, a head: M x, (C decay) S and
    (B weight)^T x — 2 * 128 * Q * (Q + 2 N) — and four passes over its
    ``[Q, Q]`` matrix (cum_i - cum_j, its clamp, scores * decay * dt),
    one exp each entry; a chunk: C B^T.  Backward, a head: dy x^T, M^T
    dy, dy S^T, (C decay)^T dy, x dS^T and (B weight) dS — 2 * 128 * Q
    * (2 Q + 4 N) — and twelve passes; a chunk: C B^T and the two products
    that store dB and dC.  Bytes by the BlockSpecs: x (dy, the owed
    cotangent) and y (dx) a tile a grid step, B and C (dB, dC) a block a
    chunk, cum and dt (their gradients) in both layouts, fp32, the
    entering states ``[N, H P]`` fp32 a chunk."""
    heads, rows = b * nc * h, b * nc * q
    per_head = 2 * LANES * q * ((2 * q + 4 * n) if backward else (q + 2 * n)) \
        + (12 if backward else 4) * q * q
    per_chunk = 2 * q * q * n * (3 if backward else 1)
    tiles_moved = (4 if owed else 3) if backward else 2
    return pl.CostEstimate(
        flops=heads * per_head + b * nc * per_chunk,
        transcendentals=heads * (q * q + 2 * q),
        bytes_accessed=itemsize * rows * (tiles_moved * h * p
                                          + (4 if backward else 2) * n)
        + 4 * rows * h * (8 if backward else 4)
        + 4 * b * nc * n * h * p)


def _run_fwd(xa, ba, ca, places, dt, cum, p, n):
    """xa, ba, ca ``[b, s, .]``: the arrays x's lane tiles (from 0) and
    B's and C's blocks (at ``places``) lie in — three, or one three
    times.  -> y ``[b, s, H*P]``, the entering states."""
    b, nc, q, h = dt.shape
    per, tiles = LANES // p, h * p // LANES
    tile, groups, rows, cols, state = _specs(q, n, h, per, tiles,
                                             lambda c: c, places)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        out_shape=(jax.ShapeDtypeStruct((b, nc * q, h * p), xa.dtype),
                   jax.ShapeDtypeStruct((b, nc, n, h * p), F32)),
        grid=(b, nc, tiles),
        in_specs=[tile, *groups, rows, rows, cols, cols],
        out_specs=(tile, state),
        scratch_shapes=[pltpu.VMEM((q, q), F32),
                        pltpu.VMEM((tiles, n, LANES), F32)],
        compiler_params=_ORDER,
        name="ssd_scan_fwd",
        cost_estimate=_cost(b, nc, q, h, p, n, xa.dtype.itemsize,
                            backward=False),
        interpret=_common.interpret(),
    )(xa, ba, ca, *_layouts(dt, cum, per))


def _run_bwd(xa, ba, ca, places, dt, cum, entering, dy, p, n, owed=None):
    """-> (dx, dB, dC), d dt, d cum; with ``owed`` (the three are one
    array, and ``[b, s, H*P]`` is owed to its x) -> (the cotangent of
    that array,), d dt, d cum."""
    b, nc, q, h = dt.shape
    per, tiles = LANES // p, h * p // LANES
    at = lambda c: nc - 1 - c
    tile, groups, rows, cols, state = _specs(q, n, h, per, tiles, at,
                                             places)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    flat = lambda a: a.reshape(b, nc * q, h * p).astype(xa.dtype)
    if owed is None:
        more, into, into_specs = [], [xa, ba, ca], [tile, *groups]
    else:
        more, into, into_specs = [flat(owed)], [xa], [pl.BlockSpec(
            (None, q, LANES), lambda i, c, t: idx32(i, at(c), t))]
    cumr, dtr, cumt, dtt = _layouts(dt, cum, per)
    *into, dcumr, ddtr, dcumt, ddtt = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, tiles=tiles,
                          one=owed is not None),
        out_shape=(*map(like, into), like(cumr), like(dtr), like(cumt),
                   like(dtt)),
        grid=(b, nc, tiles + 2),
        in_specs=[tile, tile, *groups, rows, rows, cols, cols, state]
        + [tile] * len(more),
        out_specs=(*into_specs, rows, rows, cols, cols),
        scratch_shapes=[pltpu.VMEM((q, q), F32), pltpu.VMEM((q, q), F32),
                        pltpu.VMEM((q, n), F32), pltpu.VMEM((q, n), F32),
                        pltpu.VMEM((tiles, n, LANES), F32)],
        compiler_params=_ORDER,
        name="ssd_scan_bwd",
        cost_estimate=_cost(b, nc, q, h, p, n, xa.dtype.itemsize,
                            backward=True, owed=owed is not None),
        interpret=_common.interpret(),
    )(xa, flat(dy), ba, ca, cumr, dtr, cumt, dtt, entering, *more)
    cols_of = lambda a: jnp.swapaxes(a.reshape(b, nc, h, q), 2, 3)
    return into, cols_of(ddtr) + ddtt, cols_of(dcumr) + dcumt


@jax.custom_vjp
def ssd_chunked(x, dt, cum, B, C):
    """x ``[b, c, Q, H, P]``; dt, cum ``[b, c, Q, H]`` fp32; B, C ``[b,
    c, Q, N]`` -> y like x (:func:`takes` says which shapes)."""
    return _fwd(x, dt, cum, B, C)[0]


def _flat(a):
    return a.reshape(a.shape[0], a.shape[1] * a.shape[2], -1)


def _fwd(x, dt, cum, B, C):
    y, entering = _run_fwd(_flat(x), _flat(B), _flat(C), (0, 0), dt, cum,
                           x.shape[-1], B.shape[-1])
    return y.reshape(x.shape), (x, dt, cum, B, C, entering)


def _bwd(res, dy):
    x, dt, cum, B, C, entering = res
    (dx, db, dc), ddt, dcum = _run_bwd(
        _flat(x), _flat(B), _flat(C), (0, 0), dt, cum, entering, dy,
        x.shape[-1], B.shape[-1])
    return (dx.reshape(x.shape), ddt, dcum, db.reshape(B.shape),
            dc.reshape(C.shape))


ssd_chunked.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def ssd_chunked_xbc(xbc, dt, cum, state):
    """:func:`ssd_chunked` on x, B and C where one array holds them,
    xbc ``[b, s, H*P + 2N]``, the row not cut (dt and cum are, and say
    how) -> y and x, both ``[b, s, H*P]`` (:func:`takes_xbc` says which
    shapes).  x is xbc's first channels: read x from here, and what is
    owed to it reaches the backward kernel's one output instead of
    being padded and added to it."""
    return _fwd_xbc(xbc, dt, cum, state)[0]


def _in_xbc(xbc, dt, state):
    """B's and C's tiles behind x's, and the head width."""
    d = xbc.shape[-1] - 2 * state
    return (d // LANES, d // LANES + 1), d // dt.shape[-1]


def _fwd_xbc(xbc, dt, cum, state):
    places, p = _in_xbc(xbc, dt, state)
    y, entering = _run_fwd(xbc, xbc, xbc, places, dt, cum, p, state)
    return (y, xbc[..., :y.shape[-1]]), (xbc, dt, cum, entering)


def _bwd_xbc(state, res, cts):
    xbc, dt, cum, entering = res
    dy, owed = cts
    places, p = _in_xbc(xbc, dt, state)
    (dxbc,), ddt, dcum = _run_bwd(xbc, xbc, xbc, places, dt, cum, entering,
                                  dy, p, state, owed=owed)
    return dxbc, ddt, dcum


ssd_chunked_xbc.defvjp(_fwd_xbc, _bwd_xbc)
