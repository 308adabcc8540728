"""Routed experts WITHOUT DROPPING, for the trainer: one device's share
of an expert layer.

The device holds ``held`` consecutive experts, ``first .. first + held
- 1``, of the ``published`` ones.  It routes every token over ALL the
published experts (:func:`route`: sigmoid scores, the top ``k``, gates
normalised over the ``k`` picks whether their experts are held here or
not), keeps the (token, pick) pairs whose expert it holds, and computes
those experts' part of the layer's result.  What the absent experts
would add is left out: on one device the layer runs without its
exchange, and nothing stands in for the other devices.

No capacity, no drop: :func:`plan` sorts the pairs by expert into a
buffer of ``rows_bound(T, k, held)`` rows — every pair of every token,
the static bound; all the tokens on one held expert is a legal load —
with each expert's group starting at a multiple of ``TILE_M`` rows and
holding one tile at least, so that a row tile belongs to one expert and
every expert has one.  The group sizes are DATA; a
tile that holds no rows costs an empty grid step of the grouped
products (``ops/pallas/grouped_mm.py``).  Both ways through the buffer
are gathers, forward and backward (:func:`routed_ffn`): a row reads its
pair's token, a token sums its pairs' rows — no scatter-add of
activations either way.

``distributed/parallel/expert_parallel.py`` is the other thing: GShard's
capacity API (one-hot ``[T, k, E, C]``, dropping), kept for Paddle's
``MoELayer`` surface.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pallas.grouped_mm import TILE_M, grouped_mm, grouped_mm_dw

__all__ = ["Plan", "route", "plan", "rows_bound", "routed_ffn"]

I32 = jnp.int32


class Plan(NamedTuple):
    """Where every kept pair lies in the sorted buffer of ``M`` rows."""
    row_pair: jax.Array     # [M] the pair (token * k + pick) a row holds, -1: none
    pos: jax.Array          # [T, k] the row of a pair; 0 where its expert is not held
    held: jax.Array         # [T, k] whether the pair's expert is held here
    tile_expert: jax.Array  # [M / TILE_M] the local expert of a row tile
    n_tiles: jax.Array      # [1] tiles in use: every expert has one at least


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of the sorted buffer: every pair, and a tile's padding an
    expert."""
    return -(-tokens * min(k, held) // TILE_M) * TILE_M + held * TILE_M


def route(x, w_router, k: int, scale: float):
    """x [T, C], w_router [C, published] -> the picks ``idx [T, k]`` and
    their gates ``[T, k]`` (fp32): ``scale * s_e / (sum of the picked s
    + 1e-20)``, ``s = sigmoid(x . w_router)`` in fp32 — a pick is a
    comparison of scores, so the scores take no rounding they need not."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    top, idx = jax.lax.top_k(s, k)
    gate = scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return idx.astype(I32), gate


def plan(idx, first: int, held: int) -> Plan:
    """Sort the pairs of ``idx [T, k]`` whose expert is one of ``first
    .. first + held - 1`` by expert, groups padded to whole tiles."""
    T, k = idx.shape
    M = rows_bound(T, k, held)
    local = idx - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(I32)
    skey = key[order]
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=I32)[None, :],
                    axis=0, dtype=I32)
    # at least one tile an expert: the products' dw visits every expert
    tiles = jnp.maximum((sizes + (TILE_M - 1)) // TILE_M, 1)
    ends = jnp.cumsum(tiles, dtype=I32)
    start = jnp.cumsum(sizes, dtype=I32) - sizes        # in sorted order
    padded = (ends - tiles) * TILE_M                    # in the buffer
    e = jnp.minimum(skey, held - 1)
    kept = skey < held
    dest = jnp.where(kept, padded[e] + jnp.arange(T * k, dtype=I32)
                     - start[e], M)
    row_pair = jnp.full((M,), -1, I32).at[dest].set(order, mode="drop")
    pos = jnp.zeros((T * k,), I32).at[order].set(jnp.where(kept, dest, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(M // TILE_M, dtype=I32),
                         side="right"), held - 1).astype(I32)
    return Plan(row_pair, pos.reshape(T, k), is_held, tile_expert,
                ends[-1:])


def _rows_of_pairs(buf, p: Plan):
    """[T, C]: the sum of a token's kept pairs' rows of ``buf [M, C]``,
    in fp32 (row 0 is read for a pair whose expert is not held, and
    masked)."""
    picked = jnp.where(p.held[..., None], buf[p.pos], 0)
    return jnp.sum(picked.astype(jnp.float32), axis=1)


def _tokens_of_rows(x, p: Plan):
    """[M, C]: the token of each row's pair (token 0 where it holds
    none: finite, and never used)."""
    return x[jnp.maximum(p.row_pair, 0) // p.pos.shape[1]]


@jax.custom_vjp
def routed_ffn(x, gate, w_gate_up, w_down, p: Plan):
    """The held experts' part of the layer: x [T, C], gate [T, k] (fp32)
    and the stacks ``[held, C, 2 F]`` (gate | up, one product) and
    ``[held, F, C]`` as the optimizer holds them (the kernels cast a
    panel in VMEM) -> y [T, C] = sum over a token's kept pairs of gate *
    E_e(x),  E(x) = (silu(x w_g) * x w_u) w_d.

    The gate rides on the F-wide hidden rows, before the down product.
    One backward for the whole path: it keeps x, the [M, 2 F] product
    and the plan, gathers the rows again and forms the hidden rows again
    — no [M, C] buffer outlives the pass that made it."""
    return _routed_fwd(x, gate, w_gate_up, w_down, p)[0]


def _gate_of_rows(gate, p: Plan):
    return jnp.where(p.row_pair >= 0,
                     gate.reshape(-1)[jnp.maximum(p.row_pair, 0)], 0)


def _routed_fwd(x, gate, w_gate_up, w_down, p):
    te, n, f = p.tile_expert, p.n_tiles, w_down.shape[1]
    with jax.named_scope("moe_dispatch"):
        rows = _tokens_of_rows(x, p)
    with jax.named_scope("moe_experts"):
        gu = grouped_mm(rows, w_gate_up, te, n)
        h = (jax.nn.silu(gu[:, :f].astype(jnp.float32)) * gu[:, f:]
             * _gate_of_rows(gate, p)[:, None]).astype(x.dtype)
        out = grouped_mm(h, w_down, te, n)
    with jax.named_scope("moe_combine"):
        y = _rows_of_pairs(out, p).astype(x.dtype)
    return y, (x, gate, w_gate_up, w_down, p, gu)


def _routed_bwd(res, dy):
    x, gate, w_gate_up, w_down, p, gu = res
    te, n, f32 = p.tile_expert, p.n_tiles, jnp.float32
    f, held = w_down.shape[1], w_down.shape[0]
    with jax.named_scope("moe_combine"):
        # a row without a pair takes no gradient: its products are zero
        d_out = jnp.where((p.row_pair >= 0)[:, None],
                          _tokens_of_rows(dy, p), 0)
    with jax.named_scope("moe_experts"):
        g, u = gu[:, :f].astype(f32), gu[:, f:].astype(f32)
        g_row = _gate_of_rows(gate, p)[:, None]
        sig = jax.nn.sigmoid(g)
        act = g * sig * u
        d_h = grouped_mm(d_out, w_down, te, n, trans_w=True).astype(f32)
        d_wd = grouped_mm_dw((act * g_row).astype(x.dtype), d_out, te, n,
                             held)
        d_gate_row = jnp.sum(d_h * act, axis=1)
        d_act = d_h * g_row
        d_gu = jnp.concatenate(
            [d_act * u * sig * (1 + g * (1 - sig)), d_act * g * sig],
            axis=1).astype(x.dtype)
    with jax.named_scope("moe_dispatch"):
        rows = _tokens_of_rows(x, p)
    with jax.named_scope("moe_experts"):
        d_wgu = grouped_mm_dw(rows, d_gu, te, n, held)
        d_rows = grouped_mm(d_gu, w_gate_up, te, n, trans_w=True)
    with jax.named_scope("moe_dispatch"):
        dx = _rows_of_pairs(d_rows, p).astype(x.dtype)
        d_gate = jnp.where(p.held, d_gate_row[p.pos], 0).astype(gate.dtype)
    return (dx, d_gate, d_wgu.astype(w_gate_up.dtype),
            d_wd.astype(w_down.dtype), None)


routed_ffn.defvjp(_routed_fwd, _routed_bwd)
