"""Routed experts WITHOUT DROPPING, for the trainer: one device's share
of an expert layer.

The device holds ``held`` consecutive experts, ``first .. first + held
- 1``, of the ``published`` ones.  It routes every token over ALL the
published experts (:func:`route`: sigmoid scores, the top ``k``, gates
normalised over the ``k`` picks whether their experts are held here or
not — or, the second rule, the top ``k`` of the LOGITS and a softmax
over the picked — or, the third, the top ``k`` of the sigmoid scores
PLUS A BIAS that selects and gates nothing), keeps the (token, pick)
pairs whose expert it holds, and computes those experts' part of the
layer's result.  What the absent experts
would add is left out: on one device the layer runs without its
exchange, and nothing stands in for the other devices.

No capacity, no drop: :func:`plan` sorts the pairs by expert into a
buffer with each expert's group starting at a multiple of ``TILE_M``
rows and holding one tile at least, so that a row tile belongs to one
expert and every expert has one.  The group sizes are DATA.  The
plan's arrays have the shapes of ``rows_bound(T, k, held)`` rows —
every pair of every token; all the tokens on one held expert is a legal
load — but the PASSES run on the bound the load at hand asks for
(:func:`_at_the_load`): ``load_bound`` rows, ``LOAD_ROOM`` times the
pairs a uniform router ``published`` wide sends here, where the plan's
own tile count fits them, the bound of any load otherwise — one
``lax.cond`` on data in the forward and one, on the saved plan, in the
backward, the same code at two static sizes.  Nothing is dropped on
either; with every expert held the two are the same rows and no branch
is built.

Under a checkpoint boundary the routing is decided ONCE: :func:`route`
and :func:`plan` hand on the scores, the picks and the plan's arrays
under the names :data:`ROUTING_NAMES`, and a boundary whose policy saves
them (``models/llama_pretrain._remat_wrap`` for the kinds that route)
recomputes no router's product, no ``top_k`` and no sort — a few MB of
integers and, under the ``sigmoid`` rule, one fp32 ``[T, published]`` a
layer.  What the routed path itself keeps for its backward, the gate |
up product, is written once, where it is kept: the kernel writes the
load's rows into a result of the bound of any load, the one shape both
branches of the ``cond`` have to give.

Both ways through the buffer are gathers, forward and backward
(:func:`routed_ffn`): a row reads its pair's token; a token sums the
rows IT HAS — the rows are gathered into token order (the plan's slots:
the kept pairs sorted by pair, each with its row) and a kernel sums
each token's run of them in fp32 (``ops/pallas/moe_sum_pairs.py``) — no
scatter-add of activations either way, no ``k`` row reads for a token
that holds half a pair.  A tile that holds no rows costs an empty grid
step of the grouped products (``ops/pallas/grouped_mm.py``).

The experts' weights are read WHERE THE OPTIMIZER HOLDS THEM: fp32, a
panel at a time, cast in VMEM — and, for a layer of a loop over layers,
out of the kind's stacked leaves at the layer's index
(:func:`routed_ffn`'s ``stacked``): no pass is handed ``stack[layer]``,
so the program holds no copy of a layer's experts and no slice of a
stack, forward or backward; the weights' cotangents are a layer's, as
the loop's transpose wants them.

``distributed/parallel/expert_parallel.py`` is the other thing: GShard's
capacity API (one-hot ``[T, k, E, C]``, dropping), kept for Paddle's
``MoELayer`` surface.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .pallas.grouped_mm import TILE_M, grouped_mm, grouped_mm_dw
from .pallas.moe_sum_pairs import moe_sum_pairs

__all__ = ["Plan", "route", "plan", "rows_bound", "load_bound", "routed_ffn",
           "ROUTING_NAMES"]

I32 = jnp.int32

# The bound that follows the load holds this many times the pairs a
# uniform router sends to the held experts.  The count of kept pairs is
# a sum over tokens * k picks, each held with probability held /
# published: at the widths this module is built for its deviation is
# under a hundredth of its mean, and the tiles' padding has rows of its
# own (``held`` tiles, as in ``rows_bound``).  What the room is for is a
# router that has DRIFTED towards the held experts: twice their share
# is a router the balance rule has lost, and past it the full bound
# runs — nothing is dropped either way.
LOAD_ROOM = 2


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["row_pair", "tile_expert", "n_tiles", "slot_row",
                 "slot_token", "first_slot"],
    meta_fields=["k", "load_rows"])
@dataclasses.dataclass(frozen=True)
class Plan:
    """Where every kept pair lies in the sorted buffer of ``M`` rows,
    and the kept pairs in token order (the slots: ``M`` less the
    padding, every pair there can be)."""
    row_pair: jax.Array     # [M] the pair (token * k + pick) a row holds, -1: none
    tile_expert: jax.Array  # [M / TILE_M] the local expert of a row tile
    n_tiles: jax.Array      # [1] tiles in use: every expert has one at least
    slot_row: jax.Array     # [slots] the row of a slot's pair; 0 past the last pair
    slot_token: jax.Array   # [slots] the token of a slot's pair; -1 past the last
    first_slot: jax.Array   # [T + 1] a token's first slot; last: the pairs kept
    k: int                  # static: picks a token
    load_rows: int          # static: rows of the bound that follows the load


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of the sorted buffer that holds ANY load: every pair, and a
    tile's padding an expert."""
    return _padded(tokens * min(k, held), held)


def load_bound(tokens: int, k: int, held: int, published: int) -> int:
    """Rows of the bound that follows the load: ``LOAD_ROOM`` times the
    pairs expected of a router ``published`` wide, padded like
    ``rows_bound`` and never past it (``held == published``: the same)."""
    expected = -(-tokens * k * held // published)
    return min(_padded(LOAD_ROOM * expected, held),
               rows_bound(tokens, k, held))


def _padded(pairs: int, held: int) -> int:
    return -(-pairs // TILE_M) * TILE_M + held * TILE_M


RULES = ("sigmoid", "softmax_of_picks", "sigmoid_biased_picks")
ACTIVATIONS = ("silu", "relu")

# The names :func:`route` and :func:`plan` hand their results on under
# (``checkpoint_name``): the scores where the rule's derivative reads
# them (``sigmoid``), the picks with their scores, the plan's six arrays.
ROUTING_NAMES = ("moe_scores", "moe_picks", "moe_plan")


def route(x, w_router, k: int, scale: float, rule: str = "sigmoid",
          bias=None):
    """x [T, C], w_router [C, published] -> the picks ``idx [T, k]`` and
    their gates ``[T, k]`` (fp32), by one of three rules (static):

    ``sigmoid``: ``scale * s_e / (sum of the picked s + 1e-20)``, ``s =
    sigmoid(x . w_router)``, the top ``k`` of s.
    ``softmax_of_picks``: the top ``k`` of the LOGITS ``z = x .
    w_router``, ``scale * softmax(z over the k picked)`` — the gates sum
    to ``scale`` whichever experts are held here.
    ``sigmoid_biased_picks``: the top ``k`` of ``s + bias`` (``bias
    [published]``: it SELECTS, gates nothing and reads no gradient),
    ``scale * s_e / (sum of the picked s + 1e-6)`` with the scores
    themselves — a pick's gate may so be smaller than the gate of an
    expert the bias passed over.

    The product is fp32 either way — a pick is a comparison of scores,
    so the scores take no rounding they need not."""
    if rule not in RULES:
        raise ValueError(f"route: rule {rule!r}, one of {RULES}")
    if (bias is None) != (rule != "sigmoid_biased_picks"):
        raise ValueError(f"route: rule {rule!r} with"
                         f"{'out' if bias is None else ''} a bias")
    z = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    if rule == "softmax_of_picks":
        top, idx = _top_k(z, k)
        return idx, scale * jax.nn.softmax(top, axis=-1)
    # the sigmoid's derivative reads the scores, the product's does not
    s = jax.nn.sigmoid(checkpoint_name(z, ROUTING_NAMES[0]))
    if rule == "sigmoid":
        top, idx = _top_k(s, k)
        return idx, scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    # nothing flows back through a pick: the named picks take the scores
    # they gate by — as a masked sum over the row, not a gather (T * k
    # scalars out of [T, published] cost a v5e 0.5–1.6 ms a pass as a
    # gather, and its scatter again backward)
    _, idx = _named_top_k(jax.lax.stop_gradient(
        s + bias.astype(jnp.float32)), k)
    picked = idx[..., None] == jnp.arange(s.shape[-1], dtype=I32)
    top = jnp.sum(jnp.where(picked, s[:, None, :], 0.0), axis=-1)
    return idx, scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-6)


def _named_top_k(scores, k: int):
    top, idx = jax.lax.top_k(scores, k)
    return (checkpoint_name(top, ROUTING_NAMES[1]),
            checkpoint_name(idx.astype(I32), ROUTING_NAMES[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(scores, k: int):
    """``lax.top_k`` of scores ``[T, E]`` (the picks int32), handed on
    under their name — and a derivative that reads the NAMED picks:
    ``lax.top_k``'s own gathers by the primitive's raw result, which a
    policy of names does not keep, so the recompute would pick again
    (and form the scores again to pick from)."""
    return _named_top_k(scores, k)


def _top_k_fwd(scores, k):
    top, idx = _named_top_k(scores, k)
    # lax.top_k's own rule: a pick's tangent is its score's, gathered
    # along the row — here by the named picks, int32 as they are
    rows_apart = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    _, pull = jax.vjp(lambda s: jax.lax.gather(
        s, idx[..., None], rows_apart, (1, 1)), scores)
    return (top, idx), pull


def _top_k_bwd(k, pull, cts):
    return pull(cts[0])


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def plan(idx, first: int, held: int, published: int) -> Plan:
    """Sort the pairs of ``idx [T, k]`` whose expert is one of ``first
    .. first + held - 1`` (of a router ``published`` wide) by expert,
    groups padded to whole tiles, and list them in token order."""
    T, k = idx.shape
    M = rows_bound(T, k, held)
    local = idx - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).reshape(-1)
    pairs = jnp.arange(T * k, dtype=I32)
    # two sorts and one scatter of sorted rows: a gather or a scatter of
    # T * k scalars costs five sorts of them on a v5e
    skey, order = jax.lax.sort_key_val(key, pairs)      # stable: by expert
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=I32)[None, :],
                    axis=0, dtype=I32)
    # at least one tile an expert: the products' dw visits every expert
    tiles = jnp.maximum((sizes + (TILE_M - 1)) // TILE_M, 1)
    ends = jnp.cumsum(tiles, dtype=I32)
    start = jnp.cumsum(sizes, dtype=I32) - sizes        # in sorted order
    padded = (ends - tiles) * TILE_M                    # in the buffer
    e = jnp.minimum(skey, held - 1)
    kept = skey < held
    dest = jnp.where(kept, padded[e] + pairs - start[e], M)
    row_pair = jnp.full((M,), -1, I32).at[dest].set(
        order, mode="drop", indices_are_sorted=True, unique_indices=True)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(M // TILE_M, dtype=I32),
                         side="right"), held - 1).astype(I32)
    # the kept pairs by pair, which is by token, each with its row
    slot_pair, slot_row = jax.lax.sort_key_val(
        jnp.where(kept, order, T * k), jnp.where(kept, dest, 0))
    slot_token = jnp.where(slot_pair < T * k, slot_pair // k, -1)
    slots = M - held * TILE_M                           # the pairs' bound

    def fit(a, fill):
        return jnp.pad(a[:slots], (0, max(slots - T * k, 0)),
                       constant_values=fill)
    first_slot = jnp.concatenate(
        [jnp.zeros((1,), I32),
         jnp.cumsum(jnp.sum(is_held, axis=1, dtype=I32), dtype=I32)])
    return jax.tree_util.tree_map(
        lambda a: checkpoint_name(a, ROUTING_NAMES[2]),
        Plan(row_pair, tile_expert, ends[-1:], fit(slot_row, 0),
             fit(slot_token, -1), first_slot, k,
             load_bound(T, k, held, published)))


def _first_rows(p: Plan, m: int) -> Plan:
    """The plan of a buffer of ``m`` rows: a load whose tiles fit ``m``
    rows lies in the plan's first rows, tiles and slots."""
    return dataclasses.replace(
        p, row_pair=p.row_pair[:m], tile_expert=p.tile_expert[:m // TILE_M],
        slot_row=p.slot_row[:m], slot_token=p.slot_token[:m])


def _rows_of_pairs(buf, p: Plan):
    """[T, C]: the sum of a token's kept pairs' rows of ``buf [M, C]``,
    summed in fp32, in buf's dtype.  XLA gathers the rows into token
    order (the slots: the bound's pairs, not ``T * k`` row reads; a slot
    past the last pair reads a row that is there, and belongs to no
    token); the kernel sums each token's run of them."""
    return moe_sum_pairs(buf[p.slot_row], p.slot_token, p.first_slot)


def _tokens_of_rows(x, p: Plan):
    """[M, C]: the token of each row's pair (token 0 where it holds
    none: finite, and never used)."""
    return x[jnp.maximum(p.row_pair, 0) // p.k]


def _gate_of_rows(gate, p: Plan):
    return jnp.where(p.row_pair >= 0,
                     gate.reshape(-1)[jnp.maximum(p.row_pair, 0)], 0)


def _at_the_load(fn, p: Plan, *args):
    """``fn(plan, *args)`` on a buffer as long as the load at hand asks:
    the plan's first ``load_rows`` rows where its tiles fit them — the
    plan's own ``n_tiles``, data —, all of them otherwise.  One program
    at two values of one static size; each carries a scope of its own,
    OUTSIDE the ``moe_*`` scopes its ops are charged to, so a trace
    shows which ran."""
    full = p.row_pair.shape[0]

    def at(m, scope):
        def run(p, *args):
            with jax.named_scope(scope):
                with jax.named_scope("moe_dispatch"):
                    p = _first_rows(p, m)
                return fn(p, *args)
        return run
    if p.load_rows == full:
        return at(full, "moe_bound_all")(p, *args)
    return jax.lax.cond(p.n_tiles[0] * TILE_M <= p.load_rows,
                        at(p.load_rows, "moe_bound_load"),
                        at(full, "moe_bound_all"), p, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def routed_ffn(x, gate, w_gate_up, w_down, p: Plan, act: str = "silu",
               stacked=None):
    """The held experts' part of the layer: x [T, C], gate [T, k] (fp32)
    and the stacks ``[held, C, 2 F]`` (gate | up, one product) and
    ``[held, F, C]`` as the optimizer holds them (the kernels cast a
    panel in VMEM) -> y [T, C] = sum over a token's kept pairs of gate *
    E_e(x),  E(x) = (act(x w_g) * x w_u) w_d, ``act`` (static) ``silu``
    or ``relu``.

    The gate rides on the F-wide hidden rows, before the down product.
    One backward for the whole path: it keeps x, the [M, 2 F] product
    and the plan, gathers the rows again and forms the hidden rows again
    — no [M, C] buffer outlives the pass that made it.  Every pass runs
    on the bound the load asks for (:func:`_at_the_load`); what is kept
    has the full bound's shapes, filled in its first rows — the product
    by the kernel that forms it (``grouped_mm(..., out_rows=)``: no pad,
    the rows past the load's are never written and never read).

    The products READ the experts out of ``stacked = (gate | up [L,
    held, C, 2 F], down [L, held, F, C], layer)`` — ``layer`` an int32
    scalar, data — where they lie (``grouped_mm``'s ``layer``); None:
    the two arrays themselves, stacks of one layer read at 0.  A layer
    of a loop over layers hands over the kind's whole stacks — constants
    of the loop, under ``stop_gradient`` — and as ``w_gate_up`` /
    ``w_down`` the loop's own slices ``stack[layer]``, whose VALUES no
    pass reads and no backward holds, so XLA writes none of them out (a
    kernel handed ``stack[layer]`` is handed a copy of it, once a pass).
    The weights' COTANGENTS go to ``w_gate_up`` / ``w_down`` either way,
    a layer's ``[held, C, 2 F]`` / ``[held, F, C]``, which the loop's
    transpose stacks; ``stacked`` gets none (a loop constant WITH one is
    padded to the whole stack and added up every iteration)."""
    return _at_the_load(lambda *a: _forward(act, *a)[0], p, x, gate,
                        *_read(w_gate_up, w_down, stacked))


def _read(w_gate_up, w_down, stacked):
    """What the products read, as ``grouped_mm`` takes it: the layer's
    two arrays and no index, or the two stacks and the layer."""
    if stacked is None:
        return w_gate_up, w_down, None
    gate_up, down, layer = stacked
    if gate_up.shape[1:] != w_gate_up.shape or down.shape[1:] != w_down.shape:
        raise ValueError(
            f"routed_ffn: stacks {gate_up.shape}, {down.shape} of layers "
            f"{w_gate_up.shape}, {w_down.shape}")
    return gate_up, down, layer.astype(I32).reshape(1)


def _activation(act: str):
    if act not in ACTIVATIONS:
        raise ValueError(f"routed_ffn: act {act!r}, one of {ACTIVATIONS}")
    return jax.nn.silu if act == "silu" else jax.nn.relu


def _hidden_and_its_gradient(act: str, g, u):
    """``a(g) * u`` (fp32) and the function from its cotangent to (d_g,
    d_u): silu's ``g sig(g)`` with ``sig (1 + g (1 - sig))``, or relu's
    ``max(g, 0)`` with its step."""
    if act == "silu":
        sig = jax.nn.sigmoid(g)
        return g * sig * u, lambda d: [
            d * u * sig * (1 + g * (1 - sig)), d * g * sig]
    a = _activation(act)(g)
    return a * u, lambda d: [jnp.where(g > 0, d * u, 0), d * a]


def _forward(act, p, x, gate, w_gate_up, w_down, layer, kept_rows: int = 0):
    """y and the gate | up product; ``layer``: whose experts of stacked
    ones (None: the two arrays are one layer's); ``kept_rows`` (static):
    the rows of the array the product is handed on in, its own where
    fewer."""
    te, n, f = p.tile_expert, p.n_tiles, w_down.shape[-2]
    m = p.row_pair.shape[0]
    with jax.named_scope("moe_dispatch"):
        rows = _tokens_of_rows(x, p)
    with jax.named_scope("moe_experts"):
        gu = grouped_mm(rows, w_gate_up, te, n, out_rows=kept_rows,
                        layer=layer)
        h = (_activation(act)(gu[:m, :f].astype(jnp.float32)) * gu[:m, f:]
             * _gate_of_rows(gate, p)[:, None]).astype(x.dtype)
        out = grouped_mm(h, w_down, te, n, layer=layer)
    with jax.named_scope("moe_combine"):
        y = _rows_of_pairs(out, p).astype(x.dtype)
    return y, gu


def _routed_fwd(x, gate, w_gate_up, w_down, p, act, stacked=None):
    # one shape from both branches, the bound of any load's: the load's
    # bound writes its rows of the product into an array that long
    kept = functools.partial(_forward, act, kept_rows=p.row_pair.shape[0])
    read = _read(w_gate_up, w_down, stacked)
    y, gu = _at_the_load(kept, p, x, gate, *read)
    return y, (x, gate, read, p, gu)


def _routed_bwd(act, res, dy):
    x, gate, read, p, gu = res
    d = _at_the_load(functools.partial(_backward, act), p, x, gate, *read,
                     gu, dy)
    return d + (None, None)


def _backward(act, p, x, gate, w_gate_up, w_down, layer, gu, dy):
    te, n, f32 = p.tile_expert, p.n_tiles, jnp.float32
    held, f = w_down.shape[-3:-1]
    with jax.named_scope("moe_combine"):
        # a row without a pair reads token 0's dy: finite, and every
        # product it enters has the row's gate, which is zero
        d_out = _tokens_of_rows(dy, p)
    with jax.named_scope("moe_experts"):
        gu = gu[:p.row_pair.shape[0]]
        g, u = gu[:, :f].astype(f32), gu[:, f:].astype(f32)
        g_row = _gate_of_rows(gate, p)[:, None]
        hid, d_gu_of = _hidden_and_its_gradient(act, g, u)
        d_h = grouped_mm(d_out, w_down, te, n, trans_w=True,
                         layer=layer).astype(f32)
        d_wd = grouped_mm_dw((hid * g_row).astype(x.dtype), d_out, te, n,
                             held)
        d_gate_row = jnp.sum(d_h * hid, axis=1)
        d_gu = jnp.concatenate(d_gu_of(d_h * g_row), axis=1).astype(x.dtype)
    with jax.named_scope("moe_dispatch"):
        rows = _tokens_of_rows(x, p)
    with jax.named_scope("moe_experts"):
        d_wgu = grouped_mm_dw(rows, d_gu, te, n, held)
        d_rows = grouped_mm(d_gu, w_gate_up, te, n, trans_w=True,
                            layer=layer)
    with jax.named_scope("moe_dispatch"):
        dx = _rows_of_pairs(d_rows, p).astype(x.dtype)
        # the rows hand their pairs the gates' gradient: M writes, not
        # T * k reads
        d_gate = jnp.zeros(gate.size, gate.dtype).at[
            jnp.where(p.row_pair >= 0, p.row_pair, gate.size)].set(
            d_gate_row.astype(gate.dtype), mode="drop",
            unique_indices=True).reshape(gate.shape)
    return (dx, d_gate, d_wgu.astype(w_gate_up.dtype),
            d_wd.astype(w_down.dtype))


routed_ffn.defvjp(_routed_fwd, _routed_bwd)
