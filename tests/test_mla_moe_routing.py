"""The routed experts of the expert cell at toy size: the four shares
add up to the uncut layer, nothing is dropped at any load on either of
the routed path's two bounds, the two bounds are bit for bit one
program, and the token side sums the rows a token has.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _mla_moe_toy import toy  # noqa: F401
from _toy_cell import SOUND
from benchmark import reference
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_mm import TILE_M


# -- the share ---------------------------------------------------------------
def _layer_weights(key, c, f, experts):
    ks = jax.random.split(key, 6)
    n = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) \
        / fan ** 0.5
    return {"w_router": n(ks[0], (c, experts), c),
            "we_gate_up": n(ks[1], (experts, c, 2 * f), c),
            "we_down": n(ks[2], (experts, f, c), f),
            "ws_gate": n(ks[3], (c, f), c), "ws_up": n(ks[4], (c, f), c),
            "ws_down": n(ks[5], (f, c), f)}


def _share(w, first, held):
    return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                we_down=w["we_down"][first:first + held])


def _program_layer(toy, w, x, first, held):
    cfg = dataclasses.replace(toy.cfg, expert_first=first,
                              experts_held=held)
    return hybrid_trunk._expert_layer(w, x, cfg)


def test_the_shares_add_up_to_the_whole_layer(toy):
    """The four shares' routed parts, with the shared expert counted
    once, are what the UNCUT reference gives for the whole layer."""
    from benchmark.models import xing_mhc_moe_reference as blk
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    w = _layer_weights(jax.random.PRNGKey(3), c, f, 8)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 300, c), jnp.float32)
    whole = dict(blk.dims_of(dict(toy.conf, n_routed_experts=8,
                                  expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    want = blk._experts(x, w, whole, mm)
    shared = blk._swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], mm)
    parts = [_program_layer(toy, _share(w, first, 2), x, first, 2) - shared
             for first in (0, 2, 4, 6)]
    got = sum(parts) + shared
    assert float(jnp.max(jnp.abs(got - want))) \
        < SOUND * float(jnp.max(jnp.abs(want)))
    # and a share alone is the reference's share
    one = blk._experts(x, _share(w, 2, 2), dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] + shared - one))) \
        < SOUND * float(jnp.max(jnp.abs(one)))


def _routed_case(T, c, f, held, k, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, c), jnp.float32)
    wgu = jax.random.normal(ks[1], (held, c, 2 * f), jnp.float32) / c ** .5
    wd = jax.random.normal(ks[2], (held, f, c), jnp.float32) / f ** .5
    gate = jax.random.uniform(ks[3], (T, k), jnp.float32, 0.1, 1.0)
    co = jax.random.normal(ks[4], (T, c), jnp.float32)
    return x, gate, wgu, wd, co


def _value_and_grads(x, gate, wgu, wd, co, p):
    y, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p), x, gate, wgu, wd)
    return (y,) + vjp(co)


def _filling(T, first, held, published, rows):
    """Picks [T, 2] whose kept pairs fill ``rows`` rows of the buffer to
    the last one: every held expert but the last takes one pair (one
    tile), the last ``rows - (held - 1) * TILE_M`` tokens' first picks;
    every other pick goes to an expert not held."""
    away = first + held if first + held < published else 0
    idx = np.full((T, 2), away, np.int32)
    n = rows - (held - 1) * TILE_M
    idx[:n, 0] = first + held - 1
    for e in range(held - 1):
        idx[n + e, 1] = first + e
    return jnp.asarray(idx)


# T 2048, top-2, 2 of 16 held: 512 pairs expected, the bound that
# follows the load 2 * 512 + 2 * 256 = 1536 rows, the bound of any load
# 2 * 2048 + 512 = 4608
LOADS = {
    "all_on_one_held_expert": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), first + 1), jnp.full((T,), 0)], 1),
    "none_held": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), 0), jnp.full((T,), first + held)], 1),
    "every_pick_held": lambda T, first, held, pub: jnp.stack(
        [first + jnp.arange(T) % held,
         first + (jnp.arange(T) + 1) % held], 1),
    "balanced": lambda T, first, held, pub: jnp.stack(
        [jnp.arange(T) % pub, (jnp.arange(T) // pub + 1
                               + jnp.arange(T)) % pub], 1),
    "the_load_bound_to_its_last_row": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub)),
    "one_tile_under_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) - TILE_M),
    "one_tile_over_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) + TILE_M),
}
ON_THE_LOAD_BOUND = {"none_held": True, "balanced": True,
                     "the_load_bound_to_its_last_row": True,
                     "one_tile_under_the_load_bound": True,
                     "one_tile_over_the_load_bound": False,
                     "all_on_one_held_expert": False,
                     "every_pick_held": False}


def _branches(fn, *args) -> list:
    """How many ``cond`` choose a bound in ``fn``'s jaxpr (kernel bodies
    not walked), and whether each bound's scope is on an op path."""
    conds, scopes = 0, set()

    def walk(jaxpr, outer=""):
        nonlocal conds
        for eqn in jaxpr.eqns:
            path = f"{outer}/{eqn.source_info.name_stack}"
            scopes.update(w for w in ("moe_bound_load", "moe_bound_all")
                          if w in path)
            if eqn.primitive.name == "pallas_call":
                continue
            conds += eqn.primitive.name == "cond"
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, path)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return [conds, "moe_bound_load" in scopes, "moe_bound_all" in scopes]


@pytest.mark.parametrize("load", sorted(LOADS))
def test_nothing_is_dropped_at_any_load(load):
    """Every (token, pick) pair whose expert is held has a row of its
    own, whatever the load — under the bound that follows the load, at
    its last row and past it; the result is the plain masked sum, and so
    are the four gradients."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    kept = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(jnp.sum(p.row_pair >= 0)) == kept            # no drop
    assert p.row_pair.shape[0] == moe.rows_bound(T, k, held) \
        >= T * k + held * TILE_M
    assert p.load_rows == moe.load_bound(T, k, held, pub) == 1536
    pair_of = np.asarray(p.row_pair)
    assert len(set(pair_of[pair_of >= 0].tolist())) == kept  # one row a pair
    assert int(p.n_tiles[0]) >= held                        # a tile an expert
    # which bound this load runs on: the plan's own tile count says
    assert (int(p.n_tiles[0]) * TILE_M <= p.load_rows) \
        == ON_THE_LOAD_BOUND[load]
    if load.startswith("the_load_bound"):
        assert int(p.n_tiles[0]) * TILE_M == p.load_rows
        assert int(p.row_pair[p.load_rows - 1]) >= 0        # its last row
    # the kept pairs in token order, each with its row
    held_pairs = np.flatnonzero(np.asarray(
        (idx >= first) & (idx < first + held)).reshape(-1))
    assert (np.asarray(p.slot_token)[:kept] == held_pairs // k).all() \
        and (np.asarray(p.slot_token)[kept:] == -1).all()
    assert (pair_of[np.asarray(p.slot_row)[:kept]] == held_pairs).all()
    assert int(p.first_slot[-1]) == kept

    def plain(x, gate, wgu, wd):
        y = jnp.zeros_like(x)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == e + first, gate, 0.0), -1)
            h = jax.nn.silu(x @ wgu[e][:, :f]) * (x @ wgu[e][:, f:])
            y = y + mine[:, None] * (h @ wd[e])
        return y
    # value and the four gradients, one program a form
    both = lambda fn: jax.jit(lambda *a: (lambda y, vjp: (y,) + vjp(co))(
        *jax.vjp(fn, *a)))(x, gate, wgu, wd)
    for a, b in zip(both(lambda *a: moe.routed_ffn(*a, p)), both(plain)):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


@pytest.mark.parametrize("load", ["balanced", "none_held",
                                  "one_tile_under_the_load_bound"])
def test_the_two_bounds_are_one_program(load):
    """A load that fits both bounds: the branch on the plan's first rows
    and the branch on all of them give the same value and the same four
    gradients BIT FOR BIT, and the program holds both behind one
    ``cond`` a pass."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k, seed=11)
    # the cell's dtypes: bf16 rows, fp32 gates and stacks (in fp32 the
    # CPU's elementwise loops round a last bit by the array's length)
    x, co = x.astype(jnp.bfloat16), co.astype(jnp.bfloat16)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    assert int(p.n_tiles[0]) * TILE_M <= p.load_rows < p.row_pair.shape[0]
    everything = dataclasses.replace(p, load_rows=p.row_pair.shape[0])

    both = functools.partial(_value_and_grads, x, gate, wgu, wd, co)
    for a, b in zip(both(p), both(everything)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    assert _branches(both, p) == [2, True, True]       # forward, backward
    assert _branches(both, everything) == [0, False, True]


def test_every_expert_held_builds_one_bound():
    """``held == published``: twice the expected pairs is more than
    there can be, the two bounds are the same rows and no branch is
    built."""
    T, c, f, held, k = 512, 128, 128, 3, 2
    assert moe.load_bound(T, k, held, held) == moe.rows_bound(T, k, held)
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k)
    idx = jnp.stack([jnp.arange(T) % held, (jnp.arange(T) + 1) % held],
                    1).astype(jnp.int32)
    p = moe.plan(idx, 0, held, held)
    assert p.load_rows == p.row_pair.shape[0]

    both = functools.partial(_value_and_grads, x, gate, wgu, wd, co)
    assert _branches(both, p) == [0, False, True]


def test_load_bound_is_twice_the_expected_pairs_and_never_past_any_load():
    # the expert cell: 16,384 tokens, top-4, 8 of 64
    assert moe.rows_bound(16384, 4, 8) == 67584
    assert moe.load_bound(16384, 4, 8, 64) == 16384 + 8 * TILE_M == 18432
    assert moe.load_bound(16384, 4, 32, 64) == moe.rows_bound(16384, 4, 32)
    assert moe.load_bound(100, 4, 2, 64) == TILE_M + 2 * TILE_M
    for held in (1, 2, 8, 64):
        assert moe.load_bound(4096, 4, held, 64) % TILE_M == 0
        assert moe.load_bound(4096, 4, held, 64) \
            <= moe.rows_bound(4096, 4, held)


def _parent_rows_of_pairs(buf, held, pos):
    """PR 33's token side: ``k`` row reads a token (row 0 for a pair
    whose expert is not held), masked, summed."""
    picked = jnp.where(held[..., None], buf[pos], 0)
    return jnp.sum(picked.astype(jnp.float32), axis=1)


@pytest.mark.parametrize("cut", [False, True])
def test_a_token_sums_the_rows_it_has(cut):
    """The token side against the parent's ``k`` gathers, on a plan
    whose tokens hold 0, 1, 2, 3 and 4 pairs, in fp32 to one ulp — on
    the whole plan and on its first rows."""
    T, c, held, k, pub, first = 640, 128, 4, 4, 16, 2
    rng = np.random.default_rng(5)
    idx = np.empty((T, k), np.int32)
    away = [e for e in range(pub) if not first <= e < first + held]
    for t in range(T):
        n = t % 5                                   # pairs this token holds
        idx[t] = rng.permutation(np.concatenate(
            [rng.choice(np.arange(first, first + held), n, replace=False),
             rng.choice(away, k - n, replace=False)]))
    p = moe.plan(jnp.asarray(idx), first, held, pub)
    is_held = (idx >= first) & (idx < first + held)
    pairs = is_held.sum(1)
    assert sorted(set(pairs.tolist())) == [0, 1, 2, 3, 4]
    # the row of each pair, as PR 33's plan held it
    pair_of = np.asarray(p.row_pair)
    pos = np.zeros(T * k, np.int32)
    pos[pair_of[pair_of >= 0]] = np.flatnonzero(pair_of >= 0)
    pos = pos.reshape(T, k)
    if cut:
        assert int(p.n_tiles[0]) * TILE_M <= p.load_rows < p.row_pair.shape[0]
        p = moe._first_rows(p, p.load_rows)
    buf = jax.random.normal(jax.random.PRNGKey(2),
                            (p.row_pair.shape[0], c), jnp.float32) * 3
    got = np.asarray(moe._rows_of_pairs(buf, p))
    want = np.asarray(_parent_rows_of_pairs(buf, is_held, pos))
    assert got.dtype == np.float32
    # one ulp of what is summed: three and four terms add up in the
    # product's order, not the parent's; up to two are the same sum
    ulp = np.spacing(np.asarray(
        _parent_rows_of_pairs(jnp.abs(buf), is_held, pos)))
    assert (np.abs(got - want) <= ulp).all()
    assert (got[pairs <= 2] == want[pairs <= 2]).all()
    assert (pairs == 0).any() and not got[pairs == 0].any()
