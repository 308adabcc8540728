"""The routed ReGLU experts of the window cell at toy size, and what its
configuration must state: the four shares add up to the uncut layer, the
second routing rule is a softmax over the picked logits, nothing is
dropped at skewed loads under the ReLU gate, ``check`` names what it
refuses.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _smallthinker_toy import toy  # noqa: F401
from _toy_cell import SOUND
from benchmark import reference
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, build_mesh, make_train_step)
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_mm import TILE_M

# -- the share ---------------------------------------------------------------
def _layer_weights(key, c, f, experts):
    ks = jax.random.split(key, 3)
    n = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) \
        / fan ** 0.5
    return {"w_router": n(ks[0], (c, experts), c),
            "we_gate_up": n(ks[1], (experts, c, 2 * f), c),
            "we_down": n(ks[2], (experts, f, c), f)}


def _share(w, first, held):
    return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                we_down=w["we_down"][first:first + held])


def test_the_shares_add_up_to_the_whole_layer(toy):
    """The four shares' parts are what the UNCUT reference gives for the
    whole layer: nothing is computed on every chip alike (no shared
    expert), so nothing is counted once.  The router reads another
    tensor than the experts."""
    from benchmark.models import smallthinker_moe_reference as blk
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    w = _layer_weights(jax.random.PRNGKey(3), c, f, 8)
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 300, c), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 300, c), jnp.float32)
    whole = dict(blk.dims_of(dict(toy.conf, moe_num_primary_experts=8,
                                  expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    idx, g = blk._route(y, w, whole, mm)
    assert float(jnp.max(jnp.abs(jnp.sum(g, -1) - 1))) < 1e-6
    want = blk._experts(u, idx, g, w, whole, mm)

    def part(first):
        cfg = dataclasses.replace(toy.cfg, expert_first=first,
                                  experts_held=2)
        bp = _share(w, first, 2)
        return hybrid_trunk._expert_layer(
            bp, u, cfg, hybrid_trunk._routing(bp, y, cfg,
                                              "softmax_of_picks"), "relu")
    parts = [part(first) for first in (0, 2, 4, 6)]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < SOUND * scale
    # and a share alone is the reference's share, and no share is nothing
    one = blk._experts(u, idx, g, _share(w, 2, 2),
                       dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] - one))) < SOUND * scale
    assert all(float(jnp.max(jnp.abs(p))) > 0.01 * scale for p in parts)


def test_the_second_rule_is_a_softmax_over_the_picked_logits():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    idx, gate = moe.route(x, w, 6, 1.0, "softmax_of_picks")
    z = np.asarray(jnp.dot(x, w, precision="highest"))
    order = np.argsort(-z, axis=1)[:, :6]
    assert (np.sort(np.asarray(idx), 1) == np.sort(order, 1)).all()
    picked = np.take_along_axis(z, np.asarray(idx), 1)
    want = np.exp(picked) / np.exp(picked).sum(1, keepdims=True)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6
    # the first rule is untouched by the second
    idx1, gate1 = moe.route(x, w, 6, 2.0)
    s = 1 / (1 + np.exp(-z))
    top = np.take_along_axis(s, np.asarray(idx1), 1)
    assert np.abs(np.asarray(gate1) - 2 * top / top.sum(1, keepdims=True)
                  ).max() < 1e-6
    with pytest.raises(ValueError):
        moe.route(x, w, 6, 1.0, "softmax")


# -- nothing dropped under the ReLU gate -------------------------------------
def _filling(T, first, held, published, rows):
    away = first + held if first + held < published else 0
    idx = np.full((T, 2), away, np.int32)
    n = rows - (held - 1) * TILE_M
    idx[:n, 0] = first + held - 1
    for e in range(held - 1):
        idx[n + e, 1] = first + e
    return jnp.asarray(idx)


LOADS = {
    "all_on_one_held_expert": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), first + 1), jnp.full((T,), 0)], 1),
    "none_held": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), 0), jnp.full((T,), first + held)], 1),
    "balanced": lambda T, first, held, pub: jnp.stack(
        [jnp.arange(T) % pub, (jnp.arange(T) // pub + 1
                               + jnp.arange(T)) % pub], 1),
    "one_tile_over_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) + TILE_M),
}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_nothing_is_dropped_at_skewed_loads(load, act="relu"):
    """Every kept pair has a row of its own on either bound, and the
    result and its four gradients are the plain masked sum's — under the
    ReLU gate (``tests/test_mla_moe_routing.py`` holds the same loads
    under SiLU: one code path, a static activation)."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (T, c), jnp.float32)
    wgu = jax.random.normal(ks[1], (held, c, 2 * f), jnp.float32) / c ** .5
    wd = jax.random.normal(ks[2], (held, f, c), jnp.float32) / f ** .5
    gate = jax.random.uniform(ks[3], (T, k), jnp.float32, 0.1, 1.0)
    co = jax.random.normal(ks[4], (T, c), jnp.float32)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    kept = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(jnp.sum(p.row_pair >= 0)) == kept            # no drop
    fn = jax.nn.relu if act == "relu" else jax.nn.silu

    def plain(x, gate, wgu, wd):
        y = jnp.zeros_like(x)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == e + first, gate, 0.0), -1)
            h = fn(x @ wgu[e][:, :f]) * (x @ wgu[e][:, f:])
            y = y + mine[:, None] * (h @ wd[e])
        return y
    # value and the four gradients, one program a form
    both = lambda fn: jax.jit(lambda *a: (lambda y, vjp: (y,) + vjp(co))(
        *jax.vjp(fn, *a)))(x, gate, wgu, wd)
    for a, b in zip(both(lambda *a: moe.routed_ffn(*a, p, act)),
                    both(plain)):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


def test_the_cell_s_load_bound_is_twice_the_expected_pairs():
    # the cell: 16,384 tokens, top-6, 16 of 64 held
    assert moe.rows_bound(16384, 6, 16) == 102400
    assert moe.load_bound(16384, 6, 16, 64) \
        == 2 * 24576 + 16 * TILE_M == 53248
    with pytest.raises(ValueError):
        moe.routed_ffn(jnp.zeros((8, 8)), jnp.zeros((8, 1)),
                       jnp.zeros((1, 8, 16)), jnp.zeros((1, 8, 8)),
                       moe.plan(jnp.zeros((8, 1), jnp.int32), 0, 1, 1),
                       "gelu")


# -- what a configuration must state -----------------------------------------
def _stated(**change):
    base = dict(
        vocab_size=64, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        sliding_window_size=64, moe_primary_router_apply_softmax=True,
        moe_intermediate_size=128, n_routed_experts=8, experts_held=2,
        num_experts_per_tok=3)
    base.update(change)
    return LlamaPretrainConfig(**base)


def test_layer_types_follow_from_the_two_layouts():
    cfg = _stated()
    assert cfg.layer_types == ("gqa_moe_global", "gqa_moe_window",
                               "gqa_moe_window", "gqa_moe_window")
    # the published lists are the model's depth long; the cut keeps a prefix
    deep = _stated(rope_layout=(0, 1, 1, 1) * 13,
                   sliding_window_layout=(0, 1, 1, 1) * 13,
                   num_hidden_layers=8)
    assert deep.layer_types == cfg.layer_types * 2
    assert deep.rope_layout == deep.sliding_window_layout == (0, 1, 1, 1) * 2
    # a configuration without the keys is the program it was
    plain = LlamaPretrainConfig(hidden_size=256, num_attention_heads=2)
    assert plain.layer_types is None and plain.head_dim == 128


@pytest.mark.parametrize("change,error", [
    (dict(rope_layout=(1, 1, 1, 1)), NotImplementedError),
    (dict(rope_layout=(0, 0, 1, 1)), NotImplementedError),
    (dict(moe_primary_router_apply_softmax=False), NotImplementedError),
    (dict(n_shared_experts=1), NotImplementedError),
    (dict(hc_mult=4), NotImplementedError),
    (dict(sliding_window_size=0), ValueError),
    (dict(num_experts_per_tok=0), ValueError),
    (dict(experts_held=9), ValueError),
    (dict(moe_intermediate_size=0), ValueError),
    (dict(layer_types=("gqa_moe_global", "mla_moe", "gqa_moe_window",
                       "gqa_moe_window")), NotImplementedError),
])
def test_check_names_what_it_refuses(change, error):
    with pytest.raises(error):
        _stated(**change)


def test_layers_by_kind_stay_on_one_device():
    cfg = _stated()
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="gqa_moe"):
        make_train_step(cfg, mesh, optimizer="adafactor")
