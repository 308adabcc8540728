"""The new parts of the convolution cell, each alone, at toy size on the
CPU: the gated short convolution (the operator and its vjp against three
shifted adds; the kernels in the interpreter against their ``jnp`` twin,
the three groups at lane offsets 0 / C / 2C of one array), the q / k
norms before the rotation, the third routing rule (picks by score +
bias, gates by score, no gradient to the bias, a share of picks that is
the bias's doing), the four shares adding up to the uncut layer, and
what ``check`` / ``check_layout`` refuse by name.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _lfm2_toy import toy  # noqa: F401
from _toy_cell import SOUND
from benchmark import reference
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, build_mesh, make_train_step)
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import causal_conv

F32 = jnp.float32


# -- the operator ------------------------------------------------------------
def _shifted_adds(bcx, w):
    """out[t] = Cg[t] * sum_k w[:, k] (B X)[t - (K-1) + k], written as K
    shifts of the row in numpy terms: no pad, no slice of a padded row."""
    c, k = w.shape
    b_, cg, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    u = (b_ * x).astype(F32)
    v = jnp.zeros_like(u)
    for back in range(k):           # tap K-1-back looks ``back`` back
        moved = jnp.roll(u, back, axis=1).at[:, :back].set(0.0)
        v = v + moved * w[:, k - 1 - back]
    return cg * v


def _value_and_grads(fn, bcx, w, co):
    y, pull = jax.vjp(fn, bcx, w)
    return (y,) + pull(co.astype(y.dtype))


@pytest.mark.parametrize("b,s,c,k", [(2, 1024, 256, 3), (1, 512, 128, 4),
                                     (2, 256, 384, 3)])
@pytest.mark.parametrize("dtype,tol", [(F32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_the_kernels_are_the_twin_and_three_shifted_adds(b, s, c, k, dtype,
                                                         tol):
    """Two row tiles (the halo), one (none), three lane tiles a group
    (offsets 0, 384, 768): value, d bcx as ONE ``[b, s, 3 C]`` array and
    d w summed over the row's tiles."""
    ks = jax.random.split(jax.random.PRNGKey(s + c), 3)
    bcx = jax.random.normal(ks[0], (b, s, 3 * c), F32).astype(dtype)
    w = jax.random.normal(ks[1], (c, k), F32) / k ** 0.5
    co = jax.random.normal(ks[2], (b, s, c), F32)
    assert causal_conv.takes_gated(bcx, w)
    kernel, twin, plain = (jax.jit(
        lambda bcx, w, fn=fn: _value_and_grads(fn, bcx, w, co))(bcx, w)
        for fn in (causal_conv.short_conv_gated,
                   causal_conv.short_conv_gated_xla,
                   lambda bcx, w: _shifted_adds(bcx.astype(F32), w)))
    assert kernel[1].shape == (b, s, 3 * c) and kernel[2].shape == (c, k)
    for got, want, plain_ in zip(kernel, twin, plain):
        assert got.dtype == want.dtype
        scale = max(float(jnp.max(jnp.abs(plain_))), 1.0)
        for other in (want, plain_):
            assert float(jnp.max(jnp.abs(
                got.astype(F32) - other.astype(F32)))) <= tol * scale


def test_a_shape_the_kernels_refuse_runs_the_twin():
    w = jnp.ones((128, 3))
    takes = causal_conv.takes_gated
    assert takes(jnp.zeros((1, 256, 384)), w)
    assert not takes(jnp.zeros((1, 256, 512)), w)          # not 3 C wide
    assert not takes(jnp.zeros((1, 250, 384)), w)          # no row tile
    assert not takes(jnp.zeros((1, 256, 288)), jnp.ones((96, 3)))
    assert not takes(jnp.zeros((1, 256, 384)), jnp.ones((128, 9)))
    # the trunk then takes XLA's form: the same operator
    cfg = LlamaPretrainConfig(
        vocab_size=64, hidden_size=96, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=2, layer_types=("conv",),
        num_dense_layers=1, conv_L_cache=3, dtype=F32, remat=False)
    assert cfg.layer_types == ("conv_dense",)
    bp = {nm: leaf[0] for nm, leaf in hybrid_trunk.init_blocks(
        cfg, jax.random.PRNGKey(0))["conv_dense"].items()}
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 50, 96), F32)
    want = _shifted_adds(y @ bp["w_in"], bp["conv_w"]) @ bp["w_out"]
    got = hybrid_trunk._short_conv(bp, y, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# -- q / k norms before the rotation -----------------------------------------
def test_q_and_k_are_normed_a_head_before_the_rotation(toy):
    cfg = toy.cfg
    bp = {nm: leaf[0] for nm, leaf in
          toy.params0["blocks"]["gqa_qknorm_moe"].items()}
    # weights off one, or a norm's place relative to the rotation would
    # not show in its weight
    bp["q_layernorm"] = 1 + 0.5 * jnp.cos(jnp.arange(32.0))
    bp["k_layernorm"] = 1 + 0.5 * jnp.sin(jnp.arange(32.0))
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 128), F32)
    q, k, v = llama_pretrain._qkv(bp, y, cfg, None, rotate=True)
    n = lambda x, w: reference.rms_norm(x, w, cfg.rms_norm_eps)
    want_q = reference.rope(n((y @ bp["wq"]).reshape(2, 64, 4, 32),
                              bp["q_layernorm"]), cfg.rope_theta)
    want_k = reference.rope(n((y @ bp["wk"]).reshape(2, 64, 2, 32),
                              bp["k_layernorm"]), cfg.rope_theta)
    assert float(jnp.max(jnp.abs(q - want_q))) < 1e-5
    assert float(jnp.max(jnp.abs(k - want_k))) < 1e-5
    np.testing.assert_array_equal(
        np.asarray(v), np.asarray((y @ bp["wv"]).reshape(2, 64, 2, 32)))
    # every head's q has its weight's size, whatever the projection gave
    unrotated, _, _ = llama_pretrain._qkv(bp, 7.0 * y, cfg, None, False)
    rms = jnp.sqrt(jnp.mean(jnp.square(unrotated / bp["q_layernorm"]), -1))
    assert float(jnp.max(jnp.abs(rms - 1))) < 1e-3
    # a layer without the leaves is the projection it was
    bare = {nm: v for nm, v in bp.items() if not nm.endswith("_layernorm")}
    q0, _, _ = llama_pretrain._qkv(bare, y, cfg, None, False)
    np.testing.assert_array_equal(
        np.asarray(q0), np.asarray((y @ bp["wq"]).reshape(2, 64, 4, 32)))


# -- the third rule ----------------------------------------------------------
def _router_case(T=2048, c=128, pub=64):
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (T, c), F32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (c, pub)) / c ** 0.5
    bias = jax.random.normal(jax.random.fold_in(key, 2), (pub,)) \
        * hybrid_trunk.EXPERT_BIAS_STD
    return x, w, bias


def test_the_bias_picks_and_the_scores_gate():
    x, w, bias = _router_case()
    k, scale = 4, 1.0
    idx, gate = moe.route(x, w, k, scale, "sigmoid_biased_picks", bias)
    s = 1 / (1 + np.exp(-np.asarray(jnp.dot(x, w, precision="highest"),
                                    np.float64)))
    biased = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :k]
    assert (np.sort(np.asarray(idx), 1) == np.sort(biased, 1)).all()
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    want = scale * picked / (picked.sum(1, keepdims=True) + 1e-6)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6
    # A SHARE OF THE PICKS IS THE BIAS'S DOING: at the seeded scale some
    # tokens' picks differ from the unbiased top-k, and not all
    plain = np.sort(np.argsort(-s, axis=1)[:, :k], 1)
    differs = (np.sort(np.asarray(idx), 1) != plain).any(1).mean()
    assert 0.05 < differs < 0.95, differs
    # ... and a gate may be smaller than that of an expert passed over
    best_left = np.where(
        (np.arange(64)[None, :, None] == np.asarray(idx)[:, None, :]).any(-1),
        -1.0, s).max(1)
    assert (best_left > picked.min(1)).mean() > 0.02
    # no bias with this rule, no other rule with one
    with pytest.raises(ValueError):
        moe.route(x, w, k, scale, "sigmoid_biased_picks")
    with pytest.raises(ValueError):
        moe.route(x, w, k, scale, "sigmoid", bias)


def test_the_bias_reads_no_gradient_and_the_scores_read_theirs():
    x, w, bias = _router_case(T=256)
    co = jax.random.normal(jax.random.PRNGKey(5), (256, 4), F32)

    def gates(x, w, bias):
        return jnp.sum(co * moe.route(x, w, 4, 2.0, "sigmoid_biased_picks",
                                      bias)[1])
    dx, dw, db = jax.grad(gates, argnums=(0, 1, 2))(x, w, bias)
    assert float(jnp.max(jnp.abs(db))) == 0.0
    assert float(jnp.max(jnp.abs(dx))) > 0 and float(jnp.max(jnp.abs(dw))) > 0

    # the same gates with the picks held: the derivative of the scores'
    # share, nothing through the selection
    idx = moe.route(x, w, 4, 2.0, "sigmoid_biased_picks", bias)[0]

    def held(x, w):
        s = jax.nn.sigmoid(jnp.dot(x, w, precision="highest"))
        top = jnp.take_along_axis(s, idx, 1)
        return jnp.sum(co * 2.0 * top / (jnp.sum(top, -1, keepdims=True)
                                         + 1e-6))
    for got, want in zip((dx, dw), jax.grad(held, argnums=(0, 1))(x, w)):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-6


# -- the share ---------------------------------------------------------------
def _layer_weights(key, c, f, experts):
    ks = jax.random.split(key, 4)
    n = lambda k, shape, fan: jax.random.normal(k, shape, F32) / fan ** 0.5
    return {"w_router": n(ks[0], (c, experts), c),
            "expert_bias": jax.random.normal(ks[3], (experts,))
            * hybrid_trunk.EXPERT_BIAS_STD,
            "we_gate_up": n(ks[1], (experts, c, 2 * f), c),
            "we_down": n(ks[2], (experts, f, c), f)}


def _share(w, first, held):
    return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                we_down=w["we_down"][first:first + held])


def test_the_shares_add_up_to_the_whole_layer(toy):
    """The four shares' routed parts (experts 0-1, 2-3, 4-5, 6-7 of the
    toy's 8; 0-15 .. 48-63 of the cell's 64) are what the UNCUT reference
    gives for the whole expert layer.  What every chip computes alike —
    the router, its bias, the operator, the norms — is no part of the
    sum: it is counted once."""
    from benchmark.models import lfm2_conv_moe_reference as blk
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    w = _layer_weights(jax.random.PRNGKey(3), c, f, 8)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 300, c), F32)
    whole = dict(blk.dims_of(dict(toy.conf, num_experts=8, expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    idx, g = blk._route(u, w, whole, mm)
    assert float(jnp.max(jnp.abs(jnp.sum(g, -1) - 1))) < 1e-5
    want = blk._experts(u, idx, g, w, whole, mm)

    def part(first):
        cfg = dataclasses.replace(toy.cfg, expert_first=first,
                                  experts_held=2)
        bp = _share(w, first, 2)
        return hybrid_trunk._expert_layer(
            bp, u, cfg, hybrid_trunk._routing(bp, u, cfg,
                                              "sigmoid_biased_picks"))
    parts = [part(first) for first in (0, 2, 4, 6)]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < SOUND * scale
    # and a share alone is the reference's share, and no share is nothing
    one = blk._experts(u, idx, g, _share(w, 2, 2),
                       dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] - one))) < SOUND * scale
    assert all(float(jnp.max(jnp.abs(p))) > 0.01 * scale for p in parts)


def test_the_cell_s_load_bound_is_twice_the_expected_pairs():
    # the cell: 16,384 tokens, top-4, 16 of 64 held
    from paddle_tpu.ops.pallas.grouped_mm import TILE_M
    assert moe.rows_bound(16384, 4, 16) == 65536 + 16 * TILE_M
    assert moe.load_bound(16384, 4, 16, 64) == 2 * 16384 + 16 * TILE_M


# -- what a configuration must state -----------------------------------------
PUBLISHED = ("conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv")


def _stated(**change):
    base = dict(
        vocab_size=64, hidden_size=128, intermediate_size=256,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        layer_types=PUBLISHED, num_dense_layers=1, conv_L_cache=3,
        use_expert_bias=True, moe_intermediate_size=128,
        n_routed_experts=8, experts_held=2, num_experts_per_tok=3,
        tie_word_embeddings=True)
    base.update(change)
    return LlamaPretrainConfig(**base)


def test_the_kinds_follow_from_the_published_list():
    cfg = _stated()
    assert cfg.layer_types == ("conv_dense", "gqa_qknorm_moe") \
        + ("conv_moe",) * 3
    assert _stated(num_hidden_layers=9).layer_types == (
        "conv_dense",) + ("gqa_qknorm_moe", "conv_moe", "conv_moe",
                          "conv_moe") * 2
    # two leading dense layers, as published
    two = _stated(layer_types=("conv",) + PUBLISHED, num_dense_layers=2,
                  num_hidden_layers=6)
    assert two.layer_types == ("conv_dense",) * 2 + (
        "gqa_qknorm_moe",) + ("conv_moe",) * 3
    # made again from itself (dataclasses.replace): the kinds stay
    assert dataclasses.replace(cfg, dtype=F32).layer_types \
        == cfg.layer_types
    assert set(cfg.layer_types) <= set(hybrid_trunk.KINDS)
    assert set(hybrid_trunk.ROUTED_KINDS) >= {"conv_moe", "gqa_qknorm_moe"}
    assert "conv_dense" not in hybrid_trunk.ROUTED_KINDS


@pytest.mark.parametrize("change,error", [
    (dict(layer_types=("full_attention",) + PUBLISHED), NotImplementedError),
    (dict(layer_types=("conv", "mamba", "conv", "conv", "conv")),
     ValueError),
    (dict(layer_types=("conv_dense", "attention", "conv_moe", "conv_moe",
                       "conv_moe")), NotImplementedError),
    (dict(use_expert_bias=False), NotImplementedError),
    (dict(n_shared_experts=1), NotImplementedError),
    (dict(hc_mult=4), NotImplementedError),
    (dict(layer_types=("conv_dense",) * 5, conv_L_cache=0), ValueError),
    (dict(num_experts_per_tok=0), ValueError),
    (dict(experts_held=9), ValueError),
    (dict(moe_intermediate_size=0), ValueError),
])
def test_check_names_what_it_refuses(change, error):
    with pytest.raises(error):
        _stated(**change)


def test_layers_by_kind_stay_on_one_device():
    cfg = _stated()
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="conv_moe"):
        make_train_step(cfg, mesh, optimizer="adafactor")
