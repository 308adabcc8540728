"""The Kimi-Delta-Attention / gated-attention kinds of
``models/hybrid_trunk.py`` (``kda_moe``, ``gqa_gated_moe``) against
``benchmark/models/solar_kda_moe_reference.py`` on seeded weights: each
kind's block (output and every leaf's gradient), adafactor's step on the
new leaf shapes, and what ``check`` / ``check_layout`` / the family's
``build_cfg`` refuse.  The whole toy model and the shares are in
``test_solar_trunk.py``, the one-place alterations of the program in
``test_solar_program_altered.py``; the toy's delta-rule heads are 64
wide, so its recurrence is ``kda_chunked_xla`` (the kernels run the same
``chunk_step``: ``test_kda.py`` holds them to the recurrence)."""

import pytest

import jax
import jax.numpy as jnp

from _solar_toy import BLOCK, F32, layer_of, toy, x_of  # noqa: F401
from benchmark import reference
from benchmark.models import solar_kda_moe_reference as blk
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, adafactor_update, build_mesh,
    init_adafactor_state, make_train_step)


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / (jnp.linalg.norm(want.ravel()) + 1e-30))


@pytest.mark.parametrize("kind,block", [("gqa_gated_moe", blk.attention_block),
                                        ("kda_moe", blk.kda_block)])
def test_a_kind_s_block_is_the_reference_s(toy, kind, block):
    """Output and every leaf's gradient of ONE layer on seeded weights."""
    bp, x, dims = layer_of(toy, kind), x_of(toy), blk.dims_of(toy.conf)
    assert set(bp) == set(blk.KINDS[kind][0])
    co = jax.random.normal(jax.random.PRNGKey(7), x.shape, F32)
    prog = jax.jit(jax.value_and_grad(lambda bp: jnp.sum(
        co * hybrid_trunk._kda_block(bp, x, toy.cfg))))
    want = jax.jit(jax.value_and_grad(
        lambda bp: jnp.sum(co * block(x, bp, dims)[0])))
    (a, ga), (b, gb) = prog(bp), want(bp)
    assert abs(float(a - b)) < BLOCK * abs(float(b))
    gaps = {nm: _rel(ga[nm], gb[nm]) for nm in bp}
    assert max(gaps.values()) < BLOCK, gaps


# -- adafactor ---------------------------------------------------------------
@pytest.mark.parametrize("shape", [(3, 256, 4), (3, 2), (3, 128), (3, 256),
                                   (3, 128, 128), (3, 128, 256)],
                         ids=["taps", "A_log", "o_norm", "dt_bias", "w_fa",
                              "w_fb"])
def test_adafactor_steps_the_new_leaves_a_layer_at_a_time(shape):
    """``[layers, channels, 4]`` taps, the ``[heads]`` / ``[head_dim]`` /
    ``[channels]`` vectors (unfactored) and the low-rank pair (factored
    where both axes are >= 128): the reference's ``_adafactor_leaf`` on
    one layer's leaf, layer by layer."""
    ks = jax.random.split(jax.random.PRNGKey(len(shape) + shape[-1]), 2)
    scale = jnp.asarray([1.0, 10.0, 0.1], F32).reshape(
        (3,) + (1,) * (len(shape) - 1))
    p = jax.random.normal(ks[0], shape, F32) * scale
    g = jax.random.normal(ks[1], shape, F32) * 5.0
    params, grads = {"blocks": {"kda_moe": {"w": p}}}, \
        {"blocks": {"kda_moe": {"w": g}}}
    state = init_adafactor_state(params)
    factored = len(shape) == 3 and min(shape[1:]) >= 128
    assert ("vr" in state["moments"]["blocks"]["kda_moe"]["w"]) == factored
    new, state = adafactor_update(params, grads, state, lr=0.01,
                                  weight_decay=0.1)
    new, _ = adafactor_update(new, grads, state, lr=0.01, weight_decay=0.1)
    for layer in range(3):
        want, st = p[layer], reference._opt_init(p[layer])
        for t in (1.0, 2.0):
            want, st = reference._adafactor_leaf(
                want, g[layer], st, jnp.asarray(t, F32), 0.01, 0.1)
        got = new["blocks"]["kda_moe"]["w"][layer]
        assert float(jnp.max(jnp.abs(got - want))) \
            < 1e-6 * float(jnp.max(jnp.abs(want)))


# -- what a configuration must state -----------------------------------------
def _stated(**change):
    base = dict(
        vocab_size=64, hidden_size=128, intermediate_size=256,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, position_embedding_type="nope", gqa_layers=(0, 4, 8),
        kda_num_heads=2, kda_head_dim=128, short_conv_kernel_size=4,
        moe_intermediate_size=128, n_routed_experts=8, n_shared_experts=1,
        experts_held=2, num_experts_per_tok=3)
    base.update(change)
    return LlamaPretrainConfig(**base)


def test_the_kinds_follow_from_gqa_layers():
    period = ("gqa_gated_moe",) + ("kda_moe",) * 3
    assert _stated().layer_types == period
    assert _stated(num_hidden_layers=9).layer_types == period * 2 + period[:1]
    assert hybrid_trunk.layer_runs(_stated(num_hidden_layers=6).layer_types) \
        == [("gqa_gated_moe", 0, 1), ("kda_moe", 0, 3),
            ("gqa_gated_moe", 1, 2), ("kda_moe", 3, 4)]
    assert set(hybrid_trunk.KDA_KINDS) <= set(hybrid_trunk.ROUTED_KINDS)
    shapes = hybrid_trunk.kind_shapes(_stated(), "kda_moe")
    assert shapes["w_qkv"] == (128, 768) and shapes["conv_k"] == (256, 4) \
        and shapes["w_fb"] == (128, 256) and shapes["ws_down"] == (128, 128)
    assert hybrid_trunk.kind_shapes(_stated(), "gqa_gated_moe")["wg"] \
        == (128, 4 * 32)


@pytest.mark.parametrize("change,error", [
    (dict(layer_types=("kda_moe", "attention", "kda_moe", "kda_moe")),
     NotImplementedError),
    (dict(hc_mult=4), NotImplementedError),
    (dict(kda_num_heads=0), ValueError),
    (dict(short_conv_kernel_size=0), ValueError),
    (dict(num_experts_per_tok=0), ValueError),
    (dict(experts_held=9), ValueError),
    (dict(moe_intermediate_size=0), ValueError),
])
def test_check_names_what_it_refuses(change, error):
    with pytest.raises(error):
        _stated(**change)


@pytest.mark.parametrize("key,value", [("use_gqa_gate", False),
                                       ("kda_use_full_proj", True),
                                       ("kda_allow_neg_eigval", False)])
def test_the_family_refuses_a_published_key_it_does_not_build(toy, key,
                                                             value):
    """The two kinds ARE a gated attention layer and a delta rule with
    low-rank gate maps and beta in (0, 2): a configuration that states
    otherwise is refused where its keys are read."""
    with pytest.raises(ValueError, match="solar_kda_moe"):
        toy.cell.family.build_cfg(dict(toy.conf, **{key: value}), True,
                                  toy.job)


@pytest.mark.parametrize("kind", hybrid_trunk.KDA_KINDS)
def test_layers_by_kind_stay_on_one_device(kind):
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match=kind):
        make_train_step(_stated(), mesh, optimizer="adafactor")
