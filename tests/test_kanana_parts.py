"""The latent-attention kinds of ``models/hybrid_trunk.py`` (``mla_dense``,
``mla_moe``) in their PLAIN form — ONE residual stream (``hc_mult`` 1), a
query without a latent (``q_lora_rank`` 0) — against
``benchmark/models/kanana_mla_moe_reference.py`` on seeded weights: each
kind's block (output and every leaf's gradient), what ``check`` takes and
refuses, what the family's ``build_cfg`` refuses.  The whole toy model
and the shares are in ``test_kanana_trunk.py``, the one-place
alterations of the program in ``test_kanana_program_altered.py``."""

import pytest

import jax
import jax.numpy as jnp

from _kanana_toy import layer_of, toy  # noqa: F401
from _toy_cell import SOUND, block_gap
from benchmark.models import kanana_mla_moe_reference as blk
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, build_mesh, make_train_step)

F32 = jnp.float32


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / (jnp.linalg.norm(want.ravel()) + 1e-30))


@pytest.mark.parametrize("kind", hybrid_trunk.MLA_KINDS)
def test_a_kind_s_block_is_the_reference_s(toy, kind):
    """Output (``_toy_cell.block_gap``: sound < 1e-5) and every leaf's
    gradient of ONE layer on seeded weights, on one stream."""
    assert block_gap(toy, toy.cfg, kind, hybrid_trunk._mla_block) < SOUND
    bp, dims = layer_of(toy, kind), blk.dims_of(toy.conf)
    assert set(bp) == set(blk.KINDS[kind][0])
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (1, 256, toy.cfg.hidden_size), F32)
    co = jax.random.normal(jax.random.PRNGKey(7), x.shape, F32)
    prog = jax.jit(jax.grad(lambda bp: jnp.sum(
        co * hybrid_trunk._mla_block(bp, x, toy.cfg))))
    want = jax.jit(jax.grad(lambda bp: jnp.sum(
        co * blk.KINDS[kind][1](x, bp, dims)[0])))
    ga, gb = prog(bp), want(bp)
    gaps = {nm: _rel(ga[nm], gb[nm]) for nm in bp}
    assert max(gaps.values()) < 1e-4, gaps


# -- what a configuration must state -----------------------------------------
def _stated(**change):
    base = dict(
        vocab_size=64, hidden_size=128, intermediate_size=256,
        num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
        kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_k_dense_replace=1, moe_intermediate_size=128,
        n_routed_experts=8, n_shared_experts=2, experts_held=2,
        num_experts_per_tok=3, routed_scaling_factor=2.448)
    base.update(change)
    return LlamaPretrainConfig(**base)


def test_one_stream_and_a_direct_query_are_what_the_defaults_mean():
    """``hc_mult`` 1 and ``q_lora_rank`` 0 — the fields' own defaults —
    select the plain block and the direct query: no mixer leaf, no query
    latent, two query leaves in whole lane tiles."""
    cfg = _stated()
    assert (cfg.hc_mult, cfg.q_lora_rank) == (1, 0)
    assert cfg.layer_types == ("mla_dense", "mla_moe", "mla_moe")
    shapes = hybrid_trunk.kind_shapes(cfg, "mla_moe")
    assert not [nm for nm in shapes if nm.startswith("hc")]
    assert not {"w_qa", "q_norm", "w_qb_nope", "w_qb_rope"} & set(shapes)
    assert shapes["w_q_nope"] == (128, 2 * 128) \
        and shapes["w_q_rope"] == (128, 2 * 64)
    assert shapes["ws_gate"] == (128, 2 * 128)      # two shared, one SwiGLU
    assert shapes["w_router"] == (128, 8)           # the published width
    assert list(hybrid_trunk.kind_shapes(cfg, "mla_dense"))[-3:] == [
        "w_gate", "w_up", "w_down"]
    # the two forms of either choice still build
    latent = hybrid_trunk.kind_shapes(_stated(q_lora_rank=64), "mla_moe")
    assert latent["w_qb_rope"] == (64, 128) and "w_q_nope" not in latent
    streams = hybrid_trunk.kind_shapes(_stated(hc_mult=4), "mla_dense")
    assert streams["hc2_phi"] == (4 * 128, 24) and "w_q_rope" in streams
    assert hybrid_trunk.kept_outputs(cfg, 1, 16384) == (True, False)


@pytest.mark.parametrize("change,error", [
    (dict(layer_types=("mla_dense", "attention", "mla_moe")),
     NotImplementedError),
    (dict(layer_types=("mla_dense", "attention", "mla_moe"), hc_mult=4),
     NotImplementedError),
    (dict(hc_mult=0), ValueError),
    (dict(q_lora_rank=-1), ValueError),
    (dict(kv_lora_rank=64, qk_rope_head_dim=63), ValueError),
    (dict(v_head_dim=64, qk_nope_head_dim=64), ValueError),
    (dict(n_shared_experts=0), ValueError),
    (dict(num_experts_per_tok=0), ValueError),
    (dict(experts_held=9), ValueError),
])
def test_check_names_what_it_refuses(change, error):
    """A trunk that mixes a latent-attention kind with another is refused
    at one stream as at several; what IS built is named."""
    with pytest.raises(error, match="built|mix|needs"):
        _stated(**change)


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("q_lora_rank", 64), ("tie_word_embeddings", True),
    ("num_nextn_predict_layers", 1)])
def test_the_family_refuses_a_published_key_it_does_not_build(toy, key,
                                                             value):
    with pytest.raises(ValueError, match="kanana_mla_moe"):
        toy.cell.family.build_cfg(dict(toy.conf, **{key: value}), True,
                                  toy.job)


def test_the_plain_block_stays_on_one_device():
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="mla_moe"):
        make_train_step(_stated(), mesh, optimizer="adafactor")


def test_the_family_s_costs_are_the_leaves(toy):
    """``block_costs`` (``tests/test_kernel_costs_declared.py`` holds the
    kernels' declarations; this the family's arithmetic): the resident
    matrix and vector parameters of a kind are its leaves' sizes."""
    from benchmark import kernel_costs
    for kind in hybrid_trunk.MLA_KINDS:
        costs = kernel_costs.block_costs(toy.conf, kind)
        leaves = hybrid_trunk.kind_shapes(toy.cfg, kind)
        size = lambda nm: int(jnp.prod(jnp.asarray(leaves[nm])))
        assert costs.resident_params == sum(
            size(nm) for nm in leaves if len(leaves[nm]) > 1)
        assert costs.vector_params == sum(
            size(nm) for nm in leaves if len(leaves[nm]) == 1)
        assert (costs.attn_width, costs.kv_values) == (2 * 160, 64 + 64)
    moe = kernel_costs.block_costs(toy.conf, "mla_moe")
    expert = 3 * 128 * 128
    # top-3 of 8, 2 held: three quarters of an expert a token
    assert moe.resident_params - moe.matmul_params \
        == 2 * expert - 3 * expert // 4
    assert kernel_costs.block_costs(toy.conf, "mla_dense").scan_flops == 0
