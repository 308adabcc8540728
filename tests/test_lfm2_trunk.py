"""Gated short-convolution layers among grouped-query attention layers
with per-head q / k norms, a dense lead, then routed SwiGLU experts
picked by a bias, on the normal path: ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``conv_dense`` / ``conv_moe`` /
``gqa_qknorm_moe`` (``ops/pallas/causal_conv.py``'s gated form in the
interpreter, ``ops/moe.py``'s third routing rule) held to the plain
reference ``benchmark/models/lfm2_conv_moe_reference.py`` at toy size
(``tests/_lfm2_toy.py``) — float32 on the CPU tightly, and the cells'
own bf16 within what bf16 allows and PAST the float32 tolerance.  One
place of the PROGRAM changed: ``test_lfm2_program_altered.py``; each new
part alone, the share and what ``check`` refuses:
``test_lfm2_parts.py``.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_cell
from _lfm2_toy import ref, sound, toy  # noqa: F401
from _toy_cell import SEQ, SOUND, follow
from benchmark import reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain

# The two-step CHANGE is held looser than loss and gradient: a pick of
# the router is a comparison, and after one step the program's and the
# reference's parameters differ in the seventh digit — enough to turn a
# near-tie of one token's biased scores the other way.
SOUND_CHANGE = 1e-3
# the cells' own compute type against the float32 reference: far from
# the float32 program's 1e-5, and no alteration's size
BF16 = {"loss_rel_gap": 2e-3, "grad_norm_worst_leaf_gap": 5e-2,
        "param_change_worst_leaf_gap": 0.1}


def test_the_made_tree_is_the_leaf_maker_s(toy):
    _toy_cell.made_tree_is_the_leaf_maker_s(toy, [
        ("embed",), ("blocks", "conv_moe", "conv_w"),
        ("blocks", "conv_moe", "expert_bias"),
        ("blocks", "gqa_qknorm_moe", "we_gate_up")])


def test_the_toy_has_what_the_cell_has(toy):
    cfg = toy.cfg
    assert cfg.layer_types == ("conv_dense", "gqa_qknorm_moe") \
        + ("conv_moe",) * 3
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("conv_dense", 0, 1), ("gqa_qknorm_moe", 0, 1), ("conv_moe", 0, 3)]
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (4, 2, 32)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.conv_L_cache) == (8, 2, 2, 3, 3)
    assert cfg.tie_word_embeddings and cfg.use_expert_bias
    shapes = {k: hybrid_trunk.kind_shapes(cfg, k)
              for k in hybrid_trunk.CONV_KINDS}
    for kind in ("conv_dense", "conv_moe"):
        assert shapes[kind]["w_in"] == (128, 384)       # B | Cg | X
        assert shapes[kind]["conv_w"] == (128, 3)
        assert shapes[kind]["w_out"] == (128, 128)
    assert shapes["conv_dense"]["w_gate"] == (128, 256)
    assert "w_router" not in shapes["conv_dense"]
    for kind in ("conv_moe", "gqa_qknorm_moe"):
        assert shapes[kind]["w_router"] == (128, 8)     # published width
        assert shapes[kind]["expert_bias"] == (8,)
        assert shapes[kind]["we_gate_up"] == (2, 128, 256)
    attn = shapes["gqa_qknorm_moe"]
    assert attn["q_layernorm"] == attn["k_layernorm"] == (32,)
    assert attn["wq"] == (128, 128) and attn["wk"] == (128, 64)
    # the bias is seeded off zero, at the stated size
    bias = np.asarray(toy.leaf0(("blocks", "conv_moe", "expert_bias")))
    assert 0.5 < bias.std() / hybrid_trunk.EXPERT_BIAS_STD < 2


@pytest.mark.parametrize("what", ["loss_rel_gap.step0", "loss_rel_gap.step1",
                                  "grad_norm_worst_leaf_gap",
                                  "param_change_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 8 + 9 + 12 + 2
    assert train_cell.gap_numbers(sound, ref)[what] < (
        SOUND_CHANGE if what.startswith("param") else SOUND)


def test_the_bias_reads_no_gradient_on_either_side(sound, ref):
    for kind in ("conv_moe", "gqa_qknorm_moe"):
        path = ("blocks", kind, "expert_bias")
        assert ref["grad"][path] == 0.0 and sound["grad"][path] < 1e-12
        # the job's weight decay alone moves it, alike on both sides
        assert 0 < ref["change"][path] < 1e-4
        assert abs(sound["change"][path] - ref["change"][path]) \
            < 1e-3 * ref["change"][path]


def test_bf16_is_sound_and_past_the_float32_tolerance(toy, ref):
    """The program in the cells' compute type: within what bf16 allows
    of the float32 reference, and NOT within the tolerance the float32
    program meets — the tight comparison tells the two apart."""
    gaps = train_cell.gap_numbers(
        follow(toy, dataclasses.replace(toy.cfg, dtype=jnp.bfloat16)), ref)
    for name, value in gaps.items():
        assert value < BF16[name.split(".")[0]], (name, value)
    assert gaps["grad_norm_worst_leaf_gap"] > 10 * SOUND
    assert gaps["param_change_worst_leaf_gap"] > SOUND_CHANGE


def test_logits_match_the_reference(toy):
    cfg, params = toy.cfg, toy.params0
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        x = jnp.take(params["embed"], ids[None], axis=0)
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["embed"].T)[0]
    # the row's first positions, where the taps reach before it
    rows = np.asarray([0, 1, 2, 3, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))
