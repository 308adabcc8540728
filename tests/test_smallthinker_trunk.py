"""Window and global grouped-query attention before routed ReGLU experts
on the normal path: ``make_train_step`` over ``models/hybrid_trunk.py``'s
kinds ``gqa_moe_global`` / ``gqa_moe_window`` (``ops/moe.py``'s second
routing rule and ReLU gate, ``flash_attention`` dense and windowed) held
to the plain reference ``benchmark/models/smallthinker_moe_reference.py``
at toy size (``tests/_toy_cell.py``) — float32 on the CPU, the published
PATTERN (global, three window layers, global), four query / two KV heads
of 128, a window of 64 on rows of 256, two of eight experts held from
the third on, top-3 of the logits, seeded weights.  Then one line of the
REFERENCE is changed at a time and the comparison must fail; one key of
the PROGRAM's configuration: ``test_smallthinker_program_altered.py``.
The share, the skewed loads under the ReLU gate and what ``check``
refuses are in ``test_smallthinker_routing.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_cell
from _smallthinker_toy import ref, sound, toy  # noqa: F401
from _toy_cell import (BROKEN, SEQ, SOUND, altered_reference, first_step_gap,
                       follow_reference)
from benchmark import reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain

# The two-step CHANGE is held looser than loss and gradient: a pick of
# the router is a comparison, and after one step the program's and the
# reference's parameters differ in the seventh digit — enough to turn a
# near-tie of one token's logits the other way.
SOUND_CHANGE = 1e-3


def test_the_made_tree_is_the_leaf_maker_s(toy):
    _toy_cell.made_tree_is_the_leaf_maker_s(toy, [
        ("embed",), ("blocks", "gqa_moe_window", "we_gate_up"),
        ("blocks", "gqa_moe_global", "w_router")])


def test_the_toy_has_what_the_cell_has(toy):
    cfg = toy.cfg
    assert cfg.layer_types == ("gqa_moe_global",) + ("gqa_moe_window",) * 3 \
        + ("gqa_moe_global",)
    assert cfg.rope_layout == cfg.sliding_window_layout == (0, 1, 1, 1, 0)
    # two runs of the global kind around one of the window kind
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("gqa_moe_global", 0, 1), ("gqa_moe_window", 0, 3),
        ("gqa_moe_global", 1, 2)]
    # a head's width is stated, not hidden / heads (32 here)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (4, 2, 128)
    assert SEQ == 4 * cfg.sliding_window_size
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok) == (8, 2, 2, 3)
    for kind in hybrid_trunk.GQA_MOE_KINDS:
        shapes = hybrid_trunk.kind_shapes(cfg, kind)
        assert shapes["wq"] == (128, 512) and shapes["wo"] == (512, 128)
        assert shapes["wk"] == shapes["wv"] == (128, 256)
        assert shapes["we_gate_up"] == (2, 128, 256)    # rank 4 stacked
        assert shapes["w_router"] == (128, 8)           # published width
        assert not any(nm.startswith("ws_") for nm in shapes)


@pytest.mark.parametrize("what", ["loss_rel_gap.step0", "loss_rel_gap.step1",
                                  "grad_norm_worst_leaf_gap",
                                  "param_change_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 2 * 9 + 3
    assert train_cell.gap_numbers(sound, ref)[what] < (
        SOUND_CHANGE if what.startswith("param") else SOUND)


def test_logits_match_the_reference(toy):
    cfg, params = toy.cfg, toy.params0
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        x = jnp.take(params["embed"], ids[None], axis=0)
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["lm_head"])[0]
    # the first and the last query block, and both sides of a window's end
    rows = np.asarray([0, 1, 63, 64, 65, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))


# one line changed in the REFERENCE, each of them alone
REFERENCE = {
    "router_reads_ln2": (
        "return (h + _experts(u, idx, g, w, d, mm))[0]",
        "idx, g = _route(u, w, d, mm)\n"
        "        return (h + _experts(u, idx, g, w, d, mm))[0]"),
    "softmax_before_the_top_k": (
        "return idx, jax.nn.softmax(top, axis=-1)",
        "return idx, jnp.exp(top - jax.scipy.special.logsumexp("
        "z, -1, keepdims=True))"),
    "silu_for_relu": ("jax.nn.relu(mm(u, wgu[:, :f]))",
                      "jax.nn.silu(mm(u, wgu[:, :f]))"),
    "window_off_by_one_key": ('seen &= j > i - d["window"]',
                              'seen &= j >= i - d["window"]'),
    "rotation_on_a_global_layer": (
        "if window: q, k = rope(q,", "if True:\n        q, k = rope(q,"),
    "window_on_a_global_layer": (
        "if window: seen &= j >", "if True:\n        seen &= j >"),
    "gates_over_the_held_picks_only": (
        "return idx, jax.nn.softmax(top, axis=-1)",
        "return idx, jax.nn.softmax(jnp.where((idx >= d[\"first\"]) & "
        "(idx < d[\"first\"] + d[\"held\"]), top, -jnp.inf), axis=-1)"),
}


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    other = follow_reference(
        toy, altered_reference("smallthinker_moe", *REFERENCE[what]),
        steps=1)
    gap = first_step_gap(sound, other)
    # a token whose picks are all away would make NaN gates of the held-
    # picks-only rule: that too is no agreement
    assert not gap <= BROKEN
