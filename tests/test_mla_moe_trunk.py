"""Latent attention, routed experts without dropping and the residual
streams on the normal path: ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``mla_dense`` / ``mla_moe``
(``ops/moe.py``, ``flash_attention_split``, the mHC mixers, YaRN) held
to the plain reference ``benchmark/models/xing_mhc_moe_reference.py`` at
toy size (``tests/_mla_moe_toy.py``).  One thing changed at a time, in
the program or in the reference, must fail the same comparison:
``test_mla_moe_program_altered_*.py``, ``test_mla_moe_reference_altered.py``
(and the first two of the reference's here, beside the sound program).
The share, the skewed loads and the bounds are in
``test_mla_moe_routing.py``; the mixer's maps, YaRN, adafactor on a
rank-4 stack, the kept flash outputs and the refused layouts in
``test_mla_moe_parts.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_cell
from _mla_moe_toy import (REFERENCE, SOUND_CHANGE, ref,  # noqa: F401
                          reference_altered_fails, sound, toy)
from _toy_cell import SEQ, SOUND
from benchmark import reference, train_cell
from paddle_tpu.models import hybrid_trunk


def test_the_made_tree_is_the_leaf_maker_s(toy):
    _toy_cell.made_tree_is_the_leaf_maker_s(toy, [
        ("embed",), ("blocks", "mla_moe", "we_gate_up"),
        ("blocks", "mla_dense", "hc1_alpha")])


def test_the_toy_has_what_the_cell_has(toy):
    cfg = toy.cfg
    assert cfg.layer_types == ("mla_dense", "mla_moe", "mla_moe")
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("mla_dense", 0, 1), ("mla_moe", 0, 2)]
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert cfg.hc_mult == 4 and cfg.hc_sinkhorn_iters == 20
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok) == (8, 2, 2, 2)
    assert cfg.rope_scaling["type"] == "yarn"
    shapes = hybrid_trunk.kind_shapes(cfg, "mla_moe")
    assert shapes["we_gate_up"] == (2, 128, 256)        # rank 4 stacked
    assert shapes["w_router"] == (128, 8)               # published width
    assert shapes["hc1_phi"] == (4 * 128, 4 * 4 + 2 * 4)


@pytest.mark.parametrize("what", ["loss_rel_gap.step0", "loss_rel_gap.step1",
                                  "grad_norm_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 20 + 23 + 3
    assert train_cell.gap_numbers(sound, ref)[what] < SOUND


def test_the_two_step_change_matches_the_reference(sound, ref):
    numbers = train_cell.gap_numbers(sound, ref)
    assert numbers["param_change_worst_leaf_gap"] < SOUND_CHANGE, numbers
    # and every leaf but the mixers' is far inside it
    for path, want in ref["change"].items():
        if not path[-1].startswith("hc"):
            assert abs(sound["change"][path] - want) < 1e-4 * want, path


def test_logits_match_the_reference(toy):
    cfg, params = toy.cfg, toy.params0
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        from paddle_tpu.models import llama_pretrain
        x = jnp.take(params["embed"], ids[None], axis=0)
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["lm_head"])[0]
    rows = np.asarray([0, 1, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))


@pytest.mark.parametrize("what", sorted(REFERENCE)[:2])
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    reference_altered_fails(toy, sound, what)
