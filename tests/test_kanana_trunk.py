"""The whole toy model of the family ``kanana_mla_moe`` through the REAL
``make_train_step`` (``models/hybrid_trunk.py``'s kinds ``mla_dense`` /
``mla_moe`` on ONE residual stream with a direct query) against
``benchmark/models/kanana_mla_moe_reference.py`` on seeded weights —
loss, every leaf's first gradient, the two-step change under adafactor —
and the shares adding up to the uncut expert layer."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_cell
from _kanana_toy import ref, sound, toy  # noqa: F401
from _toy_cell import SEQ, SOUND
from benchmark import reference, train_cell
from benchmark.models import kanana_mla_moe_reference as blk
from paddle_tpu.models import hybrid_trunk

F32 = jnp.float32


def test_the_made_tree_is_the_leaf_maker_s(toy):
    _toy_cell.made_tree_is_the_leaf_maker_s(toy, [
        ("embed",), ("blocks", "mla_moe", "we_gate_up"),
        ("blocks", "mla_dense", "w_q_rope")])


def test_the_toy_has_what_the_cell_has(toy):
    cfg = toy.cfg
    assert cfg.layer_types == ("mla_dense", "mla_moe", "mla_moe")
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("mla_dense", 0, 1), ("mla_moe", 0, 2)]
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.rope_scaling) == (1, 0, None)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.n_shared_experts) == (8, 2, 2, 3, 2)
    assert (cfg.routed_scaling_factor, cfg.rope_theta) == (2.448, 1e6)


@pytest.mark.parametrize("what", [
    "loss_rel_gap.step0", "loss_rel_gap.step1", "grad_norm_worst_leaf_gap",
    "param_change_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    """fp32 against fp32 to ``_toy_cell.SOUND`` (measured 1.5e-7, 0,
    2.1e-7, 2.7e-7): with no mixer's ``alpha`` among the leaves the
    two-step change is held as tightly as the gradient."""
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 12 + 15 + 3
    assert train_cell.gap_numbers(sound, ref)[what] < SOUND


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


def test_the_shares_and_the_shared_experts_once_are_the_whole_layer(toy):
    """The four shares' routed parts (experts 0-1, 2-3, 4-5, 6-7 of the
    toy's 8; 0-31 .. 96-127 of the cell's 128) plus the two shared
    experts counted ONCE are what the UNCUT reference gives for the whole
    expert layer; a share alone, with the shared experts every chip
    computes, is the reference's share."""
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    n = lambda k, shape, fan: jax.random.normal(k, shape, F32) / fan ** 0.5
    w = {"w_router": n(ks[0], (c, 8), c),
         "we_gate_up": n(ks[1], (8, c, 2 * f), c),
         "we_down": n(ks[2], (8, f, c), f),
         "ws_gate": n(ks[3], (c, 2 * f), c), "ws_up": n(ks[4], (c, 2 * f), c),
         "ws_down": n(ks[5], (2 * f, c), 2 * f)}
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 300, c), F32)
    dims = blk.dims_of(dict(toy.conf, n_routed_experts=8, expert_first=0))
    whole = dict(dims)
    mm = lambda a, b: reference.matmul(a, b, "f32")
    _, g = blk._route(u, w, whole, mm)
    assert float(jnp.max(jnp.abs(jnp.sum(g, -1) - 2.448))) < 1e-5
    want = blk._experts(u, w, whole, mm)
    shared = blk._swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], mm)

    def share(first, held=2):
        return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                    we_down=w["we_down"][first:first + held])

    def part(first):
        cfg = dataclasses.replace(toy.cfg, expert_first=first,
                                  experts_held=2)
        return hybrid_trunk._expert_layer(share(first), u, cfg)
    parts = [part(first) for first in (0, 2, 4, 6)]
    scale = float(jnp.max(jnp.abs(want)))
    # every share computed the shared experts: three of the four are
    # taken off again
    assert float(jnp.max(jnp.abs(sum(parts) - 3 * shared - want))) \
        < SOUND * scale
    one = blk._experts(u, share(2), dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] - one))) < SOUND * scale
    assert all(float(jnp.max(jnp.abs(p - shared))) > 0.01 * scale
               for p in parts)
    # and attention is computed once whatever the share: the block of a
    # share differs from the block of another by the routed parts alone
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 256, c), F32)
    bp = dict(_toy_cell.layer_of(toy, "mla_moe"), **w)
    block = lambda first: hybrid_trunk._mla_block(
        dict(bp, **share(first)), x, dataclasses.replace(
            toy.cfg, expert_first=first, experts_held=2))
    layer = sum(block(first) for first in (0, 2, 4, 6))
    uncut = blk.moe_block(x, bp, dims)[0]
    # four blocks hold the stream, attention and the shared experts four
    # times; an expert layer's own input is the same in all four
    h = x + blk._attention(reference.rms_norm(x, bp["ln1"], 1e-6), bp,
                           whole, mm)
    once = h + blk._swiglu(reference.rms_norm(h, bp["ln2"], 1e-6),
                           w["ws_gate"], w["ws_up"], w["ws_down"], mm)
    assert float(jnp.max(jnp.abs(layer - 3 * once - uncut))) \
        < 4 * SOUND * float(jnp.max(jnp.abs(uncut)))
