"""Layers by kind on the normal path: ``make_train_step`` over
``models/hybrid_trunk.py`` (a Mamba-2 mixer, NoPE GQA at its own score
scale, a residual multiplier, a tied and scaled head) held to the plain
reference ``benchmark/models/granite_hybrid_reference.py`` at toy size
(``tests/_hybrid_toy.py``) — float32 on the CPU, seeded weights.  Then
one line of the REFERENCE is changed at a time and the comparison must
fail; one key of the PROGRAM's configuration:
``test_hybrid_program_altered.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_cell
from _hybrid_toy import ref, sound, toy  # noqa: F401
from _toy_cell import (BROKEN, SEQ, SOUND, altered_reference,
                       follow_reference, worst_gap)
from benchmark import reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, adafactor_update, build_mesh,
    init_adafactor_state, make_train_step)


def test_the_made_tree_is_the_leaf_maker_s(toy):
    _toy_cell.made_tree_is_the_leaf_maker_s(toy, [
        ("embed",), ("blocks", "mamba", "w_in"),
        ("blocks", "attention", "wq")])


def test_the_toy_has_what_the_cell_has(toy):
    kinds = toy.cfg.layer_types
    assert kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert hybrid_trunk.layer_runs(kinds) == [
        ("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9)]
    assert toy.cfg.mamba_n_heads >= 2
    assert toy.cfg.num_attention_heads // toy.cfg.num_key_value_heads == 2
    assert SEQ // toy.cfg.mamba_chunk_size == 2
    assert toy.cfg.attention_multiplier != toy.cfg.head_dim ** -0.5


def test_two_steps_match_the_reference(sound, ref):
    numbers = train_cell.gap_numbers(sound, ref)
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 13 + 9 + 2
    assert max(numbers.values()) < SOUND, numbers


def test_logits_match_the_reference(toy):
    cfg, params = toy.cfg, toy.params0
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        x = jnp.take(params["embed"], ids[None], axis=0) \
            * cfg.embedding_multiplier
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["embed"].T / cfg.logits_scaling)[0]
    rows = np.asarray([0, 1, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))


# one line changed in the REFERENCE
REFERENCE = {
    "D_ignored": (' + w["D"][:, None] * xs.reshape(b, s, nh, p)', ""),
    "dt_bias_ignored": ('jax.nn.softplus(dt + w["dt_bias"])',
                        'jax.nn.softplus(dt + 0.0 * w["dt_bias"])'),
    "conv_not_causal": ("jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))",
                        "jnp.pad(x, ((0, 0), (0, k - 1), (0, 0)))"),
    "decay_ignored": ('log_a = -jnp.exp(w["A_log"]) * step',
                      'log_a = 0.0 * w["A_log"] * step'),
    "gate_ignored": ("y.reshape(b, s, di) * jax.nn.silu(z)",
                     "y.reshape(b, s, di) + 0.0 * z"),
}


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    other = follow_reference(
        toy, altered_reference("granite_hybrid", *REFERENCE[what]))
    assert worst_gap(sound, other) > BROKEN


def test_adafactor_takes_a_stacked_leaf_a_layer_at_a_time():
    """A leaf under ``blocks`` is clipped and scaled by each LAYER's
    own rms (the reference's one-tensor-a-layer rule); a top leaf by
    its own."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    scale = jnp.asarray([1.0, 10.0, 0.1])[:, None, None]
    params = {"blocks": {"w": jax.random.normal(ks[0], (3, 128, 256)) * scale,
                         "v": jax.random.normal(ks[1], (3, 64))},
              "embed": jax.random.normal(ks[2], (128, 256))}
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(ks[3], p.shape, p.dtype) * 5.0, params)
    params, grads = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), (params, grads))
    new, _ = adafactor_update(params, grads, init_adafactor_state(params),
                              lr=0.01, weight_decay=0.1)
    for leaf in ("w", "v"):
        for i in range(3):
            one = {"blocks": {leaf: params["blocks"][leaf][i:i + 1]}}
            g = {"blocks": {leaf: grads["blocks"][leaf][i:i + 1]}}
            alone, _ = adafactor_update(one, g, init_adafactor_state(one),
                                        lr=0.01, weight_decay=0.1)
            np.testing.assert_allclose(new["blocks"][leaf][i],
                                       alone["blocks"][leaf][0], rtol=1e-6)


def _hybrid_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, layer_types=("mamba", "attention"),
                mamba_n_heads=2, mamba_d_head=64, mamba_d_state=16)
    base.update(kw)
    return LlamaPretrainConfig(**base)


def test_what_the_trunk_by_kind_cannot_do_is_refused_by_name():
    with pytest.raises(ValueError, match="layer_types names"):
        _hybrid_cfg(layer_types=("mamba", "conv"))
    with pytest.raises(NotImplementedError, match="ONE B/C group"):
        _hybrid_cfg(mamba_n_groups=2)
    with pytest.raises(ValueError, match="needs mamba_n_heads"):
        _hybrid_cfg(mamba_n_heads=0)
    cfg = _hybrid_cfg()
    for axes in (dict(mp=2), dict(dp=2), dict(sep=2)):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        with pytest.raises(NotImplementedError, match="layers by kind"):
            make_train_step(cfg, mesh, optimizer="adafactor")
    with pytest.raises(NotImplementedError, match="layers by kind"):
        make_train_step(cfg, build_mesh(devices=jax.devices()[:2], pp=2),
                        pp=2, optimizer="adafactor")


def test_one_kind_and_no_multipliers_is_the_tree_it_was():
    """A configuration that states no kinds keeps ``blocks: {leaf}`` and
    an untied head; one that states them gets ``blocks: {kind: {leaf}}``,
    every leaf stated by ``param_specs``."""
    dense = LlamaPretrainConfig(vocab_size=64, hidden_size=64,
                                intermediate_size=128, num_hidden_layers=2,
                                num_attention_heads=4)
    specs = llama_pretrain.param_specs(dense, 1)
    assert set(specs) == {"embed", "blocks", "final_norm", "lm_head"}
    assert set(specs["blocks"]) == set(llama_pretrain._block_shapes(dense))
    cfg = _hybrid_cfg(tie_word_embeddings=True)
    specs = llama_pretrain.param_specs(cfg, 1)
    assert set(specs) == {"embed", "blocks", "final_norm"}
    mesh = build_mesh(devices=jax.devices()[:1])
    params = llama_pretrain.init_params(cfg, jax.random.PRNGKey(0), mesh)
    for kind in ("mamba", "attention"):
        shapes = hybrid_trunk.kind_shapes(cfg, kind)
        assert set(specs["blocks"][kind]) == set(shapes)
        for nm, shape in shapes.items():
            assert params["blocks"][kind][nm].shape == (1,) + shape
            assert len(specs["blocks"][kind][nm]) == len(shape) + 1
