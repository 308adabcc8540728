"""Layers by kind on the normal path: ``make_train_step`` over
``models/hybrid_trunk.py`` (a Mamba-2 mixer, NoPE GQA at its own score
scale, a residual multiplier, a tied and scaled head) held to the plain
reference ``benchmark/models/granite_hybrid_reference.py`` at toy size —
float32 on the CPU, the published PATTERN (one period of ten: five
state-space layers, one attention layer, four more), two state-space
heads, a GQA group of 2, two chunks a row so that the state crosses a
chunk, seeded weights.  Then one thing is changed at a time, in the
program or in the reference, and the comparison must fail.
"""

import dataclasses
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness, reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, adafactor_update, build_mesh,
    init_adafactor_state, make_train_step)

TOY = os.path.join(harness.HERE, "tests", "toy")
SEED, SEQ, ROWS = 2**31 + 77, 256, 2
SOUND, BROKEN = 1e-5, 1e-3


@pytest.fixture(scope="module")
def toy():
    conf = harness.load_json(os.path.join(TOY, "config_granite.json"))
    job = dict(harness.load_json(os.path.join(TOY, "train_job.json")),
               seq=SEQ, batch=ROWS)
    cell = harness.Cell.detached("toy-granite.train_job", 1, conf, job)
    cfg = dataclasses.replace(cell.family.build_cfg(conf, True, job),
                              dtype=jnp.float32)
    key = cell.family.seed_key(SEED)
    batches = [np.stack([train_cell.token_row(SEED, ROWS * s + r, SEQ,
                                              conf["vocab_size"])
                         for r in range(ROWS)]) for s in range(2)]
    return types.SimpleNamespace(
        cell=cell, conf=conf, job=job, cfg=cfg, batches=batches,
        leaf0=train_cell.leaf_maker(cell.family, cfg, key))


def follow(toy, cfg, extra_leaves=None):
    """The program's two steps under ``cfg``: losses, the first
    gradient's norm and the two-step change, leaf by leaf."""
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = toy.cell.family.make_params(toy.cfg, SEED, mesh)
        params.update(extra_leaves(params) if extra_leaves else {})
        step = make_train_step(cfg, mesh, lr=toy.job["lr"],
                               weight_decay=toy.job["weight_decay"],
                               optimizer="adafactor")
        # an untied head starts as the table's transpose
        leaf0 = lambda path: toy.leaf0(("embed",)).T \
            if path == ("lm_head",) else toy.leaf0(path)
        return train_cell.follow_program(
            step, params, init_adafactor_state(params), toy.batches,
            leaf0)[2]


def gaps(prog, ref):
    return max(train_cell.gap_numbers(prog, ref).values())


@pytest.fixture(scope="module")
def sound(toy):
    return follow(toy, toy.cfg)


@pytest.fixture(scope="module")
def ref(toy):
    return train_cell.run_reference(toy.cell, toy.job, toy.leaf0,
                                    toy.batches)


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
    cfg, fam = toy.cfg, toy.cell.family
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = fam.make_params(cfg, SEED, mesh)
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


def _untied(params):
    return {"lm_head": params["embed"].T.copy()}


# one thing changed in the PROGRAM's configuration
PROGRAM = {
    "pattern_shifted_by_a_layer": lambda c, full: dict(
        layer_types=tuple(full[1:1 + c.num_hidden_layers])),
    "residual_multiplier_dropped": lambda c, full: dict(
        residual_multiplier=1.0),
    "score_scale_one_over_sqrt_d": lambda c, full: dict(
        attention_multiplier=None),
    "rope_left_on": lambda c, full: dict(position_embedding_type="rope"),
    "embedding_multiplier_dropped": lambda c, full: dict(
        embedding_multiplier=1.0),
    "logits_not_divided": lambda c, full: dict(logits_scaling=1.0),
    "tie_broken": lambda c, full: dict(tie_word_embeddings=False),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    change = PROGRAM[what](toy.cfg, toy.conf["layer_types"])
    cfg = dataclasses.replace(toy.cfg, **change)
    prog = follow(toy, cfg, _untied if what == "tie_broken" else None)
    assert gaps(prog, ref) > BROKEN


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


def altered_reference(old: str, new: str):
    path = os.path.join(harness.HERE, "models",
                        "granite_hybrid_reference.py")
    with open(path) as f:
        src = f.read()
    assert src.count(old) == 1, old
    mod = types.ModuleType("benchmark.models.granite_hybrid_altered")
    mod.__package__ = "benchmark.models"
    exec(compile(src.replace(old, new), path, "exec"), mod.__dict__)
    return mod


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    cell = types.SimpleNamespace(
        block_reference=altered_reference(*REFERENCE[what]),
        conf=toy.conf)
    other = train_cell.run_reference(cell, toy.job, toy.leaf0, toy.batches)
    assert gaps(sound, other) > BROKEN


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
