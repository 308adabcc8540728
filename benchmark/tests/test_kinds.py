"""Layers by kind and the family's two ends, against whole-model
autodiff: no program involved.  The toy family ``hybrid_block``
(``toy/hybrid_block_reference.py``) has two kinds of layer in a pattern
with a period, cut to ``num_hidden_layers``; the second kind's mixer is
no attention; the table is tied, the embedding multiplied by 12, the
logits divided by 8.  ``toy/hybrid_program.py`` is ONE plain function
of the whole model.  On the CPU: verdicts, never a time."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from benchmark import models, reference

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
JOB = {"lr": 0.01, "weight_decay": 0.1}
REL = 1e-5


def load(name, source=None):
    """A toy file as a module; ``source`` replaces its text."""
    path = os.path.join(TOY, name + ".py")
    spec = importlib.util.spec_from_file_location("toy_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    if source is None:
        spec.loader.exec_module(mod)
    else:
        exec(compile(source, path, "exec"), mod.__dict__)
    return mod


def text(name):
    with open(os.path.join(TOY, name + ".py")) as f:
        return f.read()


# what a later edit could get wrong, each by one line of the reference
ALTERED = {
    "pattern_shifted_by_a_layer": (
        'conf["layer_types"][:conf["num_hidden_layers"]]',
        'conf["layer_types"][1:conf["num_hidden_layers"] + 1]'),
    "multiplier_dropped_from_the_first_input": (
        'return dims[4] * jnp.take(', 'return jnp.take('),
    "tie_broken_the_head_leaves_the_table_no_gradient": (
        'top["embed"].T,', 'jax.lax.stop_gradient(top["embed"]).T,'),
}


def altered(what):
    old, new = ALTERED[what]
    src = text("hybrid_block_reference")
    assert src.count(old) == 1, what
    return load("hybrid_block_reference", src.replace(old, new))


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(TOY, "config_hybrid.json")) as f:
        conf = json.load(f)
    fam, prog = load("hybrid_block"), load("hybrid_program")
    cfg = fam.build_cfg(conf, train=True)
    key = fam.seed_key(2**31 + 7)
    p0 = {path: fam.make_leaf(cfg, key, path) for path in fam.leaf_shapes(cfg)}
    rng = np.random.default_rng(7)
    batches = [rng.integers(0, conf["vocab_size"], (4, 65))
               for _ in range(2)]
    return conf, fam, prog, p0, batches


def by_autodiff(toy):
    """Loss of each step, the first gradient's norm and the two-step
    change per stacked leaf: ``jax.value_and_grad`` of the one plain
    function, ``_adafactor_leaf`` applied to each LAYER's leaf."""
    import jax
    import jax.numpy as jnp
    conf, fam, prog, p0, batches = toy
    tree = models.tree_of(p0, p0.__getitem__)
    flat = lambda t: {tuple(k.key for k in path): a for path, a in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    now = dict(p0)
    opt = {path: [reference._opt_init(a) for a in p] if path[0] == "blocks"
           else reference._opt_init(p) for path, p in p0.items()}
    out = {"loss": []}
    for t, tokens in enumerate(batches, 1):
        value, grads = jax.value_and_grad(prog.loss)(
            tree, jnp.asarray(tokens), conf)
        out["loss"].append(float(value))
        grads = flat(grads)
        if t == 1:
            out["grad"] = {k: float(jnp.linalg.norm(g.ravel()))
                           for k, g in grads.items()}
        for path, g in grads.items():
            step = lambda p, g, st: reference._adafactor_leaf(
                jnp.array(p), g, st, jnp.asarray(t, jnp.float32),
                JOB["lr"], JOB["weight_decay"])
            if path[0] == "blocks":
                new = [step(now[path][j], g[j], opt[path][j])
                       for j in range(g.shape[0])]
                now[path] = jnp.stack([n[0] for n in new])
                opt[path] = [n[1] for n in new]
            else:
                now[path], opt[path] = step(now[path], g, opt[path])
        tree = models.tree_of(now, now.__getitem__)
    out["change"] = {k: float(jnp.linalg.norm((now[k] - p0[k]).ravel()))
                     for k in p0}
    return out


def by_the_machinery(toy, blk):
    import jax.numpy as jnp
    conf, _, _, p0, batches = toy
    # a new array each time, as the harness's maker gives: the
    # reference's adafactor donates its parameters
    ref = reference.TrainReference(blk, conf,
                                   lambda path: jnp.array(p0[path]), JOB)
    return {"loss": [ref.step(b) for b in batches], "grad": ref.grad_norms,
            "change": ref.change_norms()}


def worst(got, want):
    return max(abs(got[k] - want[k]) / abs(want[k]) for k in want)


def test_two_kinds_a_tied_table_and_multipliers_meet_whole_model_autodiff(toy):
    conf, fam, _, p0, _ = toy
    got = by_the_machinery(toy, load("hybrid_block_reference"))
    want = by_autodiff(toy)
    # the pattern has a period and is cut: three of one kind, one of
    # the other, the attention layer third
    assert fam.layer_kinds(conf) == ("mix", "mix", "attention", "mix")
    assert set(got["grad"]) == set(want["grad"]) == set(p0)
    assert ("lm_head",) not in got["grad"]
    for a, b in zip(got["loss"], want["loss"]):
        assert a == pytest.approx(b, rel=REL)
    assert worst(got["grad"], want["grad"]) < REL
    assert worst(got["change"], want["change"]) < REL


def test_forward_rows_gives_the_whole_models_logits(toy):
    import jax.numpy as jnp
    conf, fam, prog, p0, batches = toy
    tree = models.tree_of(p0, p0.__getitem__)
    tokens = batches[0][0, :50]
    rows = np.array([0, 17, 49])
    got = reference.forward_rows(load("hybrid_block_reference"), tree, conf,
                                 tokens, rows)
    want = np.asarray(prog.logits(tree, jnp.asarray(tokens)[None], conf))
    assert got.shape == (3, conf["vocab_size"])
    np.testing.assert_allclose(got, want[0, rows], rtol=REL,
                               atol=REL * np.abs(want).max())


@pytest.mark.parametrize("what", sorted(ALTERED))
def test_an_altered_reference_leaves_whole_model_autodiff(toy, what):
    """Each fault moves one of the numbers compared far past the
    agreement of the sound family."""
    got = by_the_machinery(toy, altered(what))
    want = by_autodiff(toy)
    gaps = [abs(got["loss"][1] - want["loss"][1]) / want["loss"][1],
            worst(got["grad"], want["grad"]),
            worst(got["change"], want["change"])]
    assert max(gaps) > 1e-2, gaps


def test_the_int8_control_reaches_the_familys_head(toy):
    """The family's ``logits`` goes through ``reference.matmul``: with
    int8 products the head's logits move."""
    conf, fam, _, p0, batches = toy
    tree = models.tree_of(p0, p0.__getitem__)
    blk = load("hybrid_block_reference")
    rows = np.array([5, 40])
    f32, low = (reference.forward_rows(blk, tree, conf, batches[0][0, :50],
                                       rows, precision)
                for precision in ("f32", "int8"))
    assert np.abs(f32 - low).max() > 1e-3 * np.abs(f32).max()


@pytest.mark.parametrize("missing", ["TOP_LEAVES", "first_input", "logits"])
def test_the_two_ends_are_stated_together_or_not_at_all(toy, missing):
    blk = load("hybrid_block_reference")
    delattr(blk, missing)
    with pytest.raises(ValueError, match="not all three"):
        reference.Model(blk, toy[0])


def test_kinds_that_do_not_cover_the_depth_are_refused(toy):
    conf = dict(toy[0], num_hidden_layers=7)        # six are published
    with pytest.raises(ValueError, match="layer_kinds"):
        reference.Model(load("hybrid_block_reference"), conf)


def test_a_top_leaf_that_neither_end_reads_is_refused(toy):
    conf, _, _, p0, _ = toy
    blk = load("hybrid_block_reference")
    blk.TOP_LEAVES = blk.TOP_LEAVES + ("lm_head",)
    top = {"embed": p0[("embed",)], "final_norm": p0[("final_norm",)],
           "lm_head": p0[("embed",)].T}
    with pytest.raises(ValueError, match="lm_head"):
        reference.Model(blk, conf).reads(top)
