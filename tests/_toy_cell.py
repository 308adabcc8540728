"""The toy training cell the trunk tests share (``tests/test_hybrid_trunk*``,
``test_mla_moe_*``, ``test_smallthinker_*``): a family's toy configuration
through the REAL ``make_train_step`` in float32 on the CPU, followed for
its first steps beside the family's plain reference
(``benchmark/models/<family>_reference.py``), and the two ways a test
breaks the comparison — one key of the program's configuration, one line
of the reference's source.

Built for a suite that runs a FILE a worker (``--dist loadfile``): the
fixtures are module-scoped, made by :func:`fixtures` and imported by
name, so a file pays for the ones its tests ask for and no other.  On
the CPU a toy step is seconds of tracing and compiling and a fraction of
a second of running, so what is built once a file is built once: the
seed's parameters (:attr:`Toy.params0`; the step donates its operands,
so :func:`follow` hands it a copy), and ``leaf0`` reads a leaf out of
that tree instead of compiling a program a leaf
(``train_cell.leaf_maker``, 46 programs for the expert toy;
``test_the_made_tree_is_the_leaf_maker_s`` holds the two to the same
bits).
"""

import dataclasses
import functools
import operator
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness, train_cell
from paddle_tpu.models.llama_pretrain import (
    build_mesh, init_adafactor_state, make_train_step)

TOY = os.path.join(harness.HERE, "tests", "toy")
SEED, SEQ, ROWS = 2**31 + 77, 256, 2
SOUND, BROKEN = 1e-5, 1e-3


class Toy:
    """``toy-<name>.train_job`` at ``ROWS`` x ``SEQ`` tokens in float32:
    the cell, its configuration and job, the program's ``cfg`` and the
    first two batches."""

    def __init__(self, name, config, job):
        self.conf = harness.load_json(os.path.join(TOY, config))
        self.job = dict(harness.load_json(os.path.join(TOY, job)),
                        seq=SEQ, batch=ROWS)
        self.cell = harness.Cell.detached(f"toy-{name}.train_job", 1,
                                          self.conf, self.job)
        self.cfg = dataclasses.replace(
            self.cell.family.build_cfg(self.conf, True, self.job),
            dtype=jnp.float32)
        self.batches = [
            np.stack([train_cell.token_row(SEED, ROWS * s + r, SEQ,
                                           self.conf["vocab_size"])
                      for r in range(ROWS)]) for s in range(2)]

    @functools.cached_property
    def params0(self):
        """The seed's parameters, made once; never handed to a step."""
        mesh = build_mesh(devices=jax.devices()[:1])
        with mesh:
            return self.cell.family.make_params(self.cfg, SEED, mesh)

    def leaf0(self, path):
        return _leaf(self.params0, path)


def _leaf(tree, path):
    """A copy: the reference donates the leaves it is handed."""
    return jnp.copy(functools.reduce(operator.getitem, path, tree))


def follow(toy, cfg, steps=2, extra_leaves=None):
    """The program's first ``steps`` steps under ``cfg``: losses, the
    first gradient's norm and the change, leaf by leaf.
    ``extra_leaves(params)``: leaves the altered ``cfg`` needs beside
    the seed's."""
    start = dict(toy.params0)
    start.update(extra_leaves(start) if extra_leaves else {})
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = jax.tree_util.tree_map(jnp.copy, start)
        step = make_train_step(cfg, mesh, lr=toy.job["lr"],
                               weight_decay=toy.job["weight_decay"],
                               optimizer="adafactor")
        return train_cell.follow_program(
            step, params, init_adafactor_state(params),
            toy.batches[:steps], functools.partial(_leaf, start))[2]


def layer_of(toy, kind, layer=0):
    """One layer's leaves out of the kind's stack."""
    return {nm: leaf[layer] for nm, leaf in
            toy.params0["blocks"][kind].items()}


@functools.lru_cache(maxsize=None)
def _block_case(toy, kind, streams):
    """The kind's first layer, a seeded input of ``streams`` residual
    streams, and the plain reference's block on them."""
    bp = layer_of(toy, kind)
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (1, SEQ, streams * toy.cfg.hidden_size),
                          jnp.float32)
    blk = toy.cell.block_reference
    want = jax.jit(lambda bp, x: blk.KINDS[kind][1](
        x, bp, blk.dims_of(toy.conf))[0])(bp, x)
    return bp, x, want


def block_gap(toy, cfg, kind, body, streams=1):
    """How far the PROGRAM's block of ``kind`` (``body(leaves, x, cfg)``)
    under ``cfg`` lies from the plain reference's under the toy's own
    configuration: one layer of the seed's leaves, one seeded input, the
    norm of the outputs' difference over the reference's.  What a
    one-place alteration of a BLOCK changes shows here in a program of
    one layer (seconds) as it does in the first step of the whole toy."""
    bp, x, want = _block_case(toy, kind, streams)
    got = jax.jit(lambda bp, x: body(bp, x, cfg))(bp, x)
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def follow_reference(toy, block_reference=None, steps=2):
    """The plain reference's first ``steps`` steps, by the family's
    blocks or by ``block_reference`` in their place."""
    cell = toy.cell if block_reference is None else types.SimpleNamespace(
        block_reference=block_reference, conf=toy.conf)
    return train_cell.run_reference(cell, toy.job, toy.leaf0,
                                    toy.batches[:steps])


def first_step_gap(prog, ref):
    """Loss of the steps both followed and the first gradient, leaf by
    leaf."""
    numbers = train_cell.gap_numbers(prog, ref)
    return max(v for k, v in numbers.items()
               if k != "param_change_worst_leaf_gap")


def worst_gap(prog, ref):
    return max(train_cell.gap_numbers(prog, ref).values())


def altered_reference(family: str, old: str, new: str):
    """The family's reference module with ONE place of its source
    changed (the needle may span lines in the file)."""
    path = os.path.join(harness.HERE, "models", f"{family}_reference.py")
    with open(path) as f:
        src = f.read()
    pattern = r"\s+".join(re.escape(w) for w in old.split())
    src, n = re.subn(pattern, lambda m: new, src)
    assert n == 1, old
    mod = types.ModuleType(f"benchmark.models.{family}_altered")
    mod.__package__ = "benchmark.models"
    exec(compile(src, path, "exec"), mod.__dict__)
    return mod


def made_tree_is_the_leaf_maker_s(toy, paths):
    """``toy.leaf0`` reads ``make_params``'s tree; the benchmark's run
    hands its reference ``train_cell.leaf_maker``'s leaves: the same
    bits, on ``paths``."""
    fam = toy.cell.family
    maker = train_cell.leaf_maker(fam, toy.cfg, fam.seed_key(SEED))
    for path in paths:
        np.testing.assert_array_equal(np.asarray(toy.leaf0(path)),
                                      np.asarray(maker(path)), str(path))


def fixtures(name, config, job="train_job.json"):
    """``toy, sound, ref = fixtures(...)`` at a module's top level: the
    cell, the sound program's two steps, the reference's two steps."""
    @pytest.fixture(scope="module")
    def toy():
        return Toy(name, config, job)

    @pytest.fixture(scope="module")
    def sound(toy):
        return follow(toy, toy.cfg)

    @pytest.fixture(scope="module")
    def ref(toy):
        return follow_reference(toy)
    return toy, sound, ref
