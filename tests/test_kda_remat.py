"""Full remat keeps what ``kda_chunk_fwd`` wrote
(``ops/pallas/kda_chunk.FWD_OUTPUT_NAMES`` in the policy of
``models/llama_pretrain._remat_wrap`` for the kind ``kda_moe``, where
``hybrid_trunk.kept_outputs`` says the bytes fit): under the trunk's one
checkpoint boundary the forward kernel is in the gradient's program once
a run of delta-rule layers — twice with the budget short of them — and
the loss and every gradient are the same bits either way, and those of
``remat=False``.  The toy delta-rule trunk with its heads widened to one
lane tile, so that the recurrence is the kernels (in the interpreter),
and a block of ONE chunk: the kernels' bodies are what the CPU takes its
time to compile (15 s a program so, 38 s at four chunks a block).
"""

import dataclasses
import functools
import os
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import kda_chunk

TOY = os.path.join(harness.HERE, "tests", "toy")
# one period, a row of two blocks of one chunk
SEQ, ROWS, BLOCK_CHUNKS = 128, 1, 1


@functools.lru_cache(maxsize=None)
def _toy():
    """(cfg, blocks, x): the toy delta-rule configuration — gated GQA,
    three ``kda_moe`` layers — with ONE delta-rule head of 128 x 128, in
    the cell's dtypes."""
    conf = harness.load_json(os.path.join(TOY, "config_solar.json"))
    job = dict(harness.load_json(os.path.join(TOY, "train_job_solar.json")),
               seq=SEQ, batch=ROWS)
    cell = harness.Cell.detached("toy-solar.train_job", 1, conf, job)
    cfg = dataclasses.replace(cell.family.build_cfg(conf, True, job),
                              kda_num_heads=1, kda_head_dim=128)
    hybrid_trunk.check(cfg)
    assert cfg.remat and cfg.layer_types.count("kda_moe") == 3
    assert (cfg.dtype, cfg.param_dtype) == (jnp.bfloat16, jnp.float32)
    key = jax.random.PRNGKey(55)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (ROWS, SEQ, cfg.hidden_size), cfg.dtype)
    return cfg, hybrid_trunk.init_blocks(cfg, key), x


def _loss(cfg, blocks, x):
    out = hybrid_trunk.trunk(blocks, x, cfg, None)
    return jnp.mean(jnp.square(out.astype(jnp.float32)))


def _flash_bytes(cfg) -> int:
    return llama_pretrain.flash_output_bytes(
        ROWS, SEQ, cfg.num_attention_heads, cfg.head_dim, cfg.dtype,
        cfg.layer_types.count("gqa_gated_moe"))


@functools.lru_cache(maxsize=None)
def _program(mode: str):
    """The toy trunk's loss and gradients (of every leaf and of the
    input) and how often the gradient's program holds each kernel:
    ``kept`` under the module's own budget, ``recomputed`` with the
    budget at what flash keeps and not a byte more, ``no_remat`` with no
    boundary."""
    cfg, blocks, x = _toy()
    cfg = dataclasses.replace(cfg, remat=mode != "no_remat")
    bound = _flash_bytes(cfg) if mode == "recomputed" \
        else llama_pretrain.KEPT_BYTES
    fn = jax.jit(jax.value_and_grad(functools.partial(_loss, cfg),
                                    argnums=(0, 1)))
    with mock.patch.object(llama_pretrain, "KEPT_BYTES", bound), \
            mock.patch.object(kda_chunk, "BLOCK_CHUNKS", BLOCK_CHUNKS):
        jax.clear_caches()      # a traced loop body is kept by its avals
        assert hybrid_trunk.kept_outputs(cfg, ROWS, SEQ) == \
            (True, mode != "recomputed")
        traced = fn.trace(blocks, x)
        text = str(traced.jaxpr)
        runs = {k: len(re.findall(rf"name={k}\b", text))
                for k in ("kda_chunk_fwd", "kda_chunk_bwd", "flash_fwd")}
        return runs, traced.lower().compile()(blocks, x)


@pytest.mark.parametrize("mode,fwd_runs", [("kept", 1), ("recomputed", 2),
                                           ("no_remat", 1)])
def test_the_recompute_has_no_forward_kernel(mode, fwd_runs):
    """The three delta-rule layers are one run: a forward loop and a
    backward loop, the recompute in the second.  With the two outputs
    kept ``kda_chunk_fwd`` is in the first alone; the flash layer's
    forward is kept in both forms (its decision is made first)."""
    runs, _ = _program(mode)
    assert runs == {"kda_chunk_fwd": fwd_runs, "kda_chunk_bwd": 1,
                    "flash_fwd": 1}, runs


@pytest.mark.parametrize("other", ["recomputed", "no_remat"])
def test_kept_outputs_change_no_bit(other):
    """The kept o and entering states ARE the arrays the recompute would
    write again: the loss and every gradient with them kept equal those
    with them recomputed, and those with no boundary, bit for bit."""
    (loss, grads), (want, want_grads) = _program("kept")[1], \
        _program(other)[1]
    assert np.isfinite(float(loss)) and float(loss) == float(want)
    moved = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
        moved += bool(jnp.any(a != 0))
    assert moved > 20       # the gradients are gradients


@pytest.mark.parametrize("names,fwd_runs", [
    (kda_chunk.FWD_OUTPUT_NAMES[:1], 2), (kda_chunk.FWD_OUTPUT_NAMES[1:], 2),
    (kda_chunk.FWD_OUTPUT_NAMES, 1)], ids=["o", "entering", "both"])
def test_only_both_names_drop_the_kernel(names, fwd_runs):
    """Keeping one of the two alone buys nothing: the other still needs
    the run."""
    qkv = jax.ShapeDtypeStruct((1, 64, 3 * 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 64, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 64, 1), jnp.float32)
    fn = jax.checkpoint(
        lambda *a: jnp.sum(kda.kda_chunk(*a, 1).astype(jnp.float32) ** 2),
        policy=jax.checkpoint_policies.save_only_these_names(*names))
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(qkv, g, beta))
    assert len(re.findall(r"name=kda_chunk_fwd\b", text)) == fwd_runs
    assert len(re.findall(r"name=kda_chunk_bwd\b", text)) == 1


def test_the_fallback_names_nothing():
    """Heads the kernels do not take (the toy's own 64 x 64) run
    ``kda_chunked_xla``: nothing is named, nothing is kept, and the rule
    says so from the shapes."""
    cfg = dataclasses.replace(_toy()[0], kda_num_heads=2, kda_head_dim=64)
    assert hybrid_trunk.kept_outputs(cfg, ROWS, SEQ) == (True, False)
    qkv = jax.ShapeDtypeStruct((ROWS, SEQ, 3 * 2 * 64), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((ROWS, SEQ, 2 * 64), jnp.float32)
    beta = jax.ShapeDtypeStruct((ROWS, SEQ, 2), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda *a: kda.kda_chunk(*a, 2))(qkv, g, beta))
    assert "kda_out" not in text and "pallas_call" not in text
