"""How ``correct`` is decided, at a size a test run can hold (the toy
configuration, on the CPU):

* the plain reference agrees with the program (serving logits through
  the paged cache, training loss / gradient / update);
* the CONTROL — the reference with int8 matrix products — comes out as
  not correct against the same limits;
* a run whose timed path is broken underneath (a token altered where it
  is produced; a step that returns its state unchanged) reports
  ``correct`` false.
"""

import types

import numpy as np
import pytest

from benchmark import harness, reference, rehearse, serve_cell, train_cell


@pytest.fixture
def cpu(monkeypatch):
    """The rehearsal's patches (no look for a chip), undone afterwards."""
    saved = {k: getattr(harness, k) for k in
             ("require_chips", "memory_peak_bytes", "result_line", "log")}
    logs = (serve_cell.log, train_cell.log)
    seen = rehearse.patch_for_cpu(harness)
    yield seen
    for k, v in saved.items():
        setattr(harness, k, v)
    serve_cell.log, train_cell.log = logs


def args(like, seconds=2.0, seed=2**31 + 99, trace=0):
    return types.SimpleNamespace(workload=like, seed=seed, seconds=seconds,
                                 trace=trace)


TRAIN, SERVE = "train_job", "open_loop"


def test_sound_runs_are_correct(cpu):
    assert train_cell.run(args(TRAIN), rehearse.toy_cell(TRAIN)) == 0
    assert cpu["correct"] is True
    assert serve_cell.run(args(SERVE), rehearse.toy_cell(SERVE)) == 0
    assert cpu["correct"] is True and cpu["failed"] == 0


def test_altered_token_is_not_correct(cpu):
    def alter(srv):
        eng, vocab = srv.engine, srv.engine.cfg.vocab_size
        plain = eng.drain_stream
        eng.drain_stream = lambda: [(rid, (tok + 1) % vocab)
                                    for rid, tok in plain()]
    serve_cell.run(args(SERVE), rehearse.toy_cell(SERVE),
                   token_override=alter)
    assert cpu["correct"] is False


def test_step_that_returns_its_state_unchanged_is_not_correct(cpu):
    import jax
    import jax.numpy as jnp

    def frozen(compiled):
        def step(params, opt, tokens):
            keep = jax.tree_util.tree_map(jnp.copy, (params, opt))
            _, _, loss = compiled(params, opt, tokens)
            return keep[0], keep[1], loss
        return step
    train_cell.run(args(TRAIN), rehearse.toy_cell(TRAIN),
                   step_override=frozen)
    assert cpu["correct"] is False


def test_training_control_fails_a_limit(cpu):
    """int8 products in place of the program: one of the cell's numbers
    has to pass its limit."""
    import jax
    import jax.numpy as jnp
    cell = rehearse.toy_cell(TRAIN)
    job, fam = cell.traffic, cell.family
    cfg = fam.build_cfg(cell.conf, train=True, job=job)
    key = fam.seed_key(5)
    leaf0 = train_cell.leaf_maker(fam, cfg, key)
    rng = np.random.default_rng(5)
    batches = [rng.integers(0, cfg.vocab_size,
                            (job["batch"], job["seq"] + 1))
               for _ in range(job["reference_steps"])]
    ref = train_cell.run_reference(cell, job, leaf0, batches)
    low = train_cell.run_reference(cell, job, leaf0, batches, "int8")
    checks = harness.Checks()
    train_cell.compare(checks, job, low, ref)
    assert checks.ok is False


def test_serving_control_fails_the_limit():
    import jax
    cell = rehearse.toy_cell(SERVE)
    fam = cell.family
    cfg = fam.build_cfg(cell.conf, train=False)
    from paddle_tpu.models.llama_pretrain import build_mesh
    params = fam.make_params(cfg, 5, build_mesh(devices=jax.devices()[:1]))
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 150).tolist()
    served = rng.integers(1, cfg.vocab_size, 40).tolist()
    low = reference.serve_gaps(cell.block_reference, params, cell.conf,
                               prompt, served, control=True)
    assert low.max() > cell.traffic["limits"]["served_logit_gap_max"]
