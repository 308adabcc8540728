"""Parts of the expert cell's trunk with tests of their own: the
mixer's Sinkhorn maps, YaRN's frequencies, adafactor on a rank-4 expert
stack, the split flash forward's outputs kept under full remat, the
layouts refused by name.
"""

import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _mla_moe_toy import toy  # noqa: F401
from _toy_cell import SEED
from benchmark import reference
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    adafactor_update, build_mesh, init_adafactor_state, make_forward)


# -- the mixer ---------------------------------------------------------------
def test_sinkhorn_gives_a_doubly_stochastic_map_that_differs_by_token(toy):
    cfg = toy.cfg
    n, c = cfg.hc_mult, cfg.hidden_size
    key = toy.cell.family.seed_key(SEED)
    bp = {nm: toy.leaf0(("blocks", "mla_moe", nm))[0]
          for nm in ("hc1_phi", "hc1_alpha", "hc1_b")}
    x = jax.random.normal(key, (2, 64, n * c), jnp.float32)
    h_pre, h_post, h_res = hybrid_trunk.hc_maps(bp, "hc1", x, cfg)
    r = jnp.stack([jnp.stack(row, -1) for row in h_res], -2)[..., 0, :, :]
    assert r.shape == (2, 64, n, n)
    # the columns were normalised last: exact but for hc_eps; the rows
    # are where twenty rounds have got to (read: 3.2e-5)
    assert float(jnp.max(jnp.abs(r.sum(-2) - 1))) < 1e-5       # columns
    assert float(jnp.max(jnp.abs(r.sum(-1) - 1))) < 1e-4       # rows
    assert float(jnp.min(r)) > 0
    # data-dependent: the map is not one matrix for all tokens
    assert float(jnp.std(r, axis=(0, 1)).min()) > 1e-2
    pre = jnp.concatenate(h_pre, -1)
    post = jnp.concatenate(h_post, -1)
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2


def test_yarn_blends_the_frequencies_between_their_own_and_the_scaled():
    sc = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
          "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
          "type": "yarn"}
    inv = hybrid_trunk.yarn_inv_freq(64, 10000.0, sc)
    plain = hybrid_trunk.yarn_inv_freq(64, 10000.0, None)
    assert inv.shape == (32,)
    assert np.allclose(inv[:10], plain[:10])            # fast pairs: kept
    assert np.allclose(inv[-8:], plain[-8:] / 64)       # slow pairs: scaled
    assert np.all(np.diff(inv) < 0) and np.all(inv <= plain * (1 + 1e-6))
    from benchmark.models import xing_mhc_moe_reference as blk
    assert np.allclose(inv, blk.yarn_frequencies(64, 10000.0, sc),
                       rtol=1e-6)
    assert abs(hybrid_trunk.yarn_mscale(sc, "mscale_all_dim")
               - (0.1 * np.log(64) + 1)) < 1e-12


# -- adafactor ---------------------------------------------------------------
def test_adafactor_takes_a_rank_4_stack_an_expert_matrix_at_a_time():
    """``[L, E, in, out]``: the second moment is factored over the last
    two axes of each expert's matrix, the update clipped and scaled by
    ONE LAYER's leaf — the reference's ``_adafactor_leaf`` on ``[E, in,
    out]``, layer by layer."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    scale = jnp.asarray([1.0, 10.0, 0.1])[:, None, None, None]
    p = jax.random.normal(ks[0], (3, 4, 128, 256), jnp.float32) * scale
    g = jax.random.normal(ks[1], p.shape, jnp.float32) * 5.0
    params, grads = {"blocks": {"moe": {"w": p}}}, {"blocks": {"moe": {"w": g}}}
    state = init_adafactor_state(params)
    assert state["moments"]["blocks"]["moe"]["w"]["vr"].shape == (3, 4, 128)
    assert state["moments"]["blocks"]["moe"]["w"]["vc"].shape == (3, 4, 256)
    new, state = adafactor_update(params, grads, state, lr=0.01,
                                  weight_decay=0.1)
    new, _ = adafactor_update(new, grads, state, lr=0.01, weight_decay=0.1)
    for layer in range(3):
        want, st = p[layer], reference._opt_init(p[layer])
        for t in (1.0, 2.0):
            want, st = reference._adafactor_leaf(
                want, g[layer], st, jnp.asarray(t, jnp.float32), 0.01, 0.1)
        got = new["blocks"]["moe"]["w"][layer]
        assert float(jnp.max(jnp.abs(got - want))) \
            < 1e-6 * float(jnp.max(jnp.abs(want)))


def test_full_remat_keeps_the_split_forward_s_outputs(toy, monkeypatch):
    """``flash_attention_split`` through ``_mla_block`` under the trunk's
    checkpoint boundary: ``flash_fwd`` is in the program once a run of
    layers (the forward scan's body) where its outputs are kept, twice
    (the backward scan's too) with ``KEPT_BYTES`` at 0, and the
    loss and every gradient are the same bits."""
    mesh = build_mesh(devices=jax.devices()[:1])
    runs = len(hybrid_trunk.layer_runs(toy.cfg.layer_types))
    ids = jnp.asarray(toy.batches[0])

    def loss_and_grads(flash_fwd_runs):
        with mesh:
            params = toy.params0
            traced = jax.jit(jax.value_and_grad(
                make_forward(toy.cfg, mesh))).trace(params, ids)
            assert len(re.findall(r"name=flash_fwd\b",
                                  str(traced.jaxpr))) == flash_fwd_runs
            return traced.lower().compile()(params, ids)

    kept, kept_g = loss_and_grads(runs)
    monkeypatch.setattr(llama_pretrain, "KEPT_BYTES", 0)
    again, again_g = loss_and_grads(2 * runs)
    assert float(kept) == float(again)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept_g),
                            jax.tree_util.tree_leaves(again_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_more_than_one_device_is_refused_by_name(toy):
    mesh = types.SimpleNamespace(shape={"dp": 2, "mp": 1})
    with pytest.raises(NotImplementedError, match="mla_moe"):
        hybrid_trunk.check_layout(toy.cfg, mesh, 1)
