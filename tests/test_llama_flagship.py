"""Flagship LLaMA tests: Layer model, functional pretrain engine,
hybrid-mesh train step, graft entry.

``test_graft_entry`` is the suite's longest case by far (five minutes of
one worker under the other five's load, ~1,200 core-seconds: it RUNS the
8-device programs of ``__graft_entry__.dryrun_multichip``) and stays in
this file of 23 cases on purpose: xdist hands files out by their NUMBER
OF TESTS, largest first, so a one-case file would start last and be the
run's tail (read: +67 s; PERF.md section 6)."""

import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle


def tiny_cfg(**kw):
    from paddle_tpu.models import LlamaConfig
    base = dict(vocab_size=64, hidden_size=32, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=32, tensor_parallel=False)
    base.update(kw)
    return LlamaConfig(**base)


def test_llama_layer_forward_and_loss():
    from paddle_tpu.models import LlamaForCausalLM
    model = LlamaForCausalLM(tiny_cfg())
    ids = paddle.randint(0, 64, [2, 16])
    logits = model(ids)
    assert logits.shape == [2, 16, 64]
    loss = model(ids, labels=ids)
    assert loss.size == 1
    loss.backward()
    grads = [p for p in model.parameters() if p.grad is not None]
    assert len(grads) == len(model.parameters())


def test_llama_generate():
    from paddle_tpu.models import LlamaForCausalLM
    model = LlamaForCausalLM(tiny_cfg())
    ids = paddle.randint(0, 64, [1, 4])
    out = model.generate(ids, max_new_tokens=4)
    assert out.shape == [1, 8]


@pytest.mark.slow
def test_llama_train_converges():
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(0)
    model = LlamaForCausalLM(tiny_cfg())
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    ids = paddle.randint(0, 64, [2, 16])
    first = None
    for _ in range(15):
        loss = model(ids, labels=ids)
        if first is None:
            first = float(loss)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert float(loss) < first * 0.8, (first, float(loss))


def test_pretrain_engine_hybrid_meshes():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, init_adamw_state,
        make_train_step)

    for dp, pp, mp in [(8, 1, 1), (2, 2, 2)]:
        cfg = LlamaPretrainConfig(
            vocab_size=128, hidden_size=64, intermediate_size=192,
            num_hidden_layers=2 * max(pp, 1), num_attention_heads=4,
            num_key_value_heads=4, max_seq_len=32,
            use_pallas_attention=False, sequence_parallel=(mp > 1),
            remat=True, dtype=jnp.float32)
        mesh = build_mesh(dp=dp, pp=pp, sharding=1, sep=1, mp=mp)
        with mesh:
            params = init_params(cfg, jax.random.PRNGKey(0), mesh, pp=pp)
            opt = init_adamw_state(params, mesh, zero_axis="dp")
            mb = 2 if pp > 1 else 1
            step = make_train_step(cfg, mesh, pp=pp, microbatches=mb)
            toks = jnp.asarray(np.random.RandomState(0).randint(
                0, 128, (4 * dp * mb, 32)))
            params, opt, loss = step(params, opt, toks)
            assert np.isfinite(float(loss))


def test_pipeline_matches_single_stage():
    """pp=2 pipeline must produce the same loss as pp=1 on identical
    params (numerical equivalence of the GPipe schedule)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, make_forward)

    cfg = LlamaPretrainConfig(
        vocab_size=64, hidden_size=32, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, max_seq_len=16,
        use_pallas_attention=False, sequence_parallel=False,
        remat=False, dtype=jnp.float32)
    mesh = build_mesh(dp=2, pp=2, sharding=1, sep=1, mp=2)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
    with mesh:
        params_pp = init_params(cfg, jax.random.PRNGKey(0), mesh, pp=2)
        loss_pp = jax.jit(make_forward(cfg, mesh, pp=2, microbatches=2))(
            params_pp, toks)
        # same weights, flat layer stack
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), params_pp["blocks"])
        params_flat = dict(params_pp)
        params_flat["blocks"] = flat
        loss_flat = jax.jit(make_forward(cfg, mesh, pp=1))(params_flat,
                                                           toks)
    np.testing.assert_allclose(float(loss_pp), float(loss_flat),
                               rtol=2e-5)


def test_graft_entry():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as ge
    import jax
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(float(out))
    ge.dryrun_multichip(8)


@pytest.mark.slow
def test_adafactor_and_bf16_moment_lanes():
    """Round-3 bench optimizers: Adafactor (factored second moment) and
    AdamW with quantized (bf16) moments both train the tiny flagship.
    Reference analog: optimizer-memory reduction via
    group_sharded_stage3.py offload — on one chip, factoring/quantizing
    is the equivalent lever."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params,
        init_adafactor_state, init_adamw_state, make_train_step)
    cfg = LlamaPretrainConfig(
        vocab_size=128, hidden_size=128, intermediate_size=192,
        num_hidden_layers=2, num_attention_heads=4, max_seq_len=32,
        use_pallas_attention=False, sequence_parallel=False,
        remat=True, dtype=jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:1])
    toks = np.random.RandomState(0).randint(0, 128, (2, 33))
    with mesh:
        # adafactor lane: factored state is tiny
        params = init_params(cfg, jax.random.PRNGKey(0), mesh)
        st = init_adafactor_state(params, beta1=0.9)
        n_param_bytes = sum(x.size * x.dtype.itemsize
                            for x in jax.tree_util.tree_leaves(params))
        # second-moment bytes (vr/vc/v) must be << a full fp32 copy;
        # embed/lm_head [128,128] are at the factoring threshold so only
        # check the factored slots exist for the big matrices
        moments = st["moments"]
        assert "vr" in moments["embed"] and "vc" in moments["embed"]
        v_bytes = sum(
            x.size * x.dtype.itemsize
            for k in ("vr", "vc", "v")
            for x in jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(
                    lambda s: s.get(k) if isinstance(s, dict) else None,
                    moments,
                    is_leaf=lambda s: isinstance(s, dict) and
                    ("vr" in s or "v" in s)))
            if x is not None)
        assert v_bytes < n_param_bytes / 4, (v_bytes, n_param_bytes)
        step = make_train_step(cfg, mesh, lr=3e-2, optimizer="adafactor",
                               beta1=0.9)
        first = None
        t = jnp.asarray(toks)
        for _ in range(10):
            params, st, loss = step(params, st, t)
            if first is None:
                first = float(loss)
        assert float(loss) < first - 0.5, (first, float(loss))

        # bf16-moment AdamW lane: state dtype is bf16, still trains
        params = init_params(cfg, jax.random.PRNGKey(0), mesh)
        st = init_adamw_state(params, moment_dtype=jnp.bfloat16)
        assert st["moments"]["embed"]["m"].dtype == jnp.bfloat16
        step = make_train_step(cfg, mesh, lr=1e-3)
        first = None
        for _ in range(10):
            params, st, loss = step(params, st, t)
            if first is None:
                first = float(loss)
        assert float(loss) < first - 0.5, (first, float(loss))


def test_flagship_vpp_matches_flat():
    """Interleaved virtual pipeline (vpp=2) on the flagship trunk: loss
    and grads equal the flat pp=1 stack on identical weights (reference:
    WithInterleave, pipeline_parallel.py:1010)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, make_forward)

    cfg = LlamaPretrainConfig(
        vocab_size=64, hidden_size=32, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=4, max_seq_len=16,
        use_pallas_attention=False, sequence_parallel=False,
        remat=False, dtype=jnp.float32)
    mesh = build_mesh(dp=2, pp=2, sharding=1, sep=1, mp=2)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
    with mesh:
        params = init_params(cfg, jax.random.PRNGKey(0), mesh, pp=2,
                             vpp=2)
        loss_vpp = jax.jit(make_forward(cfg, mesh, pp=2, microbatches=2,
                                        vpp=2))(params, toks)
        # same weights in logical-stage order: [pp, v, Lc] -> [v, pp, Lc]
        # -> flat [L] (logical stage s = c*pp + r holds consecutive
        # layers)
        flat = jax.tree_util.tree_map(
            lambda a: a.transpose(1, 0, *range(2, a.ndim)).reshape(
                (-1,) + a.shape[3:]),
            params["blocks"])
        pf = dict(params)
        pf["blocks"] = flat
        loss_flat = jax.jit(make_forward(cfg, mesh, pp=1))(pf, toks)
        np.testing.assert_allclose(float(loss_vpp), float(loss_flat),
                                   rtol=2e-5)
        g_vpp = jax.jit(jax.grad(make_forward(
            cfg, mesh, pp=2, microbatches=2, vpp=2)))(params, toks)
        g_flat = jax.jit(jax.grad(make_forward(cfg, mesh, pp=1)))(
            pf, toks)
        gv = jax.tree_util.tree_map(
            lambda a: a.transpose(1, 0, *range(2, a.ndim)).reshape(
                (-1,) + a.shape[3:]),
            g_vpp["blocks"])
        for a, b in zip(jax.tree_util.tree_leaves(gv),
                        jax.tree_util.tree_leaves(g_flat["blocks"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("mode", ["kept", "recomputed"])
def test_remat_policy_keeps_loss_and_gradients(mode, kv_heads, monkeypatch):
    """What full remat holds changes what is recomputed, never a value:
    with ``flash_fwd``'s outputs kept (their bytes within
    ``FLASH_KEPT_BYTES``: the kernel runs once) and with the whole block
    recomputed (the bound at 0: it runs twice) the loss and every leaf's
    gradient are those of ``remat=False``, and the two are the same
    bits."""
    import re
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama_pretrain
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, make_forward)

    base = dict(vocab_size=64, hidden_size=256, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=kv_heads, max_seq_len=128,
                use_pallas_attention=True, sequence_parallel=False,
                dtype=jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:1])
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 129)))

    def loss_and_grads(bound=None, **kw):
        if bound is not None:
            monkeypatch.setattr(llama_pretrain, "FLASH_KEPT_BYTES", bound)
        cfg = LlamaPretrainConfig(**base, **kw)
        with mesh:
            params = init_params(cfg, jax.random.PRNGKey(0), mesh)
            fn = jax.value_and_grad(make_forward(cfg, mesh))
            runs = len(re.findall(r"name=flash_fwd\b",
                                  str(jax.make_jaxpr(fn)(params, toks))))
            return jax.jit(fn)(params, toks), runs

    (want, want_g), _ = loss_and_grads(remat=False)
    bounds = {"kept": llama_pretrain.FLASH_KEPT_BYTES, "recomputed": 0}
    (got, got_g), flash_fwd_runs = loss_and_grads(bounds[mode], remat=True)
    assert flash_fwd_runs == {"kept": 1, "recomputed": 2}[mode]
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_g),
            jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(path))
    # the other side of the rule: the kept o and lse ARE the arrays the
    # recompute would write again
    other_mode = "recomputed" if mode == "kept" else "kept"
    (other, other_g), other_runs = loss_and_grads(bounds[other_mode],
                                                  remat=True)
    assert other_runs == 3 - flash_fwd_runs
    assert float(other) == float(got)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_g),
            jax.tree_util.tree_leaves(other_g)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(path))


# (batch, rows, heads, a head's value width, dtype, flash layers), the
# bound (None: the module's own) -> kept
KEPT_BY_BYTES = {
    # 5 x (134,217,728 + 2,097,152) = 681,574,400 B
    "expert_cell": ((2, 8192, 32, 128, "bfloat16", 5), None, True),
    "expert_cell_to_the_byte": ((2, 8192, 32, 128, "bfloat16", 5),
                                681_574_400, True),
    "expert_cell_a_byte_short": ((2, 8192, 32, 128, "bfloat16", 5),
                                 681_574_399, False),
    # the one attention layer of ten: 69,206,016 B
    "hybrid_cell": ((2, 8192, 32, 64, "bfloat16", 1), None, True),
    # 18 x 68,157,440 = 1,226,833,920 B: past 2**30
    "dense_cell": ((8, 2048, 16, 128, "bfloat16", 18), None, False),
    "dense_cell_15_layers": ((8, 2048, 16, 128, "bfloat16", 15), None,
                             True),
    # 2**27 rows of a head x (4 + 4) B = 2**30 exactly
    "on_the_bound": ((2, 8192, 32, 1, "float32", 256), None, True),
    # 5 x 533 x 1321 x 61 x (1 + 4) B = 2**30 + 1
    "a_byte_over": ((533, 1321, 61, 1, "int8", 5), None, False),
    "no_flash_layer": ((2, 8192, 32, 128, "bfloat16", 0), None, False),
}


@pytest.mark.parametrize("case", sorted(KEPT_BY_BYTES))
def test_flash_outputs_are_kept_by_their_bytes(case, monkeypatch):
    """``keeps_flash_outputs`` is a function of shapes: the layers' ``o``
    and fp32 ``lse`` together against ``FLASH_KEPT_BYTES``."""
    from paddle_tpu.models import llama_pretrain
    shape, bound, kept = KEPT_BY_BYTES[case]
    assert llama_pretrain.FLASH_KEPT_BYTES == 1 << 30
    if bound is not None:
        monkeypatch.setattr(llama_pretrain, "FLASH_KEPT_BYTES", bound)
    assert llama_pretrain.keeps_flash_outputs(*shape) is kept


@pytest.mark.parametrize("policy", ["dots", "names", "cheap", "flash"])
def test_remat_policy_refuses_a_retired_name(policy):
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    with pytest.raises(ValueError, match="remat_policy"):
        LlamaPretrainConfig(remat_policy=policy)
