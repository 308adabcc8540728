"""Flagship LLaMA tests: Layer model, functional pretrain engine,
hybrid-mesh train step, graft entry.

``test_graft_entry`` holds the entry point to a finite loss under
``jax.jit`` (seconds).  The seven 8-device programs of
``__graft_entry__.dryrun_multichip`` RUN in
``test_graft_entry_dryrun_multichip``, which is marked ``slow``: alone
they are three minutes, under the other five workers' load five to seven
of one worker and ~1,200 core-seconds — a seventh of tier-1 — because
eight device threads spin in CPU collectives while the other workers
want the cores, and nearly all of it is ONE program, the 32k-vocabulary
step at hidden 1024.  What each program exercises stays in tier-1 at
dims that compile in seconds: the dp x pp x mp step in
``test_pretrain_engine_hybrid_meshes``, the three other train steps in
``test_hybrid_step_layouts`` below, expert parallelism in
``test_expert_parallel.py``, the tensor-parallel engines in
``test_serving_tp.py`` and ``test_serving_mixed.py`` (CHANGES.md, PR 53,
has the table).  ``pytest -m slow tests/test_llama_flagship.py -k
dryrun`` runs the long case."""

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


def _graft_entry():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__
    return __graft_entry__


def test_graft_entry():
    import jax
    fn, args = _graft_entry().entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(float(out))


@pytest.mark.slow
def test_graft_entry_dryrun_multichip():
    _graft_entry().dryrun_multichip(8)


# The three train steps of ``dryrun_multichip(8)`` that
# ``test_pretrain_engine_hybrid_meshes`` does not run, on the same meshes
# with the same switches.  ``bench_layout`` keeps what the BENCH-DIMS
# program is there for — heads 128 wide, the vocabulary sharded over
# ``mp``, ZeRO over ``sharding``, the ring over ``sep``, remat — and cuts
# hidden 1024 -> 256, the vocabulary 32,000 -> 512 and the row 128 -> 64.
STEP_LAYOUTS = {
    "ring_over_sep": dict(
        mesh=dict(dp=4, sep=2), zero_axis=None, rows=8, seq=33,
        cfg=dict(context_parallel="ring")),
    "vpp_zero_over_sharding": dict(
        mesh=dict(dp=2, pp=2, sharding=2), zero_axis="sharding", rows=16,
        seq=32, step=dict(pp=2, microbatches=2, vpp=2),
        cfg=dict(num_hidden_layers=4)),
    "bench_layout": dict(
        mesh=dict(sharding=2, sep=2, mp=2), zero_axis="sharding", rows=2,
        seq=65,
        cfg=dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                 num_attention_heads=2, num_key_value_heads=2,
                 max_seq_len=64, remat=True, context_parallel="ring",
                 loss_chunks=1)),
}


@pytest.mark.parametrize("layout", sorted(STEP_LAYOUTS))
def test_hybrid_step_layouts(layout):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, init_adamw_state,
        make_train_step)
    case = STEP_LAYOUTS[layout]
    how = case.get("step", {})
    cfg = LlamaPretrainConfig(**dict(
        dict(vocab_size=128, hidden_size=64, intermediate_size=192,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, max_seq_len=32,
             use_pallas_attention=False, sequence_parallel=False,
             remat=False, dtype=jnp.float32), **case["cfg"]))
    assert cfg.head_dim == (128 if layout == "bench_layout" else 16)
    mesh = build_mesh(**case["mesh"])
    with mesh:
        params = init_params(
            cfg, jax.random.PRNGKey(0), mesh,
            **{k: how[k] for k in ("pp", "vpp") if k in how})
        if layout == "bench_layout":
            assert params["lm_head"].sharding.spec[-1] == "mp"
            assert params["embed"].sharding.spec[0] == "mp"
        opt = init_adamw_state(params, mesh, zero_axis=case["zero_axis"])
        step = make_train_step(cfg, mesh, lr=1e-3, **how)
        toks = jnp.asarray(np.random.RandomState(4).randint(
            0, cfg.vocab_size, (case["rows"], case["seq"])))
        _, _, loss = step(params, opt, toks)
    assert np.isfinite(float(loss))


ONCE = {"adafactor-1": ("adafactor", {}),
        "adafactor-dp4xmp2": ("adafactor", dict(dp=4, mp=2)),
        "adamw-1": ("adamw", {}),
        "adamw-dp4xmp2-zero": ("adamw", dict(dp=4, mp=2))}


@pytest.mark.parametrize("case", sorted(ONCE))
def test_a_step_compiles_once(case):
    """A step from ``make_train_step`` called three times with the state
    its constructor made is ONE program: lowered once and compiled once
    (the events ``benchmark/harness.CompileClock`` counts), and where
    the mesh has more than one device one entry in jit's cache, every
    leaf coming back where it went in.  Before PR 53 the constructors
    made ``t`` and adafactor's ``vr`` / ``vc`` with a bare ``jnp.zeros``
    — arrays that carry no mesh, which the step returns placed on it —
    and the step left its outputs' shardings to the compiler: 2, 2, 2
    and 3 programs in these four cases, each traced, lowered and
    compiled.  On ONE device the step stays unpinned, so that a cell's
    compiled text stays what it was: the compiler's words for
    "replicated" then differ from the constructor's, and jit keeps a
    second entry for the same executable (no trace, no lowering, no
    compile: the second call of the ``solar`` toy's step takes 0.26 s,
    its first 20.7)."""
    import jax
    import jax.monitoring
    import jax.numpy as jnp
    from paddle_tpu.models.llama_pretrain import (
        LlamaPretrainConfig, build_mesh, init_params, init_adafactor_state,
        init_adamw_state, make_train_step)
    optimizer, dims = ONCE[case]
    cfg = LlamaPretrainConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_seq_len=32, use_pallas_attention=False,
        sequence_parallel=False, remat=False, dtype=jnp.float32)
    n = int(np.prod(list(dims.values()) or [1]))
    mesh = build_mesh(devices=jax.devices()[:n], **dims)
    seen = []

    def listener(event, secs, fun_name=None, **_):
        if fun_name == "jit(step)":
            seen.append(event.rsplit("/", 1)[-1])

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        with mesh:
            params = init_params(cfg, jax.random.PRNGKey(0), mesh)
            if optimizer == "adafactor":
                state = init_adafactor_state(params)
                bare = init_adafactor_state(
                    jax.tree_util.tree_map(np.asarray, params))
            else:
                state = init_adamw_state(params, mesh, zero_axis="dp")
                bare = init_adamw_state(
                    jax.tree_util.tree_map(np.asarray, params))
            # the bits of a state over parameters that carry no mesh,
            # every leaf on the parameters' mesh
            for (path, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(state)[0],
                    jax.tree_util.tree_leaves(bare)):
                assert a.sharding.mesh == mesh, path
                assert a.dtype == b.dtype, path
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              str(path))
            step = make_train_step(cfg, mesh, optimizer=optimizer)
            toks = jnp.asarray(np.random.RandomState(0).randint(
                0, cfg.vocab_size, (4, 33)))
            placed = jax.tree_util.tree_map(lambda x: x.sharding,
                                            (params, state))
            losses = []
            for _ in range(3):
                params, state, loss = step(params, state, toks)
                losses.append(float(loss))
                if n > 1:
                    assert jax.tree_util.tree_map(
                        lambda x: x.sharding, (params, state)) == placed
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert seen == ["jaxpr_to_mlir_module_duration",
                    "backend_compile_duration"], seen
    if n > 1:
        assert step._cache_size() == 1
    assert losses[2] < losses[1] < losses[0], losses


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
    ``KEPT_BYTES``: the kernel runs once) and with the whole block
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
            monkeypatch.setattr(llama_pretrain, "KEPT_BYTES", bound)
        cfg = LlamaPretrainConfig(**base, **kw)
        with mesh:
            params = init_params(cfg, jax.random.PRNGKey(0), mesh)
            fn = jax.value_and_grad(make_forward(cfg, mesh))
            runs = len(re.findall(r"name=flash_fwd\b",
                                  str(jax.make_jaxpr(fn)(params, toks))))
            return jax.jit(fn)(params, toks), runs

    (want, want_g), _ = loss_and_grads(remat=False)
    bounds = {"kept": llama_pretrain.KEPT_BYTES, "recomputed": 0}
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
    and fp32 ``lse`` together against ``KEPT_BYTES``."""
    from paddle_tpu.models import llama_pretrain
    shape, bound, kept = KEPT_BY_BYTES[case]
    assert llama_pretrain.KEPT_BYTES == 1 << 30
    if bound is not None:
        monkeypatch.setattr(llama_pretrain, "KEPT_BYTES", bound)
    assert llama_pretrain.keeps_flash_outputs(*shape) is kept


SOLAR, PERIOD = "solar-open2-250b.pretrain-kda-moe", \
    ("gqa_gated_moe", "kda_moe", "kda_moe", "kda_moe")
# an entered cell, keys of its program's configuration stated otherwise,
# (rows, positions) where not the cell's own, the budget (None: the
# module's own) -> (``flash_fwd``'s outputs kept, ``kda_chunk_fwd``'s)
KEPT_OF_A_TRUNK = {
    # 136,314,880 + 3 x (134,217,728 + 134,217,728) = 941,621,248 B
    "delta_rule_cell": (SOLAR, {}, None, None, (True, True)),
    "delta_rule_cell_to_the_byte": (SOLAR, {}, None, 941_621_248,
                                    (True, True)),
    "delta_rule_cell_a_byte_short": (SOLAR, {}, None, 941_621_247,
                                     (True, False)),
    # a layer deeper, 1,210,056,704 B: the delta rule's are recomputed,
    # flash's are kept as they were
    "a_layer_deeper": (SOLAR, {"layer_types": PERIOD + ("kda_moe",)}, None,
                       None, (True, False)),
    # ISSUE 52's rungs (a) / (b): 3 x 536,870,912 B alone are past 2**30
    "a_row_of_16k": (SOLAR, {}, (1, 16384), None, (True, False)),
    # four delta-rule layers and no flash layer: 2**30 to the byte
    "on_the_budget": (SOLAR, {"layer_types": ("kda_moe",) * 4}, None, None,
                      (False, True)),
    "on_the_budget_beside_flash": (
        SOLAR, {"layer_types": ("gqa_gated_moe",) + ("kda_moe",) * 4}, None,
        None, (True, False)),
    # flash past the budget keeps nothing, and what it would have taken
    # is not counted against the delta rule's
    "flash_over_the_budget": (SOLAR, {"layer_types": PERIOD}, None,
                              136_314_879, (False, False)),
    # seven flash layers' 7 x 8,519,680 B do not fit, three delta-rule
    # layers' 3 x 16,777,216 do
    "flash_over_delta_rule_within": (
        SOLAR, {"layer_types": ("gqa_gated_moe",) * 7 + ("kda_moe",) * 3},
        (1, 512), 3 * 16_777_216, (False, True)),
    # 8,000 positions are padded to 32 blocks of 256: the kernel writes
    # 8,192 rows of o; flash's 8,000 are 133,120,000 B
    "a_padded_row": (SOLAR, {}, (1, 8000), 133_120_000 + 805_306_368,
                     (True, True)),
    "a_padded_row_a_byte_short": (SOLAR, {}, (1, 8000),
                                  133_120_000 + 805_306_367, (True, False)),
    # heads the kernels do not take run ``kda_chunked_xla``: nothing named
    "heads_of_64": (SOLAR, {"kda_head_dim": 64}, None, None, (True, False)),
    "fp16_rows": (SOLAR, {"dtype": "float16"}, None, None, (True, False)),
    # no delta-rule layer: flash's decision, the four trunks by kind
    "hybrid_cell": ("granite-4.0-h-micro.pretrain-8k", {}, None, None,
                    (True, False)),
    "expert_cell": ("xing4.0-29b-a4b.pretrain-8k-moe", {}, None, None,
                    (True, False)),
    "expert_cell_a_byte_short": ("xing4.0-29b-a4b.pretrain-8k-moe", {}, None,
                                 681_574_399, (False, False)),
    "window_cell": ("smallthinker-21b-a3b.pretrain-16k-moe", {}, None, None,
                    (True, False)),
    "convolution_cell": ("lfm2-24b-a2b.pretrain-8k-conv-moe", {}, None, None,
                         (True, False)),
}


@pytest.mark.parametrize("case", sorted(KEPT_OF_A_TRUNK))
def test_a_trunk_keeps_kernel_outputs_by_one_budget(case, monkeypatch):
    """``hybrid_trunk.kept_outputs`` is a function of shapes: flash's
    bytes against ``KEPT_BYTES`` first, exactly ``keeps_flash_outputs``
    (a trunk with no delta-rule layer decides as it did), then the
    ``kda_moe`` layers' ``kda_chunk.kept_bytes`` beside what flash
    keeps, where the kernels take the shapes."""
    import dataclasses
    import jax.numpy as jnp
    from benchmark import harness
    from paddle_tpu.models import hybrid_trunk, llama_pretrain
    name, stated, shape, bound, kept = KEPT_OF_A_TRUNK[case]
    cell = harness.find_cell(name)
    cfg = cell.family.build_cfg(cell.conf, True, cell.traffic)
    if "dtype" in stated:
        stated = dict(stated, dtype=jnp.dtype(stated["dtype"]))
    if "layer_types" in stated:     # the configuration cuts them to depth
        stated = dict(stated, num_hidden_layers=len(stated["layer_types"]))
    cfg = dataclasses.replace(cfg, **stated)
    assert cfg.layer_types == stated.get("layer_types", cfg.layer_types)
    rows, seq = shape or (cell.traffic["batch"], cell.traffic["seq"])
    if bound is not None:
        monkeypatch.setattr(llama_pretrain, "KEPT_BYTES", bound)
    assert hybrid_trunk.kept_outputs(cfg, rows, seq) == kept
    kinds = hybrid_trunk.flash_kinds(cfg)
    assert kept[0] is llama_pretrain.keeps_flash_outputs(
        rows, seq, cfg.num_attention_heads,
        cfg.v_head_dim if "mla_moe" in kinds else cfg.head_dim, cfg.dtype,
        sum(kind in kinds for kind in cfg.layer_types))


def test_the_delta_rule_s_kept_bytes_are_the_kernel_s_outputs():
    """``kda_chunk.kept_bytes`` against the two arrays ``kda_chunk_fwd``
    declares, at a row of whole blocks, a padded one and one shorter
    than a block."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.pallas import kda_chunk
    assert kda_chunk.kept_bytes(1, 8192, 64, jnp.bfloat16, 64) == \
        134_217_728 + 134_217_728
    for s, dtype in ((512, jnp.bfloat16), (300, jnp.float32),
                     (100, jnp.bfloat16)):
        outs = []

        def spy(qkv, g, beta, chunk):
            outs.extend(jax.eval_shape(functools.partial(
                kda_chunk._run_fwd, chunk=chunk), qkv, g, beta))
            return jnp.zeros(outs[0].shape, outs[0].dtype)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(kda_chunk, "kda_chunked", spy)
            jax.eval_shape(
                lambda *a: kda.kda_chunk(*a, 2),
                jax.ShapeDtypeStruct((3, s, 3 * 2 * 128), dtype),
                jax.ShapeDtypeStruct((3, s, 2 * 128), jnp.float32),
                jax.ShapeDtypeStruct((3, s, 2), jnp.float32))
        assert kda_chunk.kept_bytes(3, s, 2, dtype, kda.CHUNK) == sum(
            o.size * o.dtype.itemsize for o in outs), s


@pytest.mark.parametrize("policy", ["dots", "names", "cheap", "flash"])
def test_remat_policy_refuses_a_retired_name(policy):
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    with pytest.raises(ValueError, match="remat_policy"):
        LlamaPretrainConfig(remat_policy=policy)
