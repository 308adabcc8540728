"""The names the program gives its work (docs/OBSERVABILITY.md,
"Profiler spans and scopes"): ``jax.named_scope`` names inside the step
programs, ``name=`` on every ``pallas_call``, and ``RecordEvent`` spans
that reach the profiler's trace under a plain
``jax.profiler.start_trace``.  All on the CPU: scopes are read from the
lowered text, spans from a trace the CPU backend records."""

import ast
import collections
import glob
import importlib.util
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (x64 before any array)
from benchmark import xplane, xplane_meta
from paddle_tpu.models import paged_decode, serving_engine
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, build_mesh, init_adafactor_state, init_params,
    make_train_step)
from paddle_tpu.observability import EventRing, MetricsRegistry
from paddle_tpu.profiler.utils import (RecordEvent, _buffer,
                                       _disable_collection, _drain_spans,
                                       _enable_collection)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = {"block", "attn_qkv", "rope", "attn_out", "mlp"}
DECODE = BLOCK | {"embed", "pool_carry", "kv_write", "paged_attn",
                  "logits", "sample"}
PREFILL = BLOCK | {"embed", "layer_scan", "varlen_attn"}
# kernels of the program that the readers' copies do not list yet: only a
# ``benchmark`` PR may add ``moe_sum_pairs`` (PR 34, the expert layer's
# token side) and the mixers' four (PR 36, ``ops/pallas/hc_mix.py``) to
# ``benchmark/models/xing_mhc_moe.KERNELS`` (ROADMAP D14)
HC_KERNELS = ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd")
AHEAD = ("moe_sum_pairs",) + HC_KERNELS
# ... of which ``moe_sum_pairs`` is named by a family since PR 44
# (``benchmark/models/smallthinker_moe.KERNELS``, a new file)
AHEAD_OF_EVERY_FAMILY = HC_KERNELS
# a span of the program that the readers' copy (``xplane_meta.SPANS``)
# does not list yet (PR 54: the loader's start; ROADMAP D14)
SPANS_AHEAD = ("dataloader.start",)


def scope_names(lowered) -> set:
    """Every identifier on an op path of the lowered text."""
    text = lowered.as_text(debug_info=True)
    words = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        words.update(xplane_meta.path_words(path))
    return words


def _cfg(**kw):
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_seq_len=256, dtype=jnp.float32,
                param_dtype=jnp.float32, remat=False, loss_chunks=1,
                use_pallas_attention=False)
    base.update(kw)
    return LlamaPretrainConfig(**base)


def _mesh():
    return build_mesh(devices=jax.devices()[:1])


# -- the train step ---------------------------------------------------------
@pytest.mark.parametrize("accum,want", [
    (1, BLOCK | {"embed", "layer_scan", "attn", "loss_head",
                 "optimizer"}),
    (2, BLOCK | {"embed", "layer_scan", "attn", "loss_head", "optimizer",
                 "grad_accum"})])
def test_train_step_carries_the_scopes(accum, want):
    cfg = _cfg(remat=True, loss_chunks=2)
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        step = make_train_step(cfg, mesh, lr=1e-2, optimizer="adafactor",
                               accum_steps=accum)
        low = step.lower(params, opt,
                         jax.ShapeDtypeStruct((4, 33), jnp.int64))
    names = scope_names(low)
    assert want <= names, want - names
    # forward, backward and recompute of a block are told apart
    text = low.as_text(debug_info=True)
    assert "transpose(jvp(layer_scan))" in text or accum > 1
    assert "rematted_computation" in text
    # the readers key on the XLA module ``jit_step``
    assert "jit(step)" in text


def family_names() -> tuple:
    """``(SCOPES, KERNELS)`` that the block families under
    ``benchmark/models/`` add to the base vocabulary, all together."""
    scopes, kernels = set(), set()
    for path in glob.glob(os.path.join(REPO, "benchmark", "models",
                                       "*.py")):
        name = os.path.basename(path)[:-3]
        if name == "__init__" or name.endswith("_reference"):
            continue
        mod = importlib.import_module(f"benchmark.models.{name}")
        scopes.update(getattr(mod, "SCOPES", ()))
        kernels.update(getattr(mod, "KERNELS", ()))
    return scopes, kernels


def test_train_step_by_kind_carries_the_family_s_scopes():
    """Layers by kind: the state-space block's five scopes beside the
    base vocabulary's, the attention kind with no ``rope`` (the
    configuration states no rotation), every base scope of a train step
    still there — so ``unscoped_pct.train`` stays honest."""
    from benchmark.models import granite_hybrid
    cfg = _cfg(remat=True, loss_chunks=2,
               layer_types=("mamba", "attention", "mamba"),
               num_hidden_layers=3, mamba_n_heads=2, mamba_d_head=64,
               mamba_d_state=64, mamba_chunk_size=128,
               position_embedding_type="nope", attention_multiplier=0.0625,
               residual_multiplier=0.22, embedding_multiplier=12.0,
               logits_scaling=8.0, tie_word_embeddings=True)
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = (BLOCK - {"rope"}) | {"embed", "layer_scan", "attn",
                                 "loss_head", "optimizer"} \
        | set(granite_hybrid.SCOPES)
    assert want <= names, want - names
    assert "rope" not in names
    assert set(granite_hybrid.SCOPES) <= family_names()[0]
    # the scan and the convolution run as their kernels, under their
    # scopes, forward and backward
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    for scope, kernel in (("ssm_scan", "ssd_scan_fwd"),
                          ("ssm_scan", "ssd_scan_bwd"),
                          ("ssm_conv", "causal_conv_fwd"),
                          ("ssm_conv", "causal_conv_bwd")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            xplane_meta.KERNELS + granite_hybrid.KERNELS) == kernel


def test_train_step_of_the_expert_kinds_carries_the_family_s_scopes():
    """``mla_dense`` / ``mla_moe``: the nine scopes of the family
    ``xing_mhc_moe`` beside the base vocabulary's (``attn_qkv`` gives way
    to ``mla_q`` / ``mla_kv``; the dense lead keeps ``mlp``), forward and
    backward — the routed path is ONE custom_vjp whose backward names
    its own scopes — and the kernels under their scopes."""
    from benchmark.models import xing_mhc_moe
    cfg = _cfg(remat=True, loss_chunks=2, hidden_size=128,
               intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=2,
               q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=1,
               rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                             "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 64},
               moe_intermediate_size=128, n_routed_experts=8,
               experts_held=2, expert_first=2, n_shared_experts=1,
               num_experts_per_tok=2, routed_scaling_factor=2.0, hc_mult=4)
    assert cfg.layer_types == ("mla_dense", "mla_moe")
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = (BLOCK - {"attn_qkv"}) | {"embed", "layer_scan", "attn",
                                     "loss_head", "optimizer"} \
        | set(xing_mhc_moe.SCOPES)
    assert want <= names, want - names
    assert set(xing_mhc_moe.SCOPES) <= family_names()[0]
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    for scope, kernel in (("moe_experts", "grouped_mm"),
                          ("moe_experts", "grouped_mm_dw"),
                          ("moe_combine", "moe_sum_pairs"),
                          ("moe_dispatch", "moe_sum_pairs"),
                          ("attn", "flash_fwd"), ("attn", "flash_bwd_dkv")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        # ``moe_sum_pairs`` (PR 34) is AHEAD of the readers' copy:
        # ``xing_mhc_moe.KERNELS`` is a benchmark file (ROADMAP D14)
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            xplane_meta.KERNELS + xing_mhc_moe.KERNELS + AHEAD) == kernel
    # the split form's backward is one pass at this row length (PR 42)
    assert not any(p.endswith("attn/flash_bwd_dq/pallas_call")
                   for p in paths)
    # the routed path runs on one of two bounds, each under a scope of
    # its own OUTSIDE the family's: the innermost name a reader knows is
    # still one of the five ``moe_*``, on every op of either branch,
    # forward (the remat's too) and backward (the routed path is one
    # custom_vjp; its backward lies outside ``rematted_computation``)
    routed = [p for p in paths if "moe_bound_" in p]
    names_of = xplane_meta.SCOPES + xing_mhc_moe.SCOPES
    for bound in ("moe_bound_load", "moe_bound_all"):
        assert bound not in names_of
        for scope in ("moe_dispatch", "moe_experts", "moe_combine"):
            for phase in ("checkpoint/rematted_computation/block/cond/",
                          "checkpoint/block/cond/"):
                assert any(p.startswith(phase) and f"/{bound}/{scope}/" in p
                           for p in routed), (phase, bound, scope)
    assert len(routed) > 100
    for p in routed:
        assert xplane_meta.scope_of(p, names_of) in (
            "moe_dispatch", "moe_experts", "moe_combine"), p
    # the mixers' passes over the streams are the four kernels of
    # ``ops/pallas/hc_mix.py`` (the toy's 128-wide streams are a shape
    # they take), each under the scope ``hc_mix_pct.train`` reads, in
    # the forward, in the remat's forward and — two custom_vjps whose
    # backwards name their scope themselves — in the backward
    for phase, scope, kernel in (
            ("block/", "hc_pre", "hc_pre_fwd"),
            ("block/", "hc_post", "hc_post_fwd"),
            ("checkpoint/rematted_computation/block/", "hc_pre",
             "hc_pre_fwd"),
            ("checkpoint/rematted_computation/block/", "hc_post",
             "hc_post_fwd"),
            ("checkpoint/block/", "hc_post", "hc_post_bwd"),
            ("checkpoint/block/", "hc_pre", "hc_pre_bwd")):
        assert f"{phase}{scope}/{kernel}/pallas_call" in paths, kernel
        assert xplane_meta.scope_of(
            f"jit(step)/{phase}{scope}/{kernel}/pallas_call",
            names_of) == scope
        assert xplane_meta.kernel_of(
            f"jit(step)/{phase}{scope}/{kernel}/pallas_call",
            AHEAD) == kernel
    # and what stays XLA's — the maps' few numbers a token, under jax's
    # own autodiff — is charged to ``hc_pre`` in all three phases
    small = [p for p in paths if p.endswith("/logistic") and "hc_" in p
             and "pallas_call" not in p and "/hc_pre_fwd/" not in p]
    assert {xplane_meta.scope_of(p, names_of) for p in small} == {"hc_pre"}
    assert {xplane_meta.phase_of("jit(step)/" + p) for p in small} >= {
        "recompute"}


def test_train_step_of_the_plain_latent_attention_kinds_names_no_mixer():
    """``mla_dense`` / ``mla_moe`` on ONE residual stream with a direct
    query (``hc_mult`` 1, ``q_lora_rank`` 0: the fields' defaults): the
    seven scopes of the family ``kanana_mla_moe`` beside the base
    vocabulary's name the plain block's work exactly as they name the
    mHC block's — ``mla_q`` is still the query's, ``rope``, ``attn`` and
    ``attn_out`` attention's, the dense lead keeps ``mlp``, the routed
    path its two bounds — and ``hc_pre`` / ``hc_post`` appear nowhere."""
    from benchmark.models import kanana_mla_moe
    cfg = _cfg(remat=True, loss_chunks=2, hidden_size=128,
               intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=2,
               kv_lora_rank=64, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=1,
               rope_theta=1e6, moe_intermediate_size=128, n_routed_experts=8,
               experts_held=2, expert_first=2, n_shared_experts=2,
               num_experts_per_tok=3, routed_scaling_factor=2.448)
    assert cfg.layer_types == ("mla_dense", "mla_moe")
    assert (cfg.hc_mult, cfg.q_lora_rank) == (1, 0)
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = (BLOCK - {"attn_qkv"}) | {"embed", "layer_scan", "attn",
                                     "loss_head", "optimizer"} \
        | set(kanana_mla_moe.SCOPES)
    assert want <= names, want - names
    assert not {"hc_pre", "hc_post", "attn_qkv"} & names
    assert set(kanana_mla_moe.SCOPES) <= family_names()[0]
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    assert not [p for p in paths if "/hc_" in p or p.startswith("hc_")]
    names_of = xplane_meta.SCOPES + kanana_mla_moe.SCOPES
    for scope, kernel in (("moe_experts", "grouped_mm"),
                          ("moe_experts", "grouped_mm_dw"),
                          ("moe_combine", "moe_sum_pairs"),
                          ("moe_dispatch", "moe_sum_pairs"),
                          ("attn", "flash_fwd"), ("attn", "flash_bwd_dkv")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            xplane_meta.KERNELS + kanana_mla_moe.KERNELS) == kernel
    # the direct query's two products are ``mla_q``'s, in the forward,
    # the remat's forward and the backward; the block's two residual adds
    # are ``block``'s own
    for phase in ("block/", "checkpoint/rematted_computation/block/",
                  "checkpoint/block/"):
        assert any(p.startswith(phase + "mla_q/") and "dot_general" in p
                   for p in paths), phase
    for p in paths:
        if "/mla_q/" in p or "/mla_kv/" in p:
            assert xplane_meta.scope_of(p, names_of) in ("mla_q", "mla_kv")
    # the routed path on its two bounds, each under a scope outside the
    # family's, as in the mHC block
    routed = [p for p in paths if "moe_bound_" in p]
    for bound in ("moe_bound_load", "moe_bound_all"):
        assert bound not in names_of
        assert any(f"/{bound}/moe_experts/" in p for p in routed), bound
    for p in routed:
        assert xplane_meta.scope_of(p, names_of) in (
            "moe_dispatch", "moe_experts", "moe_combine"), p


def test_train_step_of_the_window_kinds_carries_the_family_s_scopes():
    """``gqa_moe_global`` / ``gqa_moe_window``: the base vocabulary's
    attention scopes (``rope`` in the window layers only) and the four
    ``moe_*`` of the family ``smallthinker_moe`` — no ``moe_shared``, no
    ``mlp`` —, the route and the plan issued BEFORE attention, the dense
    kernels in the global layer and the windowed form under its own
    names in the window layers, nothing of a block under no scope."""
    from benchmark.models import smallthinker_moe
    cfg = _cfg(remat=True, loss_chunks=2, hidden_size=128,
               num_hidden_layers=4, num_attention_heads=2,
               num_key_value_heads=1, head_dim=128, max_seq_len=256,
               rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
               sliding_window_size=64, moe_primary_router_apply_softmax=True,
               moe_intermediate_size=128, n_routed_experts=8,
               experts_held=2, expert_first=2, num_experts_per_tok=3,
               use_pallas_attention=True)
    assert cfg.layer_types == ("gqa_moe_global",) + ("gqa_moe_window",) * 3
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = (BLOCK - {"mlp"}) | {"embed", "layer_scan", "attn", "loss_head",
                                "optimizer"} | set(smallthinker_moe.SCOPES)
    assert want <= names, want - names
    assert not {"moe_shared", "mlp", "hc_pre", "mla_q"} & names
    assert smallthinker_moe.SCOPES == smallthinker_moe.MOE_SCOPES == (
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine")
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    vocabulary = xplane_meta.KERNELS + smallthinker_moe.KERNELS
    for scope, kernel in (("moe_experts", "grouped_mm"),
                          ("moe_experts", "grouped_mm_dw"),
                          ("moe_combine", "moe_sum_pairs"),
                          ("moe_dispatch", "moe_sum_pairs"),
                          ("attn", "flash_fwd"), ("attn", "flash_bwd_dkv"),
                          ("attn", "flash_win_fwd"),
                          ("attn", "flash_win_bwd_dkv"), ("rope", "rope")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            vocabulary) == kernel
    # a reader of the dense kernels does not take the windowed form's
    # time, nor the other way about: the names are told apart whole
    assert xplane_meta.kernel_of(
        "jit(step)/block/attn/flash_win_fwd/pallas_call",
        xplane_meta.KERNELS) == ""
    # the rotation is the window layers' alone (three of the four)
    rope = [p for p in paths if p.endswith("rope/rope/pallas_call")]
    assert rope and all("block" in p for p in rope)
    names_of = xplane_meta.SCOPES + smallthinker_moe.SCOPES
    # every op of a block is charged to one of the names: the innermost
    # known name is never the bare loop's
    inside = [p for p in paths if "/block/" in p or p.startswith("block/")]
    assert len(inside) > 200
    assert {xplane_meta.scope_of(p, names_of) for p in inside} <= (
        (BLOCK - {"mlp"}) | {"attn"} | set(smallthinker_moe.SCOPES))


def test_train_step_of_the_conv_kinds_carries_the_family_s_scopes():
    """``conv_dense`` / ``conv_moe`` / ``gqa_qknorm_moe``: the operator's
    three scopes with its two kernels under ``short_conv``, ``qk_norm``
    between ``attn_qkv`` and ``rope`` in the attention layer, the four
    ``moe_*`` of the family ``lfm2_conv_moe`` — no ``moe_shared`` —,
    ``mlp`` in the dense lead alone, nothing of a block under no scope."""
    from benchmark.models import lfm2_conv_moe
    cfg = _cfg(remat=True, loss_chunks=2, hidden_size=128,
               intermediate_size=256, num_hidden_layers=5,
               num_attention_heads=4, num_key_value_heads=2, max_seq_len=256,
               layer_types=("conv", "full_attention", "conv", "conv", "conv"),
               num_dense_layers=1, conv_L_cache=3, use_expert_bias=True,
               moe_intermediate_size=128, n_routed_experts=8,
               experts_held=2, expert_first=2, num_experts_per_tok=3,
               tie_word_embeddings=True, use_pallas_attention=True)
    assert cfg.layer_types == ("conv_dense", "gqa_qknorm_moe") \
        + ("conv_moe",) * 3
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = BLOCK | {"embed", "layer_scan", "attn", "loss_head",
                    "optimizer"} | set(lfm2_conv_moe.SCOPES)
    assert want <= names, want - names
    assert not {"moe_shared", "hc_pre", "mla_q", "ssm_conv"} & names
    assert lfm2_conv_moe.SCOPES == (
        "conv_in_proj", "short_conv", "conv_out_proj", "qk_norm",
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine")
    assert lfm2_conv_moe.KERNELS == (
        "short_conv_fwd", "short_conv_bwd", "grouped_mm", "grouped_mm_dw",
        "moe_sum_pairs")
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    vocabulary = xplane_meta.KERNELS + lfm2_conv_moe.KERNELS
    for scope, kernel in (("short_conv", "short_conv_fwd"),
                          ("short_conv", "short_conv_bwd"),
                          ("moe_experts", "grouped_mm"),
                          ("moe_experts", "grouped_mm_dw"),
                          ("moe_combine", "moe_sum_pairs"),
                          ("attn", "flash_fwd"), ("attn", "flash_bwd_dkv")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            vocabulary) == kernel
    # the hybrid cell's convolution is another kernel under another name
    assert xplane_meta.kernel_of(
        "jit(step)/block/short_conv/short_conv_fwd/pallas_call",
        xplane_meta.KERNELS + ("causal_conv_fwd",)) == ""
    names_of = xplane_meta.SCOPES + lfm2_conv_moe.SCOPES
    inside = [p for p in paths if "/block/" in p or p.startswith("block/")]
    assert len(inside) > 200
    assert {xplane_meta.scope_of(p, names_of) for p in inside} <= (
        BLOCK | {"attn"} | set(lfm2_conv_moe.SCOPES))
    # the norms of q and k stand before the rotation: ``qk_norm``'s ops
    # feed ``rope``'s, in the one attention layer
    assert any("/qk_norm/" in p for p in inside) and \
        any("/rope/" in p for p in inside)


def _kda_cfg():
    """One period of the delta-rule kinds at toy widths, the recurrence's
    heads one lane tile wide (the kernels take them)."""
    return _cfg(remat=True, loss_chunks=2, hidden_size=128,
                intermediate_size=256, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                max_seq_len=256, position_embedding_type="nope",
                gqa_layers=(0,), kda_num_heads=2, kda_head_dim=128,
                short_conv_kernel_size=4, moe_intermediate_size=128,
                n_routed_experts=8, n_shared_experts=1, experts_held=2,
                expert_first=2, num_experts_per_tok=3,
                use_pallas_attention=True)


def test_train_step_of_the_kda_kinds_carries_the_family_s_scopes():
    """``kda_moe`` / ``gqa_gated_moe``: the mixer's six scopes with the
    convolution's kernels under ``kda_conv`` and the recurrence's under
    ``kda_chunk``, ``attn_gate`` between ``attn`` and ``attn_out`` in the
    attention layer, the five ``moe_*`` of the family ``solar_kda_moe`` —
    ``moe_shared`` among them —, no ``mlp``, no ``rope``, nothing of a
    block under no scope."""
    from benchmark.models import solar_kda_moe
    cfg = _kda_cfg()
    assert cfg.layer_types == ("gqa_gated_moe",) + ("kda_moe",) * 3
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        low = make_train_step(cfg, mesh, lr=1e-2,
                              optimizer="adafactor").lower(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    names = scope_names(low)
    want = (BLOCK - {"mlp", "rope"}) | {
        "embed", "layer_scan", "attn", "loss_head", "optimizer"} \
        | set(solar_kda_moe.SCOPES)
    assert want <= names, want - names
    assert not {"mlp", "rope", "qk_norm", "hc_pre", "mla_q", "ssm_conv",
                "short_conv"} & names
    assert solar_kda_moe.SCOPES == (
        "kda_in_proj", "kda_conv", "kda_gates", "kda_chunk", "kda_out_gate",
        "kda_out_proj", "attn_gate", "moe_route", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared")
    assert solar_kda_moe.KERNELS == (
        "kda_chunk_fwd", "kda_chunk_bwd", "causal_conv_fwd",
        "causal_conv_bwd", "grouped_mm", "grouped_mm_dw", "moe_sum_pairs")
    paths = re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))
    vocabulary = xplane_meta.KERNELS + solar_kda_moe.KERNELS
    for scope, kernel in (("kda_chunk", "kda_chunk_fwd"),
                          ("kda_chunk", "kda_chunk_bwd"),
                          ("kda_conv", "causal_conv_fwd"),
                          ("kda_conv", "causal_conv_bwd"),
                          ("moe_experts", "grouped_mm"),
                          ("moe_experts", "grouped_mm_dw"),
                          ("moe_combine", "moe_sum_pairs"),
                          ("attn", "flash_fwd"), ("attn", "flash_bwd_dkv")):
        assert any(p.endswith(f"{scope}/{kernel}/pallas_call")
                   for p in paths), kernel
        assert xplane_meta.kernel_of(
            f"jit(step)/block/{scope}/{kernel}/pallas_call",
            vocabulary) == kernel
    # a kernel of this family's is no name of the base vocabulary
    assert xplane_meta.kernel_of(
        "jit(step)/block/kda_chunk/kda_chunk_fwd/pallas_call",
        xplane_meta.KERNELS) == ""
    names_of = xplane_meta.SCOPES + solar_kda_moe.SCOPES
    inside = [p for p in paths if "/block/" in p or p.startswith("block/")]
    assert len(inside) > 200
    assert {xplane_meta.scope_of(p, names_of) for p in inside} <= (
        (BLOCK - {"mlp", "rope"}) | {"attn"} | set(solar_kda_moe.SCOPES))
    # the gate stands between the kernel and the out-projection
    assert any("/attn_gate/" in p for p in inside)


@pytest.mark.parametrize("on_load,on_all", [(6, 0), (4, 2), (0, 0)])
def test_moe_bounds_counts_the_passes_by_their_bound(on_load, on_all):
    """``tools/moe_bounds.py``: a pass of the routed path is two
    ``grouped_mm`` runs under one of the two bound scopes; ops of either
    branch that are no product add to its seconds only."""
    spec = importlib.util.spec_from_file_location(
        "moe_bounds", os.path.join(REPO, "tools", "moe_bounds.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def op(path, t):
        return xplane_meta.Op("x", t, t + 1e-3, 1e-3, path, "", "", 0., 0.)
    head = "jit(step)/checkpoint/block/cond/branch_1_fun"
    ops, t = [op("jit(step)/block/moe_shared/dot_general", 0.0)], 1.0
    for bound, n in (("moe_bound_load", on_load), ("moe_bound_all", on_all)):
        for _ in range(n):
            for tail in ("moe_experts/grouped_mm/pallas_call",
                         "moe_experts/grouped_mm/pallas_call",
                         "moe_experts/grouped_mm_dw/pallas_call",
                         "moe_dispatch/gather"):
                ops.append(op(f"{head}/{bound}/{tail}", t))
                t += 1.0
    got = tool.count(xplane_meta.MetaTrace({0: ops}, {0: []}, []))
    assert got["passes"] == {"moe_bound_load": on_load,
                             "moe_bound_all": on_all}
    assert got["self_s"]["moe_bound_load"] == pytest.approx(4e-3 * on_load)
    assert got["share_on_the_load_bound"] == (
        on_load / (on_load + on_all) if on_load + on_all else None)


def scope_primitives(jaxpr, scope, outer="") -> collections.Counter:
    """The primitives of ``jaxpr`` (calls, scans and remat bodies
    walked, kernel bodies not) whose name stack holds ``scope``."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        if scope in re.findall(r"\w+", path):
            found[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += scope_primitives(sub, scope, path)
    return found


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "recomputed"])
@pytest.mark.parametrize("kernels", [3, 4])
@pytest.mark.parametrize("heads,kv_heads,hidden,moves", [
    (16, 8, 2048, "reshape"),       # head dim 128: addressed where it lies
    (4, 2, 256, "transpose")])      # head dim 64: the transposing entry
def test_train_step_attention_is_the_kernels_alone(heads, kv_heads, hidden,
                                                   moves, kernels, kept,
                                                   monkeypatch):
    """What the ``attn`` scope of the train step holds with the Pallas
    attention on and GQA: the flash kernels — forward, the recompute's
    forward, and the backward in ONE pass (``flash_bwd_dkv`` sums dQ too
    and forms delta itself): THREE; past both VMEM rules, here the
    module constants set to 0 bytes, ``flash_bwd_dq`` runs before it: four;
    where full remat keeps the forward's outputs (their bytes within
    ``KEPT_BYTES``, here the module's own or 0) the recompute's
    forward is gone: one fewer — and the moves its addressing needs:
    bitcast reshapes at head dim 128, transposes at 64, beside the two
    names on the forward's outputs (``name``: no op of the program) and,
    kept, the rounding jax puts on what a checkpoint keeps.  K/V are
    never repeated: no ``broadcast_in_dim``, and nothing sums a group
    back."""
    flash = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    pretrain = importlib.import_module("paddle_tpu.models.llama_pretrain")
    if kernels == 4:
        monkeypatch.setattr(flash, "ONE_PASS_DQ_BYTES", 0)
        monkeypatch.setattr(flash, "ONE_PASS_DKV_BYTES", 0)
    if not kept:
        monkeypatch.setattr(pretrain, "KEPT_BYTES", 0)
    cfg = _cfg(hidden_size=hidden, num_attention_heads=heads,
               num_key_value_heads=kv_heads, num_hidden_layers=1,
               remat=True, loss_chunks=2, use_pallas_attention=True)
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        step = make_train_step(cfg, mesh, lr=1e-2, optimizer="adafactor")
        jaxpr = jax.make_jaxpr(step)(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    found = scope_primitives(jaxpr.jaxpr, "attn")
    assert found["pallas_call"] == kernels - kept, found
    assert set(found) == {"pallas_call", moves, "name"} | (
        {"reduce_precision"} if kept else set()), found


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "recomputed"])
def test_train_step_delta_rule_is_the_kernels_alone(kept, monkeypatch):
    """What the ``kda_chunk`` scope of a delta-rule step holds: the
    recurrence's kernels — forward, the recompute's forward, backward:
    THREE a run of layers; where full remat keeps the forward's outputs
    (their bytes within ``KEPT_BYTES``, here the module's own or what
    flash keeps and no more) the recompute's forward is gone: two —
    beside the names on the forward's outputs (``name``: no op of the
    program).  The row is whole blocks: no pad, no slice, no cast."""
    pretrain = importlib.import_module("paddle_tpu.models.llama_pretrain")
    cfg = _kda_cfg()
    if not kept:
        monkeypatch.setattr(pretrain, "KEPT_BYTES",
                            pretrain.flash_output_bytes(
                                2, 256, cfg.num_attention_heads,
                                cfg.head_dim, cfg.dtype, 1))
    mesh = _mesh()
    with mesh:
        params = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), mesh))
        opt = jax.eval_shape(init_adafactor_state, params)
        step = make_train_step(cfg, mesh, lr=1e-2, optimizer="adafactor")
        jaxpr = jax.make_jaxpr(step)(
            params, opt, jax.ShapeDtypeStruct((2, 257), jnp.int64))
    found = scope_primitives(jaxpr.jaxpr, "kda_chunk")
    assert found["pallas_call"] == 3 - kept, found
    assert set(found) == {"pallas_call", "name"}, found
    # flash's decision was made first and is the same in both forms
    assert scope_primitives(jaxpr.jaxpr, "attn")["pallas_call"] == 2


def test_attention_head_dim_64_matches_the_composite():
    """``_attention`` at 4 query / 2 KV heads of 64 through the flash
    kernels' transposing entry against the XLA composite, values and
    gradients."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.llama_pretrain import _attention
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.normal(0, 1, (2, 256, 4, 64)), jnp.float32)
    k, v, w = (jnp.asarray(rng.normal(0, 1, (2, 256, n, 64)), jnp.float32)
               for n in (2, 2, 4))

    def grads(pallas):
        cfg = _cfg(hidden_size=256, use_pallas_attention=pallas)
        out, vjp = jax.vjp(lambda q, k, v: _attention(q, k, v, cfg),
                           q, k, v)
        return out, vjp(w)

    set_flags({"FLAGS_pallas_interpret": True})
    try:
        out, got = grads(True)
    finally:
        set_flags({"FLAGS_pallas_interpret": False})
    want_out, want = grads(False)
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_attention_mqa_over_mp_still_splits_the_heads():
    """One KV head under ``mp`` 2: K/V are repeated to TWO heads, the
    least count ``mp`` divides (not to the four query heads), and each
    shard runs the kernels on its 2 query heads and 1 KV head; values
    and gradients are the composite's."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.models.llama_pretrain import _attention
    rng = np.random.RandomState(8)
    q, k, v, w = (jnp.asarray(rng.normal(0, 1, (2, 128, n, 64)),
                              jnp.float32) for n in (4, 1, 1, 4))
    mesh = build_mesh(mp=2, devices=jax.devices()[:2])

    def grads(pallas, mesh):
        cfg = _cfg(hidden_size=256, num_key_value_heads=1,
                   use_pallas_attention=pallas)
        fn = lambda q, k, v: _attention(q, k, v, cfg, mesh)  # noqa: E731
        out, vjp = jax.vjp(fn, q, k, v)
        return out, vjp(w), jax.make_jaxpr(fn)(q, k, v)

    set_flags({"FLAGS_pallas_interpret": True})
    try:
        with mesh:
            out, got, jaxpr = grads(True, mesh)
    finally:
        set_flags({"FLAGS_pallas_interpret": False})
    per_shard, = (e for e in jaxpr.eqns if e.primitive.name == "shard_map")
    assert [x.aval.shape[2] for x in per_shard.invars] == [4, 2, 2]
    assert [x.aval.shape[2]
            for x in per_shard.params["jaxpr"].invars] == [2, 1, 1]
    want_out, want, _ = grads(False, None)
    np.testing.assert_allclose(out, want_out, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


# -- the serving step programs ------------------------------------------------
class Recorder:
    """Stands where the engine keeps a jitted step program: lowers the
    first call's arguments, then calls through."""

    def __init__(self, fn, into: dict, key: str):
        self.fn, self.into, self.key = fn, into, key

    def __call__(self, *args):
        if self.key not in self.into:
            self.into[self.key] = scope_names(self.fn.lower(*args))
        return self.fn(*args)


def _engine(metrics_registry=None, **kw):
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), _mesh())
    cache = paged_decode.PagedKVCache(cfg, num_pages=64, pages_max=8,
                                      batch=2, page=16)
    return serving_engine.ContinuousBatchingEngine(
        cfg, params, cache, metrics_registry=metrics_registry, **kw)


def _record_packed_prefill(monkeypatch, into):
    real = serving_engine._prefill_packed
    monkeypatch.setattr(
        serving_engine, "_prefill_packed",
        lambda *a: Recorder(real(*a), into, "prefill_packed"))


def _drive(eng):
    """Three requests, the last two arriving while the first decodes
    (a mixed engine piggybacks only on a running decode)."""
    rng = np.random.RandomState(3)

    def submit():
        eng.submit(rng.randint(1, 128, (int(rng.randint(20, 40)),)),
                   max_new_tokens=6)
    submit()
    eng.step()
    eng.step()
    submit()
    submit()
    done = eng.finished() + eng.run_to_completion()
    assert len(done) == 3


LANES = {
    "sync": (dict(), "_step", DECODE),
    "overlap": (dict(overlap=True), "_step_async", DECODE),
    "horizon": (dict(decode_horizon=2), "_step_multi", DECODE),
    "mixed": (dict(mixed=True, mixed_token_budget=16), "_step_mixed",
              DECODE | PREFILL),
}


@pytest.mark.parametrize("lane", sorted(LANES))
def test_serving_step_programs_carry_the_scopes(lane, monkeypatch):
    kw, attr, want = LANES[lane]
    seen = {}
    _record_packed_prefill(monkeypatch, seen)
    eng = _engine(**kw)
    setattr(eng, attr, Recorder(getattr(eng, attr), seen, lane))
    _drive(eng)
    assert want <= seen[lane], want - seen[lane]
    # the first wave is a packed one in every lane: an idle mixed
    # engine degrades to it
    assert PREFILL <= seen["prefill_packed"], \
        PREFILL - seen["prefill_packed"]


def test_spec_step_carries_the_scopes(monkeypatch):
    from paddle_tpu.models.serving_engine import SpecConfig
    seen = {}
    eng = _engine(spec=SpecConfig(gamma=2, source="prompt_lookup"))
    real = eng._spec_fused
    monkeypatch.setattr(eng, "_spec_fused",
                        lambda: Recorder(real(), seen, "spec"))
    _drive(eng)
    want = BLOCK | {"embed", "layer_scan", "attn", "kv_write", "logits",
                    "sample"}
    assert want <= seen["spec"], want - seen["spec"]


def test_every_scope_of_the_vocabulary_is_tested_somewhere():
    tested = DECODE | PREFILL | {"attn", "loss_head", "optimizer",
                                 "grad_accum"}
    assert tested == set(xplane_meta.SCOPES)


# -- Pallas kernels -----------------------------------------------------------
def pallas_call_names() -> list:
    """The ``name=`` of every ``pallas_call`` site under ops/pallas;
    a site that does not state its work (``cost_estimate=``) fails."""
    names = []
    for path in sorted(glob.glob(os.path.join(
            REPO, "paddle_tpu", "ops", "pallas", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", "") == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path}:{node.lineno} has no name="
                # a constant, or a choice between two: a site that runs
                # ANOTHER AMOUNT OF WORK under a static argument says so
                # in its name (``flash_win_*``: the windowed form)
                name = kw["name"]
                either = [name.body, name.orelse] \
                    if isinstance(name, ast.IfExp) else [name]
                assert all(isinstance(n, ast.Constant) for n in either), \
                    f"{path}:{node.lineno}"
                assert "cost_estimate" in kw, \
                    f"{path}:{node.lineno} ({either[0].value}) declares " \
                    f"no cost_estimate="
                names.extend(n.value for n in either)
    return names


def test_every_pallas_call_site_carries_a_distinct_name():
    names = pallas_call_names()
    # ``flash_attention_split`` runs through the dense kernels' three
    # call sites: a name is one site — but for the windowed form, which
    # runs the same three sites on fewer block pairs under names of its
    # own (26 sites, 29 names)
    assert len(names) == 31 and len(set(names)) == 31
    # the readers' copy still lists the three names retired with their
    # kernels (ROADMAP D14): a subset until a benchmark PR prunes it.
    # A kernel of ONE family's program is named by that family
    # (``KERNELS`` of its module under benchmark/models/), not by the
    # base vocabulary
    own = family_names()[1]
    assert own == {"ssd_scan_fwd", "ssd_scan_bwd", "causal_conv_fwd",
                   "causal_conv_bwd", "grouped_mm", "grouped_mm_dw",
                   "moe_sum_pairs", "flash_win_fwd", "flash_win_bwd_dq",
                   "flash_win_bwd_dkv", "short_conv_fwd", "short_conv_bwd",
                   "kda_chunk_fwd", "kda_chunk_bwd"}
    assert not own & set(xplane_meta.KERNELS)
    ahead = set(AHEAD_OF_EVERY_FAMILY)
    assert set(names) <= set(xplane_meta.KERNELS) | own | ahead
    assert own <= set(names) and ahead <= set(names)
    assert not ahead & (own | set(xplane_meta.KERNELS))


def test_the_kernels_the_entered_cell_reads_have_a_call_site():
    """``flash_attn_roofline_pct.train`` sums the kernels its reader
    names, and the by-kernel split of the train cell shows ``rope``: a
    kernel renamed or deleted in the program would read as 0 there."""
    spec = importlib.util.spec_from_file_location(
        "flash_reader", os.path.join(
            REPO, "benchmark", "layer_metrics",
            "flash_attn_roofline_pct.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert len(reader.KERNELS) == 3
    assert set(reader.KERNELS) | {"rope"} <= set(pallas_call_names())


def test_the_kernels_the_scan_reader_names_have_a_call_site():
    """``ssd_scan_roofline_pct.train`` sums the two scan kernels."""
    spec = importlib.util.spec_from_file_location(
        "scan_reader", os.path.join(
            REPO, "benchmark", "layer_metrics",
            "ssd_scan_roofline_pct.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert set(reader.KERNELS) == {"ssd_scan_fwd", "ssd_scan_bwd"}
    assert set(reader.KERNELS) <= set(pallas_call_names())


def test_a_kernel_name_reaches_the_op_path():
    """``pallas_call(name=)`` binds under a scope of that name: the
    path the device trace shows ends ``<scope>/<name>/pallas_call``."""
    from paddle_tpu.ops.pallas.rope import fused_rope, rope_tables
    cos, sin = rope_tables(16, 128, 1e4)

    def f(x):
        with jax.named_scope("rope"):
            return fused_rope(x, cos, sin)
    text = jax.jit(f).lower(
        jnp.zeros((1, 16, 2, 128), jnp.float32)).as_text(debug_info=True)
    paths = re.findall(r'loc\("([^"]+)"', text)
    assert "jit(f)/rope/rope/pallas_call" in paths
    assert xplane_meta.kernel_of("jit(f)/rope/rope/pallas_call") == "rope"
    # a program that names no kernel has a structural word there
    assert xplane_meta.kernel_of(
        "jit(step)/jvp()/while/body/closed_call/pallas_call") == ""


# -- spans --------------------------------------------------------------------
@pytest.fixture
def cpu_trace(tmp_path):
    """A plain ``jax.profiler.start_trace`` session (what the benchmark's
    ``TraceSlice`` and an operator's TensorBoard capture open); yields a
    function that stops it and reads the file."""
    jax.profiler.start_trace(str(tmp_path))
    stopped = []

    def read():
        if not stopped:
            jax.profiler.stop_trace()
            stopped.append(True)
        with open(xplane.find_xplane(str(tmp_path)), "rb") as f:
            return xplane_meta.parse(f.read())
    yield read
    if not stopped:
        jax.profiler.stop_trace()


def test_record_event_reaches_a_plain_jax_trace(cpu_trace):
    assert not _buffer.enabled
    with RecordEvent("engine.admit", n_requests=3, lane="packed"):
        with RecordEvent("admit.write_pages"):
            jnp.ones((8,)).block_until_ready()
    mt = cpu_trace()
    admit, = mt.spans(("engine.admit",))
    inner, = mt.spans(("admit.write_pages",))
    assert admit.attrs["n_requests"] in (3, "3")
    assert admit.attrs["lane"] == "packed"
    assert admit.start_s <= inner.start_s <= inner.end_s <= admit.end_s
    assert admit.thread == inner.thread
    # no buffer entry: only the Paddle-API Profiler collects those
    assert _drain_spans() == []


def test_record_event_with_no_session_buffers_nothing():
    assert not _buffer.enabled
    with RecordEvent("engine.step"):
        pass
    ev = RecordEvent("engine.fetch")
    ev.begin()
    ev.end()
    ev.end()                                   # idempotent
    assert _drain_spans() == []
    _enable_collection()
    try:
        with RecordEvent("collected"):
            pass
        assert [s[0] for s in _drain_spans()] == ["collected"]
    finally:
        _disable_collection()
        _drain_spans()


def test_record_event_as_a_decorator_is_reentrant(cpu_trace):
    @RecordEvent("engine.drain")
    def down(n):
        return n if n == 0 else down(n - 1)
    assert down(2) == 0
    spans = cpu_trace().spans(("engine.drain",))
    assert len(spans) == 3
    outer, mid, inner = sorted(spans, key=lambda h: h.start_s)
    assert outer.end_s >= mid.end_s >= inner.end_s


def test_ring_span_carries_its_fields_to_the_trace(cpu_trace):
    ring = EventRing()
    with ring.span("engine.admit", n_requests=2, tokens=70,
                   lane="packed"):
        pass
    ev, = ring.recent()
    assert ev["name"] == "engine.admit" and ev["tokens"] == 70
    assert ev["dur_s"] >= 0
    span, = cpu_trace().spans(("engine.admit",))
    assert span.attrs["lane"] == "packed"
    assert int(span.attrs["tokens"]) == 70


def test_engine_spans_nest_on_one_thread_line(cpu_trace):
    reg = MetricsRegistry()
    eng = _engine(metrics_registry=reg)
    _drive(eng)
    mt = cpu_trace()
    names = {h.name for h in mt.spans()}
    assert {"engine.step", "engine.sweep", "engine.admit",
            "engine.dispatch", "engine.fetch", "engine.drain",
            "admit.first_token_tail", "admit.write_pages"} <= names
    write = mt.spans(("admit.write_pages",))[0]
    admit = [h for h in mt.spans(("engine.admit",))
             if h.start_s <= write.start_s and write.end_s <= h.end_s]
    assert admit, "admit.write_pages outside every engine.admit"
    step = [h for h in mt.spans(("engine.step",))
            if h.start_s <= admit[0].start_s
            and admit[0].end_s <= h.end_s]
    assert step, "engine.admit outside every engine.step"
    assert step[0].thread == admit[0].thread == write.thread
    assert admit[0].attrs["lane"] == "packed"
    assert int(admit[0].attrs["n_requests"]) >= 1
    tail = mt.spans(("admit.first_token_tail",))[0]
    assert admit[0].start_s <= tail.start_s <= tail.end_s \
        <= admit[0].end_s
    # one ring event a wave, none a step
    ring_names = [e["name"] for e in eng.metrics.ring.recent()]
    assert ring_names.count("engine.admit") == \
        len(mt.spans(("engine.admit",)))
    assert "engine.step" not in ring_names
    # every engine.step is the engine thread's
    assert len({h.thread for h in mt.spans(("engine.step",))}) == 1


def span_call_sites() -> set:
    """The name of every ``RecordEvent("...")`` / ``<ring>.span("...")``
    site of the program (a literal first argument with a dot in it: the
    tracer's ``ctx.span(phase, t0, t1)`` takes its names from data)."""
    names = set()
    for path in sorted(glob.glob(os.path.join(
            REPO, "paddle_tpu", "**", "*.py"), recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node.args and \
                    (getattr(node.func, "id", "") == "RecordEvent"
                     or getattr(node.func, "attr", "") == "span") and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str) and \
                    "." in node.args[0].value:
                names.add(node.args[0].value)
    return names


def test_every_span_call_site_is_in_the_vocabulary():
    sites = span_call_sites()
    assert sites == set(xplane_meta.SPANS) | set(SPANS_AHEAD)
    assert not set(SPANS_AHEAD) & set(xplane_meta.SPANS)


def test_dataloader_spans(cpu_trace):
    """``dataloader.start`` opens once an iterator, at its construction,
    closes when the first batch is handed out — around that batch's
    ``dataloader.next`` — and carries what the set-up found; it is ONE
    event of the process-wide ring as well, on both clocks."""
    from paddle_tpu.io.worker import MultiprocessBatchIterator
    from paddle_tpu.observability import default_ring
    seq0 = (default_ring().recent()[-1:] or [{"seq": 0}])[0]["seq"]
    t0 = time.time()
    it = MultiprocessBatchIterator(
        _Rows(), [[0, 1], [2, 3]], num_workers=1,
        to_device=lambda b: jnp.asarray(b))
    try:
        assert next(it).shape == (2, 4)
        t1 = time.time()
        assert next(it).shape == (2, 4)
    finally:
        it.shutdown()
    mt = cpu_trace()
    nxt, second = mt.spans(("dataloader.next",))
    wait, _ = mt.spans(("dataloader.wait",))
    dev, _ = mt.spans(("dataloader.to_device",))
    assert nxt.start_s <= wait.start_s <= wait.end_s <= dev.start_s
    assert dev.end_s <= nxt.end_s
    start, = mt.spans(("dataloader.start",))
    assert start.start_s <= nxt.start_s and nxt.end_s <= start.end_s \
        <= second.start_s
    assert int(start.attrs["num_workers"]) == 1
    assert start.attrs["transport"] in ("shm", "queue")
    assert start.thread == nxt.thread
    ev, = [e for e in default_ring().recent(since=seq0)
           if e["name"] == "dataloader.start"]
    assert ev["num_workers"] == 1
    assert ev["transport"] == start.attrs["transport"]
    assert ev["dur_s"] == pytest.approx(start.end_s - start.start_s,
                                        abs=5e-3)
    assert t0 - 1e-3 <= ev["epoch_ns"] * 1e-9 - ev["dur_s"]
    assert ev["epoch_ns"] * 1e-9 <= t1 + 1e-3


class _Rows:
    def __len__(self):
        return 4

    def __getitem__(self, i):
        return np.full((4,), i, np.int64)


def test_server_spans(cpu_trace):
    from paddle_tpu.inference.serving import (GenerationServer,
                                              generate_http)
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), _mesh())
    cache = paged_decode.PagedKVCache(cfg, num_pages=64, pages_max=8,
                                      batch=2, page=16)
    srv = GenerationServer(cfg, params, cache)
    port = srv.start()
    try:
        toks = generate_http(f"http://127.0.0.1:{port}",
                             [int(t) for t in range(1, 20)],
                             max_new_tokens=3, timeout=120.0)
        assert len(toks) == 3
    finally:
        srv.stop()
        for t in srv._threads:
            t.join(30)
    mt = cpu_trace()
    http, = mt.spans(("server.http",))
    deliver = mt.spans(("server.deliver",))
    assert deliver and http.end_s <= deliver[0].start_s
    steps = mt.spans(("engine.step",))
    assert steps and http.thread != steps[0].thread
