"""Full remat keeps an expert layer's routing (``ops/moe.ROUTING_NAMES``
in the policy of ``models/llama_pretrain._remat_wrap`` for the kinds
that route): under the trunk's one checkpoint boundary the router's
product, ``top_k`` and the plan's two sorts are in the gradient's program
once a run of layers — twice with the names left out of the policy — and
the loss and every gradient are the same bits either way, for the three
routing rules and three families' blocks at toy size.  And what the
routed path keeps for its backward, the gate | up product, is written
once: by the kernel, into an array of the one shape both branches of the
``cond`` give, with no pad.
"""

import dataclasses
import functools
import os
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness
from paddle_tpu.models import hybrid_trunk
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_mm import TILE_M, grouped_mm

TOY = os.path.join(harness.HERE, "tests", "toy")
SEQ, ROWS = 128, 1
# family -> (toy configuration, its job, the layers kept): one routed
# layer (rule ``sigmoid``, four streams, a shared expert) and three runs
# of two kinds (``softmax_of_picks``; ``sigmoid_biased_picks``) — one
# kind's layers in TWO runs, one of them longer than a layer, as the
# window cell's kinds lie
FAMILIES = {
    "xing_mhc_moe": ("config_xing.json", "train_job.json", ("mla_moe",)),
    "smallthinker_moe": ("config_smallthinker.json",
                         "train_job_smallthinker.json",
                         ("gqa_moe_window", "gqa_moe_window",
                          "gqa_moe_global", "gqa_moe_window")),
    "lfm2_conv_moe": ("config_lfm2.json", "train_job_lfm2.json",
                      ("conv_moe", "conv_moe", "gqa_qknorm_moe", "conv_moe")),
}


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (kernel bodies apart)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _routing_ops(closed_jaxpr, published: int) -> dict:
    """The sorts, the ``top_k`` and the router's products (fp32
    ``Precision.HIGHEST`` onto ``published`` scores) in a program."""
    out = {"sort": 0, "top_k": 0, "router": 0}
    for eqn in _eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("sort", "top_k"):
            out[name] += 1
        elif name == "dot_general" and "HIGHEST" in str(
                eqn.params["precision"]) \
                and eqn.outvars[0].aval.shape[-1] == published:
            out["router"] += 1
    return out


@functools.lru_cache(maxsize=None)
def _toy(family: str):
    """(cfg, blocks, x): the family's toy configuration cut to the layers
    of ``FAMILIES``, seeded leaves and one row of inputs, in the cells'
    dtypes — bf16 rows, fp32 leaves and scores.  (All in fp32 the CPU
    compiles the forward loop's router product and the backward loop's
    to different last bits, and "the same bits" is no longer the
    recomputing program's own property.)"""
    conf_file, job_file, layers = FAMILIES[family]
    conf = harness.load_json(os.path.join(TOY, conf_file))
    job = dict(harness.load_json(os.path.join(TOY, job_file)), seq=SEQ,
               batch=ROWS)
    cell = harness.Cell.detached(f"toy-{family}.train_job", 1, conf, job)
    cfg = dataclasses.replace(
        cell.family.build_cfg(conf, True, job), layer_types=layers, num_hidden_layers=len(layers),
        rope_layout=None, sliding_window_layout=None)
    hybrid_trunk.check(cfg)
    assert cfg.remat and cfg.experts_held < cfg.n_routed_experts
    assert (cfg.dtype, cfg.param_dtype) == (jnp.bfloat16, jnp.float32)
    key = jax.random.PRNGKey(46)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (ROWS, SEQ, cfg.hidden_size), cfg.dtype)
    return cfg, hybrid_trunk.init_blocks(cfg, key), x


def _loss(cfg, blocks, x):
    out = hybrid_trunk.trunk(blocks, x, cfg, None)
    return jnp.mean(jnp.square(out.astype(jnp.float32)))


def _routed_runs(cfg) -> int:
    return sum(kind in hybrid_trunk.ROUTED_KINDS
               for kind, _, _ in hybrid_trunk.layer_runs(cfg.layer_types))


@functools.lru_cache(maxsize=None)
def _program(family: str, kept: bool, sliced: bool = False):
    """The toy trunk's loss and gradients (of every leaf and of the
    input) with the routing's names in the boundary's policy or left
    out of it: the program's jaxpr, and what it computes.  ``sliced``:
    the routed path is handed a layer's own slices of the expert stacks
    and neither the stacks nor an index (slice, then call: the program
    before the grouped products read a stack in place; without the
    routing kept the trunk hands on no stack either)."""
    cfg, blocks, x = _toy(family)
    fn = jax.jit(jax.value_and_grad(functools.partial(_loss, cfg),
                                    argnums=(0, 1)))
    real = moe.routed_ffn
    with mock.patch.object(hybrid_trunk, "ROUTED_KINDS",
                           hybrid_trunk.ROUTED_KINDS if kept else ()), \
            mock.patch.object(moe, "routed_ffn",
                              (lambda *a: real(*a[:6])) if sliced else real):
        jax.clear_caches()      # a traced loop body is kept by its avals
        traced = fn.trace(blocks, x)
        return traced.jaxpr, traced.lower().compile()(blocks, x)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_recompute_has_no_route_and_no_plan(family):
    """A run of routed layers is a forward loop and a backward loop, the
    recompute in the second: with the routing kept the router's product,
    its ``top_k`` and the plan's two sorts are in the first alone."""
    cfg = _toy(family)[0]
    runs = _routed_runs(cfg)
    assert runs == (1 if family == "xing_mhc_moe" else 3)
    count = lambda kept: _routing_ops(_program(family, kept)[0],
                                      cfg.n_routed_experts)
    assert count(True) == {"sort": 2 * runs, "top_k": runs, "router": runs}
    assert count(False) == {"sort": 4 * runs, "top_k": 2 * runs,
                            "router": 2 * runs}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_kept_routing_gives_the_recomputed_routing_s_bits(family):
    (kept, kept_g), (again, again_g) = (_program(family, kept)[1]
                                        for kept in (True, False))
    assert np.isfinite(float(kept)) and float(kept) == float(again)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(kept_g),
                            jax.tree_util.tree_leaves(again_g)):
        # a bias that selects reads no gradient, by construction
        assert (float(jnp.max(jnp.abs(a))) > 0) \
            != ("expert_bias" in jax.tree_util.keystr(path)), \
            jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def _grouped_mm_depths(closed_jaxpr) -> set:
    """The layers in the stacks that a program's ``grouped_mm`` read."""
    return {eqn.invars[-1].aval.shape[0] for eqn in _eqns(closed_jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == "grouped_mm"}


@pytest.mark.parametrize("family", sorted(FAMILIES)[:2])
def test_a_trunk_reading_the_stacks_gives_the_sliced_layers_bits(family):
    """The trunk hands a routed layer the kind's WHOLE expert stacks and
    its index (``routed_ffn``'s ``stacked``); the program before it
    handed on the layer's slices alone.  Under full remat with the
    routing kept, through a trunk with a kind's layers in two runs, the
    loss, every leaf's gradient — each layer's of the expert stacks, none
    of them zero — and what is kept of the routing are the same bits;
    every grouped product reads a stack of its kind's depth, and the
    backward loops hand on a LAYER's gradient a layer."""
    cfg, blocks, _ = _toy(family)
    assert cfg.remat and cfg.remat_policy == "full"
    layers, runs = cfg.layer_types, hybrid_trunk.layer_runs(cfg.layer_types)
    assert max(sum(k == kind for k, _, _ in runs) for kind in layers) == 2
    jaxpr, (got, got_g) = _program(family, True)    # (the cache's key)
    sliced_jaxpr, (want, want_g) = _program(family, True, True)
    assert np.isfinite(float(want)) and float(got) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        name = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        if "we_" in name:
            assert a.shape[0] == layers.count(path[1].key) and all(
                float(jnp.max(jnp.abs(layer))) > 0 for layer in a), name
    # the routing is decided once a run either way
    routing = lambda jp: _routing_ops(jp, cfg.n_routed_experts)
    assert routing(jaxpr) == routing(sliced_jaxpr) \
        == {"sort": 2 * len(runs), "top_k": len(runs), "router": len(runs)}
    assert _grouped_mm_depths(jaxpr) == {layers.count(k) for k in layers}
    assert _grouped_mm_depths(sliced_jaxpr) == {1}
    # no loop hands on experts but the backward loops, their gradients: a
    # scan of n layers gives [n, held, ...] of each of the two leaves once
    held = [blocks[layers[0]][leaf].shape[1:]
            for leaf in ("we_gate_up", "we_down")]
    given = [v.aval.shape[0] for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "scan" for v in eqn.outvars
             if v.aval.shape[1:] in held]
    assert sorted(given) == sorted(2 * [b - a for _, a, b in runs])


@pytest.mark.parametrize("rule", moe.RULES)
def test_the_picks_derivative_is_lax_top_k_s(rule):
    """``route`` differentiates its ``top_k`` by the picks it NAMES; the
    gates, the picks and both gradients are the bits of the same rule
    written over ``lax.top_k`` and its own derivative."""
    T, c, pub, k, scale = 512, 128, 16, 3, 2.5
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (T, c), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (c, pub)) / c ** 0.5
    co = jax.random.normal(jax.random.fold_in(key, 2), (T, k), jnp.float32)
    bias = 0.02 * jax.random.normal(jax.random.fold_in(key, 3), (pub,)) \
        if rule == "sigmoid_biased_picks" else None

    def plain(x, w):
        z = jnp.dot(x, w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        if rule == "softmax_of_picks":
            top, idx = jax.lax.top_k(z, k)
            return idx, scale * jax.nn.softmax(top, axis=-1)
        s = jax.nn.sigmoid(z)
        if bias is not None:
            _, idx = jax.lax.top_k(jax.lax.stop_gradient(s + bias), k)
            top = jnp.take_along_axis(s, idx, axis=-1)
            return idx, scale * top / (jnp.sum(top, -1, keepdims=True)
                                       + 1e-6)
        top, idx = jax.lax.top_k(s, k)
        return idx, scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)

    def gates_and_grads(route):
        gate, pull, idx = jax.vjp(lambda x, w: route(x, w)[::-1], x, w,
                                  has_aux=True)
        return (idx, gate) + pull(co)
    named = gates_and_grads(
        lambda x, w: moe.route(x, w, k, scale, rule, bias))
    for a, b in zip(named, gates_and_grads(plain)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert named[0].dtype == jnp.int32 and float(jnp.max(jnp.abs(
        named[2]))) > 0


def _routed_case(T, c, f, held, k, seed=3):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (T, c), jnp.float32).astype(bf),
            jax.random.uniform(ks[1], (T, k), jnp.float32, 0.1, 1.0),
            jax.random.normal(ks[2], (held, c, 2 * f), jnp.float32) / c ** .5,
            jax.random.normal(ks[3], (held, f, c), jnp.float32) / f ** .5,
            jax.random.normal(ks[4], (T, c), jnp.float32).astype(bf))


def test_the_kept_product_is_written_where_it_is_kept():
    """What ``routed_ffn`` keeps of its forward for the backward: the
    gate | up product in an array of the bound of any load (one shape
    from both branches), its first ``load_rows`` rows written by the
    kernel itself — the program pads nothing to that shape."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, _ = _routed_case(T, c, f, held, k)
    idx = jnp.stack([jnp.arange(T) % pub, (jnp.arange(T) + 5) % pub],
                    1).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    full = p.row_pair.shape[0]
    assert p.load_rows == 1536 < full == 4608
    assert int(p.n_tiles[0]) * TILE_M <= p.load_rows    # the load's bound
    fwd = functools.partial(moe._routed_fwd, p=p, act="silu")
    assert not [eqn for eqn in _eqns(
        jax.make_jaxpr(fwd)(x, gate, wgu, wd).jaxpr)
        if eqn.primitive.name == "pad"
        and eqn.outvars[0].aval.shape == (full, 2 * f)]
    y, res = jax.jit(fwd)(x, gate, wgu, wd)
    assert res[-1].shape == (full, 2 * f)
    rows = x[jnp.maximum(p.row_pair[:p.load_rows], 0) // k]
    want = grouped_mm(rows, wgu, p.tile_expert[:p.load_rows // TILE_M],
                      p.n_tiles)
    tiles = int(p.n_tiles[0]) * TILE_M
    np.testing.assert_array_equal(np.asarray(res[-1][:tiles]),
                                  np.asarray(want[:tiles]))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(moe.routed_ffn(x, gate, wgu, wd, p)))


@pytest.mark.parametrize("act", moe.ACTIVATIONS)
def test_a_load_past_its_bound_loses_nothing(act):
    """All the tokens on the held experts: the plan's tiles do not fit
    the load's bound and the passes run on the bound of any load, where
    the kept product is the kernel's whole result.  Value and the four
    gradients are BIT FOR BIT those of the same pairs planned with
    ``held == published``, the program of one bound."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k, seed=5)
    local = jnp.stack([jnp.arange(T) % held, (jnp.arange(T) + 1) % held],
                      1).astype(jnp.int32)
    p = moe.plan(local + first, first, held, pub)
    one = moe.plan(local, 0, held, held)
    assert int(p.n_tiles[0]) * TILE_M > p.load_rows
    assert one.load_rows == one.row_pair.shape[0] == p.row_pair.shape[0]
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def value_and_grads(p):
        y, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p, act), x, gate,
                         wgu, wd)
        return (y,) + vjp(co)
    got, want = jax.jit(value_and_grads)(p), jax.jit(value_and_grads)(one)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _moe_passes():
    """``tools/moe_passes.py`` as a module."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "moe_passes", os.path.join(os.path.dirname(harness.HERE), "tools",
                                   "moe_passes.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_moe_passes_splits_the_scopes_by_pass():
    """``tools/moe_passes.py``: an op of a ``moe_*`` scope is charged to
    its scope AND the pass its path names; a ``sort`` or a ``pad`` is
    listed wherever it runs."""
    from benchmark import xplane_meta
    from benchmark.models import smallthinker_moe
    tool = _moe_passes()

    def op(path, category="fusion"):
        return xplane_meta.Op("x", 0.0, 1e-3, 1e-3, path, "", category,
                              0., 0.)
    fwd = "jit(step)/jvp(layer_scan)/while/body/closed_call/block"
    bwd = "jit(step)/transpose(jvp(layer_scan))/while/body/closed_call" \
          "/checkpoint"
    mt = xplane_meta.MetaTrace({0: [
        op(f"{fwd}/moe_dispatch/sort", "sort"),
        op(f"{fwd}/moe_dispatch/gather"),
        op(f"{bwd}/rematted_computation/block/moe_dispatch/gather"),
        op(f"{bwd}/rematted_computation/block/moe_experts/pad", "pad"),
        op(f"{bwd}/block/moe_combine/gather"),
        op(f"{bwd}/block/attn/dot_general"),
        op("jit(step)/transpose(jvp(embed))/scatter-add", "sort")]},
        {0: []}, []).named(xplane_meta.SCOPES + smallthinker_moe.SCOPES,
                           xplane_meta.KERNELS)
    got = tool.by_pass(mt)
    assert got["scope_pass"] == pytest.approx({
        "moe_dispatch|forward": 2e-3, "moe_dispatch|recompute": 1e-3,
        "moe_experts|recompute": 1e-3, "moe_combine|backward": 1e-3})
    assert got["sort_pad"] == pytest.approx({
        "sort|moe_dispatch|forward": 1e-3, "pad|moe_experts|recompute": 1e-3,
        "sort|embed|backward": 1e-3})
    assert len(got["recompute_ops"]) == 2


def test_moe_passes_lists_the_grouped_products_by_call_site():
    """``tools/moe_passes.py``'s ``calls``: a call site is one instruction
    of the step; its text gives ``[K, N]``, the grid's tiles and the
    experts held, its runs the ms a run — set against the ms the tiles
    EXPECTED in use take at the peak, the rest spread over the changes
    of expert."""
    from benchmark import xplane_meta
    tool = _moe_passes()
    cell = harness.find_cell("lfm2-24b-a2b.pretrain-8k-conv-moe")
    tiles = "s32[144]{0:T(256)} %slice.65, s32[1]{0:T(128)} %gte.630"
    # the experts of a kind's three layers stacked, and the layer
    layer = "s32[1]{0:T(128)} %gte.785"
    mm = ("%grouped_mm.26 = bf16[69632,3072]{1,0:T(8,128)(2,1)} "
          f"custom-call({tiles}, {layer}, bf16[36864,2048]{{1,0}} "
          "%fusion.13, f32[3,16,2048,3072]{3,2,1,0:T(8,128)} %gte.390), "
          'custom_call_target="tpu_custom_call"')
    dx = ("%grouped_mm.32 = bf16[36864,1536]{1,0} "
          f"custom-call({tiles}, {layer}, bf16[36864,2048]{{1,0}} "
          "%fusion.24, f32[1,16,1536,2048]{3,2,1,0} %gte.631)")
    dw = ("%grouped_mm_dw.10 = f32[16,2048,3072]{2,1,0:T(8,128)} "
          f"custom-call({tiles}, bf16[36864,2048]{{1,0}} %fusion.27, "
          "bf16[36864,3072]{1,0} %pad_maximum_fusion.1)")

    def op(text, kernel, path, ms):
        return xplane_meta.Op(
            text, 0.0, 1e-3 * ms, 1e-3 * ms,
            f"jit(step)/{path}/moe_bound_load/moe_experts/{kernel}"
            "/pallas_call", "", "custom-call", 0., 0.)
    fwd, bwd = "jvp(layer_scan)", "transpose(jvp(layer_scan))"
    mt = xplane_meta.MetaTrace({0: [
        op(mm, "grouped_mm", fwd, 1.7), op(mm, "grouped_mm", fwd, 1.9),
        op(dx, "grouped_mm", bwd, 0.9), op(dw, "grouped_mm_dw", bwd, 1.8),
        op("%fusion.1 = f32[8]{0} fusion()", "gather", fwd, 5.0)]},
        {0: []}, []).named(*xplane_meta.names_of(cell))
    rows = {r["site"]: r for r in tool.by_call(mt, cell, 2, 197e12)}
    assert sorted(rows) == ["grouped_mm.26", "grouped_mm.32",
                            "grouped_mm_dw.10"]
    # 16,384 pairs expected of 16,384 tokens: 64 tiles + half a tile each
    # of 16 experts; 3 panels of 1,024, 2 of 768, 2 x 2 blocks of dw
    assert [(r["K"], r["N"], r["tiles"], r["tiles_in_use"], r["changes"],
             r["pass"], r["bound"]) for r in rows.values()] == [
        (2048, 3072, 144, 72.0, 48, "forward", "moe_bound_load"),
        (2048, 1536, 144, 72.0, 32, "backward", "moe_bound_load"),
        (2048, 3072, 144, 72.0, 64, "backward", "moe_bound_load")]
    gate_up = rows["grouped_mm.26"]
    assert gate_up["runs_a_step"] == 1.0 and gate_up["ms_a_run"] == 1.8
    at_peak = 2e3 * 72 * 256 * 2048 * 3072 / 197e12
    assert gate_up["ms_at_peak"] == pytest.approx(at_peak, abs=1e-4)
    assert gate_up["us_a_change"] == pytest.approx(
        1e3 * (1.8 - at_peak) / 48, abs=0.01)


def test_moe_passes_lists_what_the_layer_loops_hold_by_kind():
    """``tools/moe_passes.py``'s ``layer_scan``: an op that no inner
    scope claims is a row of its kind — the instruction's name without
    its number, the primitive its path ends in, the pass, its result(s) —
    with its count, ms and GB a step; the rows that only MOVE the fp32
    experts, a layer's, a run's or a stack's, add up as copies (what a
    kernel handed ``stack[layer]`` costs; none where it reads the stack)
    and as writes (the gradient's side)."""
    from benchmark import xplane_meta
    tool = _moe_passes()
    cell = harness.find_cell("smallthinker-21b-a3b.pretrain-16k-moe")
    leaves = tool.expert_leaves(cell)
    assert leaves == [(16, 2560, 1536), (16, 768, 2560)]

    def op(text, path, ms, gb, category="loop fusion"):
        return xplane_meta.Op(text, 0.0, 1e-3 * ms, 1e-3 * ms,
                              f"jit(step)/{path}", "", category, 0.,
                              1e9 * gb)
    fwd = "jvp(layer_scan)"
    bwd = "transpose(jvp(layer_scan))"
    sliced = ("%dynamic-slice_bitcast_fusion.{} = f32[16,2560,1536]"
              "{{2,1,0:T(8,128)}} fusion(f32[6,16,2560,1536]{{3,2,1,0}} %p)")
    ops = [
        # a layer's experts out of the stack: both loops, two steps
        op(sliced.format(76), f"{fwd}/while/body/squeeze", 0.77, 0.5),
        op(sliced.format(76), f"{fwd}/while/body/squeeze", 0.77, 0.5),
        op(sliced.format(56), f"{bwd}/while/body/squeeze", 0.75, 0.5),
        # both runs' slices of a stack, ONE fusion of two results
        op("%fusion.1094 = (f32[2,16,768,2560]{3,2,1,0}, f32[6,16,768,2560]"
           "{3,2,1,0}) fusion(f32[8,16,768,2560]{3,2,1,0} %p.3), kind=kLoop",
           f"{fwd}/layer_scan/slice", 2.3, 1.5),
        # the write side: a layer's gradient, the runs' joined, the zeros
        op("%constant_dynamic-update-slice_fusion.38 = f32[3,16,768,2560]"
           "{3,2,1,0} fusion(f32[3,16,768,2560]{3,2,1,0} %gte.1)",
           f"{bwd}/while/body/dynamic_update_slice", 0.4, 0.25),
        op("%constant_dynamic-update-slice_fusion.22 = f32[6,16,2560,1536]"
           "{3,2,1,0} fusion(f32[6,16,2560,1536]{3,2,1,0} %c, f32[3,16,2560,"
           "1536]{3,2,1,0} %while.232)", f"{bwd}/layer_scan/concatenate",
           2.2, 1.5),
        op("%broadcast_in_dim.7 = f32[3,16,2560,1536]{3,2,1,0} broadcast("
           "f32[]{:T(128)} %c.1)", f"{bwd}/broadcast_in_dim", 1.0, 0.75,
           "broadcast"),
        # XLA's own prefetch of a layer's gradient block before its write
        op("%copy-done.101 = f32[1,16,768,2560]{3,2,1,0:T(8,128)S(1)} "
           "copy-done((f32[1,16,768,2560]{3,2,1,0:T(8,128)S(1)}, f32[1,16,"
           "768,2560]{3,2,1,0}, u32[]{:S(2)}) %copy-start.101)",
           f"{bwd}/while", 0.3, 0.25, "copy-done"),
        # not the experts': a saved input; computing: the loop's own
        op("%dynamic-slice_bitcast_fusion.53 = bf16[1,16384,3584]{2,1,0} "
           "fusion(bf16[3,1,16384,3584]{3,2,1,0} %p.9)",
           f"{bwd}/while/body/squeeze", 1.0, 0.7),
        op("%fusion.1570 = f32[1048576]{0:T(1024)S(1)} fusion(s32[98304]{0} "
           "%r), kind=kCustom", f"{bwd}/while", 2.0, 0.01, "custom fusion"),
        op("%fusion.9 = bf16[16384,2560]{1,0} fusion()",
           f"{fwd}/while/body/closed_call/block/attn_out/dot_general", 9, 1)]
    mt = xplane_meta.MetaTrace({0: ops}, {0: []}, []).named(
        *xplane_meta.names_of(cell))
    got = tool.under_the_loops(mt, 2, leaves)
    assert got["ms"] == pytest.approx((3 * 0.77 - 0.02 + 2.3 + 0.4 + 2.2
                                       + 1.0 + 0.3 + 1.0 + 2.0) / 2, abs=2e-3)
    assert got["experts"] == {
        "copy": {"count": 2.0, "ms": pytest.approx(2.295),
                 "GB": pytest.approx(1.5)},
        "write": {"count": 1.5, "ms": pytest.approx(1.8),
                  "GB": pytest.approx(1.25)},
        "prefetch": {"count": 0.5, "ms": pytest.approx(0.15),
                     "GB": pytest.approx(0.125)}}
    rows = {(r["op"], r["pass"], r["result"]): r for r in got["kinds"]}
    assert len(rows) == len(got["kinds"]) == 9      # the block's op: none
    first = rows["dynamic-slice_bitcast_fusion", "forward",
                 "f32[16,2560,1536]"]
    assert (first["of"], first["moves"], first["of_experts"],
            first["count"], first["ms"], first["GB"]) == (
        "squeeze", "copy", True, 1.0, 0.77, 0.5)
    two = rows["fusion", "forward", "f32[2,16,768,2560] f32[6,16,768,2560]"]
    assert (two["of"], two["moves"], two["of_experts"]) == (
        "slice", "copy", True)
    assert [(r["moves"], r["of_experts"]) for r in got["kinds"]
            if r["op"] in ("fusion", "dynamic-slice_bitcast_fusion")
            and r["pass"] == "backward"] == [("", False), ("copy", False),
                                             ("copy", True)]
    assert {r["of"]: r["moves"] for r in got["kinds"]
            if r["op"].startswith(("constant", "broadcast"))} == {
        "dynamic_update_slice": "write", "concatenate": "write",
        "broadcast_in_dim": "write"}
    # the grouped products reading the stacks in place: no copy is left
    there = tool.under_the_loops(xplane_meta.MetaTrace(
        {0: ops[4:]}, {0: []}, []).named(*xplane_meta.names_of(cell)), 2,
        leaves)
    assert there["experts"]["copy"] == {"count": 0.0, "ms": 0.0, "GB": 0.0}
    assert there["experts"]["write"] == got["experts"]["write"]
