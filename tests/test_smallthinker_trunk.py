"""Window and global grouped-query attention before routed ReGLU experts
on the normal path: ``make_train_step`` over ``models/hybrid_trunk.py``'s
kinds ``gqa_moe_global`` / ``gqa_moe_window`` (``ops/moe.py``'s second
routing rule and ReLU gate, ``flash_attention`` dense and windowed) held
to the plain reference ``benchmark/models/smallthinker_moe_reference.py``
at toy size — float32 on the CPU, the published PATTERN (global, three
window layers, global), four query / two KV heads of 128, a window of 64
on rows of 256, two of eight experts held from the third on, top-3 of
the logits, seeded weights.  Then one thing is changed at a time, in
the program or in the reference, and the comparison must fail.  The
share, the skewed loads under the ReLU gate and what ``check`` refuses
have tests of their own.
"""

import dataclasses
import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness, reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    LlamaPretrainConfig, build_mesh, init_adafactor_state, make_train_step)
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_mm import TILE_M

TOY = os.path.join(harness.HERE, "tests", "toy")
SEED, SEQ, ROWS = 2**31 + 77, 256, 2
SOUND, BROKEN = 1e-5, 1e-3
# The two-step CHANGE is held looser than loss and gradient: a pick of
# the router is a comparison, and after one step the program's and the
# reference's parameters differ in the seventh digit — enough to turn a
# near-tie of one token's logits the other way.
SOUND_CHANGE = 1e-3


@pytest.fixture(scope="module")
def toy():
    conf = harness.load_json(os.path.join(TOY, "config_smallthinker.json"))
    job = dict(harness.load_json(
        os.path.join(TOY, "train_job_smallthinker.json")),
        seq=SEQ, batch=ROWS)
    cell = harness.Cell.detached("toy-smallthinker.train_job", 1, conf, job)
    cfg = dataclasses.replace(cell.family.build_cfg(conf, True, job),
                              dtype=jnp.float32)
    key = cell.family.seed_key(SEED)
    batches = [np.stack([train_cell.token_row(SEED, ROWS * s + r, SEQ,
                                              conf["vocab_size"])
                         for r in range(ROWS)]) for s in range(2)]
    return types.SimpleNamespace(
        cell=cell, conf=conf, job=job, cfg=cfg, batches=batches,
        leaf0=train_cell.leaf_maker(cell.family, cfg, key))


def follow(toy, cfg):
    """The program's two steps under ``cfg``: losses, the first
    gradient's norm and the two-step change, leaf by leaf."""
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = toy.cell.family.make_params(toy.cfg, SEED, mesh)
        step = make_train_step(cfg, mesh, lr=toy.job["lr"],
                               weight_decay=toy.job["weight_decay"],
                               optimizer="adafactor")
        return train_cell.follow_program(
            step, params, init_adafactor_state(params), toy.batches,
            toy.leaf0)[2]


def first_step_gap(prog, ref):
    """Loss of both steps and the first gradient, leaf by leaf."""
    numbers = train_cell.gap_numbers(prog, ref)
    return max(v for k, v in numbers.items()
               if k != "param_change_worst_leaf_gap")


@pytest.fixture(scope="module")
def sound(toy):
    return follow(toy, toy.cfg)


@pytest.fixture(scope="module")
def ref(toy):
    return train_cell.run_reference(toy.cell, toy.job, toy.leaf0,
                                    toy.batches)


def test_the_toy_has_what_the_cell_has(toy):
    cfg = toy.cfg
    assert cfg.layer_types == ("gqa_moe_global",) + ("gqa_moe_window",) * 3 \
        + ("gqa_moe_global",)
    assert cfg.rope_layout == cfg.sliding_window_layout == (0, 1, 1, 1, 0)
    # two runs of the global kind around one of the window kind
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("gqa_moe_global", 0, 1), ("gqa_moe_window", 0, 3),
        ("gqa_moe_global", 1, 2)]
    # a head's width is stated, not hidden / heads (32 here)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (4, 2, 128)
    assert SEQ == 4 * cfg.sliding_window_size
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok) == (8, 2, 2, 3)
    for kind in hybrid_trunk.GQA_MOE_KINDS:
        shapes = hybrid_trunk.kind_shapes(cfg, kind)
        assert shapes["wq"] == (128, 512) and shapes["wo"] == (512, 128)
        assert shapes["wk"] == shapes["wv"] == (128, 256)
        assert shapes["we_gate_up"] == (2, 128, 256)    # rank 4 stacked
        assert shapes["w_router"] == (128, 8)           # published width
        assert not any(nm.startswith("ws_") for nm in shapes)


@pytest.mark.parametrize("what", ["loss_rel_gap.step0", "loss_rel_gap.step1",
                                  "grad_norm_worst_leaf_gap",
                                  "param_change_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 2 * 9 + 3
    assert train_cell.gap_numbers(sound, ref)[what] < (
        SOUND_CHANGE if what.startswith("param") else SOUND)


def test_logits_match_the_reference(toy):
    cfg, fam = toy.cfg, toy.cell.family
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = fam.make_params(cfg, SEED, mesh)
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        x = jnp.take(params["embed"], ids[None], axis=0)
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["lm_head"])[0]
    # the first and the last query block, and both sides of a window's end
    rows = np.asarray([0, 1, 63, 64, 65, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))


# one thing changed in the PROGRAM's configuration
PROGRAM = {
    # 63 divides into no block: the composite's mask, one key short
    "window_one_key_short": lambda c: dict(sliding_window_size=63),
    "other_experts_held": lambda c: dict(expert_first=3),
    "top_two": lambda c: dict(num_experts_per_tok=2),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    cfg = dataclasses.replace(toy.cfg, **PROGRAM[what](toy.cfg))
    assert first_step_gap(follow(toy, cfg), ref) > BROKEN


# one line changed in the REFERENCE, each of them alone
REFERENCE = {
    "router_reads_ln2": (
        "return (h + _experts(u, idx, g, w, d, mm))[0]",
        "idx, g = _route(u, w, d, mm)\n"
        "        return (h + _experts(u, idx, g, w, d, mm))[0]"),
    "softmax_before_the_top_k": (
        "return idx, jax.nn.softmax(top, axis=-1)",
        "return idx, jnp.exp(top - jax.scipy.special.logsumexp("
        "z, -1, keepdims=True))"),
    "silu_for_relu": ("jax.nn.relu(mm(u, wgu[:, :f]))",
                      "jax.nn.silu(mm(u, wgu[:, :f]))"),
    "window_off_by_one_key": ('seen &= j > i - d["window"]',
                              'seen &= j >= i - d["window"]'),
    "rotation_on_a_global_layer": (
        "if window: q, k = rope(q,", "if True:\n        q, k = rope(q,"),
    "window_on_a_global_layer": (
        "if window: seen &= j >", "if True:\n        seen &= j >"),
    "gates_over_the_held_picks_only": (
        "return idx, jax.nn.softmax(top, axis=-1)",
        "return idx, jax.nn.softmax(jnp.where((idx >= d[\"first\"]) & "
        "(idx < d[\"first\"] + d[\"held\"]), top, -jnp.inf), axis=-1)"),
}


def altered_reference(old: str, new: str):
    path = os.path.join(harness.HERE, "models",
                        "smallthinker_moe_reference.py")
    with open(path) as f:
        src = f.read()
    # the needle may span lines in the file
    pattern = r"\s+".join(re.escape(w) for w in old.split())
    src, n = re.subn(pattern, lambda m: new, src)
    assert n == 1, old
    mod = types.ModuleType("benchmark.models.smallthinker_moe_altered")
    mod.__package__ = "benchmark.models"
    exec(compile(src, path, "exec"), mod.__dict__)
    return mod


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    cell = types.SimpleNamespace(
        block_reference=altered_reference(*REFERENCE[what]),
        conf=toy.conf)
    other = train_cell.run_reference(cell, toy.job, toy.leaf0, toy.batches)
    gap = first_step_gap(sound, other)
    # a token whose picks are all away would make NaN gates of the held-
    # picks-only rule: that too is no agreement
    assert not gap <= BROKEN


# -- the share ---------------------------------------------------------------
def _layer_weights(key, c, f, experts):
    ks = jax.random.split(key, 3)
    n = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) \
        / fan ** 0.5
    return {"w_router": n(ks[0], (c, experts), c),
            "we_gate_up": n(ks[1], (experts, c, 2 * f), c),
            "we_down": n(ks[2], (experts, f, c), f)}


def _share(w, first, held):
    return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                we_down=w["we_down"][first:first + held])


def test_the_shares_add_up_to_the_whole_layer(toy):
    """The four shares' parts are what the UNCUT reference gives for the
    whole layer: nothing is computed on every chip alike (no shared
    expert), so nothing is counted once.  The router reads another
    tensor than the experts."""
    from benchmark.models import smallthinker_moe_reference as blk
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    w = _layer_weights(jax.random.PRNGKey(3), c, f, 8)
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 300, c), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 300, c), jnp.float32)
    whole = dict(blk.dims_of(dict(toy.conf, moe_num_primary_experts=8,
                                  expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    idx, g = blk._route(y, w, whole, mm)
    assert float(jnp.max(jnp.abs(jnp.sum(g, -1) - 1))) < 1e-6
    want = blk._experts(u, idx, g, w, whole, mm)

    def part(first):
        cfg = dataclasses.replace(toy.cfg, expert_first=first,
                                  experts_held=2)
        bp = _share(w, first, 2)
        return hybrid_trunk._expert_layer(
            bp, u, cfg, hybrid_trunk._routing(bp, y, cfg,
                                              "softmax_of_picks"), "relu")
    parts = [part(first) for first in (0, 2, 4, 6)]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < SOUND * scale
    # and a share alone is the reference's share, and no share is nothing
    one = blk._experts(u, idx, g, _share(w, 2, 2),
                       dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] - one))) < SOUND * scale
    assert all(float(jnp.max(jnp.abs(p))) > 0.01 * scale for p in parts)


def test_the_second_rule_is_a_softmax_over_the_picked_logits():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
    idx, gate = moe.route(x, w, 6, 1.0, "softmax_of_picks")
    z = np.asarray(jnp.dot(x, w, precision="highest"))
    order = np.argsort(-z, axis=1)[:, :6]
    assert (np.sort(np.asarray(idx), 1) == np.sort(order, 1)).all()
    picked = np.take_along_axis(z, np.asarray(idx), 1)
    want = np.exp(picked) / np.exp(picked).sum(1, keepdims=True)
    assert np.abs(np.asarray(gate) - want).max() < 1e-6
    # the first rule is untouched by the second
    idx1, gate1 = moe.route(x, w, 6, 2.0)
    s = 1 / (1 + np.exp(-z))
    top = np.take_along_axis(s, np.asarray(idx1), 1)
    assert np.abs(np.asarray(gate1) - 2 * top / top.sum(1, keepdims=True)
                  ).max() < 1e-6
    with pytest.raises(ValueError):
        moe.route(x, w, 6, 1.0, "softmax")


# -- nothing dropped under the ReLU gate -------------------------------------
def _filling(T, first, held, published, rows):
    away = first + held if first + held < published else 0
    idx = np.full((T, 2), away, np.int32)
    n = rows - (held - 1) * TILE_M
    idx[:n, 0] = first + held - 1
    for e in range(held - 1):
        idx[n + e, 1] = first + e
    return jnp.asarray(idx)


LOADS = {
    "all_on_one_held_expert": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), first + 1), jnp.full((T,), 0)], 1),
    "none_held": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), 0), jnp.full((T,), first + held)], 1),
    "balanced": lambda T, first, held, pub: jnp.stack(
        [jnp.arange(T) % pub, (jnp.arange(T) // pub + 1
                               + jnp.arange(T)) % pub], 1),
    "one_tile_over_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) + TILE_M),
}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_nothing_is_dropped_at_skewed_loads(load, act="relu"):
    """Every kept pair has a row of its own on either bound, and the
    result and its four gradients are the plain masked sum's — under the
    ReLU gate (``tests/test_mla_moe_trunk.py`` holds the same loads under
    SiLU: one code path, a static activation)."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (T, c), jnp.float32)
    wgu = jax.random.normal(ks[1], (held, c, 2 * f), jnp.float32) / c ** .5
    wd = jax.random.normal(ks[2], (held, f, c), jnp.float32) / f ** .5
    gate = jax.random.uniform(ks[3], (T, k), jnp.float32, 0.1, 1.0)
    co = jax.random.normal(ks[4], (T, c), jnp.float32)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    kept = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(jnp.sum(p.row_pair >= 0)) == kept            # no drop
    fn = jax.nn.relu if act == "relu" else jax.nn.silu

    def plain(x, gate, wgu, wd):
        y = jnp.zeros_like(x)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == e + first, gate, 0.0), -1)
            h = fn(x @ wgu[e][:, :f]) * (x @ wgu[e][:, f:])
            y = y + mine[:, None] * (h @ wd[e])
        return y
    got, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p, act), x, gate, wgu,
                       wd)
    want, vjp_plain = jax.vjp(plain, x, gate, wgu, wd)
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    for a, b in zip(vjp(co), vjp_plain(co)):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


def test_the_cell_s_load_bound_is_twice_the_expected_pairs():
    # the cell: 16,384 tokens, top-6, 16 of 64 held
    assert moe.rows_bound(16384, 6, 16) == 102400
    assert moe.load_bound(16384, 6, 16, 64) \
        == 2 * 24576 + 16 * TILE_M == 53248
    with pytest.raises(ValueError):
        moe.routed_ffn(jnp.zeros((8, 8)), jnp.zeros((8, 1)),
                       jnp.zeros((1, 8, 16)), jnp.zeros((1, 8, 8)),
                       moe.plan(jnp.zeros((8, 1), jnp.int32), 0, 1, 1),
                       "gelu")


# -- what a configuration must state -----------------------------------------
def _stated(**change):
    base = dict(
        vocab_size=64, hidden_size=128, num_hidden_layers=4,
        num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        sliding_window_size=64, moe_primary_router_apply_softmax=True,
        moe_intermediate_size=128, n_routed_experts=8, experts_held=2,
        num_experts_per_tok=3)
    base.update(change)
    return LlamaPretrainConfig(**base)


def test_layer_types_follow_from_the_two_layouts():
    cfg = _stated()
    assert cfg.layer_types == ("gqa_moe_global", "gqa_moe_window",
                               "gqa_moe_window", "gqa_moe_window")
    # the published lists are the model's depth long; the cut keeps a prefix
    deep = _stated(rope_layout=(0, 1, 1, 1) * 13,
                   sliding_window_layout=(0, 1, 1, 1) * 13,
                   num_hidden_layers=8)
    assert deep.layer_types == cfg.layer_types * 2
    assert deep.rope_layout == deep.sliding_window_layout == (0, 1, 1, 1) * 2
    # a configuration without the keys is the program it was
    plain = LlamaPretrainConfig(hidden_size=256, num_attention_heads=2)
    assert plain.layer_types is None and plain.head_dim == 128


@pytest.mark.parametrize("change,error", [
    (dict(rope_layout=(1, 1, 1, 1)), NotImplementedError),
    (dict(rope_layout=(0, 0, 1, 1)), NotImplementedError),
    (dict(moe_primary_router_apply_softmax=False), NotImplementedError),
    (dict(n_shared_experts=1), NotImplementedError),
    (dict(hc_mult=4), NotImplementedError),
    (dict(sliding_window_size=0), ValueError),
    (dict(num_experts_per_tok=0), ValueError),
    (dict(experts_held=9), ValueError),
    (dict(moe_intermediate_size=0), ValueError),
    (dict(layer_types=("gqa_moe_global", "mla_moe", "gqa_moe_window",
                       "gqa_moe_window")), NotImplementedError),
])
def test_check_names_what_it_refuses(change, error):
    with pytest.raises(error):
        _stated(**change)


def test_layers_by_kind_stay_on_one_device():
    cfg = _stated()
    mesh = build_mesh(dp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="gqa_moe"):
        make_train_step(cfg, mesh, optimizer="adafactor")
