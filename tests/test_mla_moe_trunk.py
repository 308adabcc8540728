"""Latent attention, routed experts without dropping and the residual
streams on the normal path: ``make_train_step`` over
``models/hybrid_trunk.py``'s kinds ``mla_dense`` / ``mla_moe``
(``ops/moe.py``, ``flash_attention_split``, the mHC mixers, YaRN) held
to the plain reference ``benchmark/models/xing_mhc_moe_reference.py`` at
toy size — float32 on the CPU, the published PATTERN (a dense lead, then
expert layers), two heads of 128 | 64 | 128, four streams, two of eight
experts held from the third on, top-2, seeded weights.  Then one thing
is changed at a time, in the program or in the reference, and the
comparison must fail.  The share, the skewed loads, the mixer's maps and
adafactor on a rank-4 stack have tests of their own.
"""

import dataclasses
import functools
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from benchmark import harness, reference, train_cell
from paddle_tpu.models import hybrid_trunk, llama_pretrain
from paddle_tpu.models.llama_pretrain import (
    adafactor_update, build_mesh, init_adafactor_state, make_forward,
    make_train_step)
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas.grouped_mm import TILE_M

TOY = os.path.join(harness.HERE, "tests", "toy")
SEED, SEQ, ROWS = 2**31 + 77, 256, 2
SOUND, BROKEN = 1e-5, 1e-3
# The two-step CHANGE is held looser than loss and gradient: a pick of
# the router is a comparison, and after one step the program's and the
# reference's parameters differ in the seventh digit — enough to turn a
# near-tie of one token's scores the other way.  A mixer's alpha is
# three numbers whose gradient is a sum of terms that nearly cancel, so
# one token's flip shows in its second adafactor step (read: 3.6e-2 on
# this seed; the gradient of the FIRST step agrees to 3e-7).
SOUND_CHANGE = 6e-2


@pytest.fixture(scope="module")
def toy():
    conf = harness.load_json(os.path.join(TOY, "config_xing.json"))
    job = dict(harness.load_json(os.path.join(TOY, "train_job.json")),
               seq=SEQ, batch=ROWS)
    cell = harness.Cell.detached("toy-xing.train_job", 1, conf, job)
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
    assert cfg.layer_types == ("mla_dense", "mla_moe", "mla_moe")
    assert hybrid_trunk.layer_runs(cfg.layer_types) == [
        ("mla_dense", 0, 1), ("mla_moe", 0, 2)]
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert cfg.hc_mult == 4 and cfg.hc_sinkhorn_iters == 20
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok) == (8, 2, 2, 2)
    assert cfg.rope_scaling["type"] == "yarn"
    shapes = hybrid_trunk.kind_shapes(cfg, "mla_moe")
    assert shapes["we_gate_up"] == (2, 128, 256)        # rank 4 stacked
    assert shapes["w_router"] == (128, 8)               # published width
    assert shapes["hc1_phi"] == (4 * 128, 4 * 4 + 2 * 4)


@pytest.mark.parametrize("what", ["loss_rel_gap.step0", "loss_rel_gap.step1",
                                  "grad_norm_worst_leaf_gap"])
def test_two_steps_match_the_reference(sound, ref, what):
    assert set(sound["grad"]) == set(ref["grad"])       # leaf for leaf
    assert len(ref["grad"]) == 20 + 23 + 3
    assert train_cell.gap_numbers(sound, ref)[what] < SOUND


def test_the_two_step_change_matches_the_reference(sound, ref):
    numbers = train_cell.gap_numbers(sound, ref)
    assert numbers["param_change_worst_leaf_gap"] < SOUND_CHANGE, numbers
    # and every leaf but the mixers' is far inside it
    for path, want in ref["change"].items():
        if not path[-1].startswith("hc"):
            assert abs(sound["change"][path] - want) < 1e-4 * want, path


def test_logits_match_the_reference(toy):
    cfg, fam = toy.cfg, toy.cell.family
    mesh = build_mesh(devices=jax.devices()[:1])
    with mesh:
        params = fam.make_params(cfg, SEED, mesh)
    ids = toy.batches[0][0, :SEQ]

    def program(params, ids):
        from paddle_tpu.models import llama_pretrain
        x = jnp.take(params["embed"], ids[None], axis=0)
        x = hybrid_trunk.trunk(params["blocks"], x, cfg, None)
        x = llama_pretrain._rms_norm(x, params["final_norm"],
                                     cfg.rms_norm_eps)
        return (x @ params["lm_head"])[0]
    rows = np.asarray([0, 1, 127, 128, 200, SEQ - 1])
    got = np.asarray(jax.jit(program)(params, jnp.asarray(ids)))[rows]
    want = reference.forward_rows(toy.cell.block_reference, params,
                                  toy.conf, ids, rows)
    assert np.max(np.abs(got - want)) < SOUND * np.max(np.abs(want))


# one thing changed in the PROGRAM's configuration
PROGRAM = {
    "no_yarn": lambda c: dict(rope_scaling=None),
    "gates_not_scaled": lambda c: dict(routed_scaling_factor=1.0),
    "one_sinkhorn_round": lambda c: dict(hc_sinkhorn_iters=1),
    "other_experts_held": lambda c: dict(expert_first=3),
    "top_one": lambda c: dict(num_experts_per_tok=1),
    "clamp_at_a_half": lambda c: dict(mhc_h_res_clamp_max=0.5),
    "eps_of_the_mixers_norm": lambda c: dict(rms_norm_eps=1e-2),
}


@pytest.mark.parametrize("what", sorted(PROGRAM))
def test_a_program_altered_in_one_place_fails(toy, ref, what):
    cfg = dataclasses.replace(toy.cfg, **PROGRAM[what](toy.cfg))
    assert first_step_gap(follow(toy, cfg), ref) > BROKEN


# one line changed in the REFERENCE
REFERENCE = {
    "shared_expert_ignored": (
        'return routed + _swiglu(x, w["ws_gate"], w["ws_up"], '
        'w["ws_down"], mm)', "return routed"),
    "gates_over_the_held_picks_only": (
        "g = d[\"gate_scale\"] * top / (jnp.sum(top, -1, keepdims=True) "
        "+ 1e-20)",
        "g = d[\"gate_scale\"] * top / (jnp.sum(jnp.where((idx >= "
        "d[\"first\"]) & (idx < d[\"first\"] + d[\"held\"]), top, 0.0), "
        "-1, keepdims=True) + 1e-20)"),
    "rotated_key_ignored": ("kh = jnp.concatenate([kh, k_r], -1)",
                            "kh = jnp.concatenate([kh, 0.0 * k_r], -1)"),
    "h_post_not_doubled": ("h_post = 2.0 * jax.nn.sigmoid(",
                           "h_post = 1.0 * jax.nn.sigmoid("),
    "columns_not_normalised": (
        'r = r / (jnp.sum(r, 0, keepdims=True) + d["hc_eps"])', "r = r"),
    "streams_not_summed": (
        'x = x.reshape(*x.shape[:-1], -1, d["hidden"]).sum(-2)',
        'x = x.reshape(*x.shape[:-1], -1, d["hidden"])[..., 0, :]'),
}


def altered_reference(old: str, new: str):
    path = os.path.join(harness.HERE, "models",
                        "xing_mhc_moe_reference.py")
    with open(path) as f:
        src = f.read()
    # the needle may span lines in the file
    import re
    pattern = r"\s+".join(re.escape(w) for w in old.split())
    src, n = re.subn(pattern, lambda m: new, src)
    assert n == 1, old
    mod = types.ModuleType("benchmark.models.xing_mhc_moe_altered")
    mod.__package__ = "benchmark.models"
    exec(compile(src, path, "exec"), mod.__dict__)
    return mod


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_a_reference_altered_in_one_line_fails(toy, sound, what):
    cell = types.SimpleNamespace(
        block_reference=altered_reference(*REFERENCE[what]),
        conf=toy.conf)
    other = train_cell.run_reference(cell, toy.job, toy.leaf0, toy.batches)
    assert first_step_gap(sound, other) > BROKEN


# -- the share ---------------------------------------------------------------
def _layer_weights(key, c, f, experts):
    ks = jax.random.split(key, 6)
    n = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) \
        / fan ** 0.5
    return {"w_router": n(ks[0], (c, experts), c),
            "we_gate_up": n(ks[1], (experts, c, 2 * f), c),
            "we_down": n(ks[2], (experts, f, c), f),
            "ws_gate": n(ks[3], (c, f), c), "ws_up": n(ks[4], (c, f), c),
            "ws_down": n(ks[5], (f, c), f)}


def _share(w, first, held):
    return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                we_down=w["we_down"][first:first + held])


def _program_layer(toy, w, x, first, held):
    cfg = dataclasses.replace(toy.cfg, expert_first=first,
                              experts_held=held)
    return hybrid_trunk._expert_layer(w, x, cfg)


def test_the_shares_add_up_to_the_whole_layer(toy):
    """The four shares' routed parts, with the shared expert counted
    once, are what the UNCUT reference gives for the whole layer."""
    from benchmark.models import xing_mhc_moe_reference as blk
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    w = _layer_weights(jax.random.PRNGKey(3), c, f, 8)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 300, c), jnp.float32)
    whole = dict(blk.dims_of(dict(toy.conf, n_routed_experts=8,
                                  expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    want = blk._experts(x, w, whole, mm)
    shared = blk._swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], mm)
    parts = [_program_layer(toy, _share(w, first, 2), x, first, 2) - shared
             for first in (0, 2, 4, 6)]
    got = sum(parts) + shared
    assert float(jnp.max(jnp.abs(got - want))) \
        < SOUND * float(jnp.max(jnp.abs(want)))
    # and a share alone is the reference's share
    one = blk._experts(x, _share(w, 2, 2), dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] + shared - one))) \
        < SOUND * float(jnp.max(jnp.abs(one)))


def _routed_case(T, c, f, held, k, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, c), jnp.float32)
    wgu = jax.random.normal(ks[1], (held, c, 2 * f), jnp.float32) / c ** .5
    wd = jax.random.normal(ks[2], (held, f, c), jnp.float32) / f ** .5
    gate = jax.random.uniform(ks[3], (T, k), jnp.float32, 0.1, 1.0)
    co = jax.random.normal(ks[4], (T, c), jnp.float32)
    return x, gate, wgu, wd, co


def _value_and_grads(x, gate, wgu, wd, co, p):
    y, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p), x, gate, wgu, wd)
    return (y,) + vjp(co)


def _filling(T, first, held, published, rows):
    """Picks [T, 2] whose kept pairs fill ``rows`` rows of the buffer to
    the last one: every held expert but the last takes one pair (one
    tile), the last ``rows - (held - 1) * TILE_M`` tokens' first picks;
    every other pick goes to an expert not held."""
    away = first + held if first + held < published else 0
    idx = np.full((T, 2), away, np.int32)
    n = rows - (held - 1) * TILE_M
    idx[:n, 0] = first + held - 1
    for e in range(held - 1):
        idx[n + e, 1] = first + e
    return jnp.asarray(idx)


# T 2048, top-2, 2 of 16 held: 512 pairs expected, the bound that
# follows the load 2 * 512 + 2 * 256 = 1536 rows, the bound of any load
# 2 * 2048 + 512 = 4608
LOADS = {
    "all_on_one_held_expert": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), first + 1), jnp.full((T,), 0)], 1),
    "none_held": lambda T, first, held, pub: jnp.stack(
        [jnp.full((T,), 0), jnp.full((T,), first + held)], 1),
    "every_pick_held": lambda T, first, held, pub: jnp.stack(
        [first + jnp.arange(T) % held,
         first + (jnp.arange(T) + 1) % held], 1),
    "balanced": lambda T, first, held, pub: jnp.stack(
        [jnp.arange(T) % pub, (jnp.arange(T) // pub + 1
                               + jnp.arange(T)) % pub], 1),
    "the_load_bound_to_its_last_row": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub)),
    "one_tile_under_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) - TILE_M),
    "one_tile_over_the_load_bound": lambda T, first, held, pub: _filling(
        T, first, held, pub, moe.load_bound(T, 2, held, pub) + TILE_M),
}
ON_THE_LOAD_BOUND = {"none_held": True, "balanced": True,
                     "the_load_bound_to_its_last_row": True,
                     "one_tile_under_the_load_bound": True,
                     "one_tile_over_the_load_bound": False,
                     "all_on_one_held_expert": False,
                     "every_pick_held": False}


def _branches(fn, *args) -> list:
    """How many ``cond`` choose a bound in ``fn``'s jaxpr (kernel bodies
    not walked), and whether each bound's scope is on an op path."""
    conds, scopes = 0, set()

    def walk(jaxpr, outer=""):
        nonlocal conds
        for eqn in jaxpr.eqns:
            path = f"{outer}/{eqn.source_info.name_stack}"
            scopes.update(w for w in ("moe_bound_load", "moe_bound_all")
                          if w in path)
            if eqn.primitive.name == "pallas_call":
                continue
            conds += eqn.primitive.name == "cond"
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, path)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return [conds, "moe_bound_load" in scopes, "moe_bound_all" in scopes]


@pytest.mark.parametrize("load", sorted(LOADS))
def test_nothing_is_dropped_at_any_load(load):
    """Every (token, pick) pair whose expert is held has a row of its
    own, whatever the load — under the bound that follows the load, at
    its last row and past it; the result is the plain masked sum, and so
    are the four gradients."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    kept = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(jnp.sum(p.row_pair >= 0)) == kept            # no drop
    assert p.row_pair.shape[0] == moe.rows_bound(T, k, held) \
        >= T * k + held * TILE_M
    assert p.load_rows == moe.load_bound(T, k, held, pub) == 1536
    pair_of = np.asarray(p.row_pair)
    assert len(set(pair_of[pair_of >= 0].tolist())) == kept  # one row a pair
    assert int(p.n_tiles[0]) >= held                        # a tile an expert
    # which bound this load runs on: the plan's own tile count says
    assert (int(p.n_tiles[0]) * TILE_M <= p.load_rows) \
        == ON_THE_LOAD_BOUND[load]
    if load.startswith("the_load_bound"):
        assert int(p.n_tiles[0]) * TILE_M == p.load_rows
        assert int(p.row_pair[p.load_rows - 1]) >= 0        # its last row
    # the kept pairs in token order, each with its row
    held_pairs = np.flatnonzero(np.asarray(
        (idx >= first) & (idx < first + held)).reshape(-1))
    assert (np.asarray(p.slot_token)[:kept] == held_pairs // k).all() \
        and (np.asarray(p.slot_token)[kept:] == -1).all()
    assert (pair_of[np.asarray(p.slot_row)[:kept]] == held_pairs).all()
    assert int(p.first_slot[-1]) == kept

    def plain(x, gate, wgu, wd):
        y = jnp.zeros_like(x)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == e + first, gate, 0.0), -1)
            h = jax.nn.silu(x @ wgu[e][:, :f]) * (x @ wgu[e][:, f:])
            y = y + mine[:, None] * (h @ wd[e])
        return y
    got, vjp = jax.vjp(lambda *a: moe.routed_ffn(*a, p), x, gate, wgu, wd)
    want, vjp_plain = jax.vjp(plain, x, gate, wgu, wd)
    scale = max(float(jnp.max(jnp.abs(want))), 1.0)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * scale
    for a, b in zip(vjp(co), vjp_plain(co)):
        assert float(jnp.max(jnp.abs(a - b))) \
            < 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0)


@pytest.mark.parametrize("load", ["balanced", "none_held",
                                  "one_tile_under_the_load_bound"])
def test_the_two_bounds_are_one_program(load):
    """A load that fits both bounds: the branch on the plan's first rows
    and the branch on all of them give the same value and the same four
    gradients BIT FOR BIT, and the program holds both behind one
    ``cond`` a pass."""
    T, c, f, held, k, pub, first = 2048, 128, 128, 2, 2, 16, 4
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k, seed=11)
    # the cell's dtypes: bf16 rows, fp32 gates and stacks (in fp32 the
    # CPU's elementwise loops round a last bit by the array's length)
    x, co = x.astype(jnp.bfloat16), co.astype(jnp.bfloat16)
    idx = LOADS[load](T, first, held, pub).astype(jnp.int32)
    p = moe.plan(idx, first, held, pub)
    assert int(p.n_tiles[0]) * TILE_M <= p.load_rows < p.row_pair.shape[0]
    everything = dataclasses.replace(p, load_rows=p.row_pair.shape[0])

    both = functools.partial(_value_and_grads, x, gate, wgu, wd, co)
    for a, b in zip(both(p), both(everything)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    assert _branches(both, p) == [2, True, True]       # forward, backward
    assert _branches(both, everything) == [0, False, True]


def test_every_expert_held_builds_one_bound():
    """``held == published``: twice the expected pairs is more than
    there can be, the two bounds are the same rows and no branch is
    built."""
    T, c, f, held, k = 512, 128, 128, 3, 2
    assert moe.load_bound(T, k, held, held) == moe.rows_bound(T, k, held)
    x, gate, wgu, wd, co = _routed_case(T, c, f, held, k)
    idx = jnp.stack([jnp.arange(T) % held, (jnp.arange(T) + 1) % held],
                    1).astype(jnp.int32)
    p = moe.plan(idx, 0, held, held)
    assert p.load_rows == p.row_pair.shape[0]

    both = functools.partial(_value_and_grads, x, gate, wgu, wd, co)
    assert _branches(both, p) == [0, False, True]


def test_load_bound_is_twice_the_expected_pairs_and_never_past_any_load():
    # the expert cell: 16,384 tokens, top-4, 8 of 64
    assert moe.rows_bound(16384, 4, 8) == 67584
    assert moe.load_bound(16384, 4, 8, 64) == 16384 + 8 * TILE_M == 18432
    assert moe.load_bound(16384, 4, 32, 64) == moe.rows_bound(16384, 4, 32)
    assert moe.load_bound(100, 4, 2, 64) == TILE_M + 2 * TILE_M
    for held in (1, 2, 8, 64):
        assert moe.load_bound(4096, 4, held, 64) % TILE_M == 0
        assert moe.load_bound(4096, 4, held, 64) \
            <= moe.rows_bound(4096, 4, held)


def _parent_rows_of_pairs(buf, held, pos):
    """PR 33's token side: ``k`` row reads a token (row 0 for a pair
    whose expert is not held), masked, summed."""
    picked = jnp.where(held[..., None], buf[pos], 0)
    return jnp.sum(picked.astype(jnp.float32), axis=1)


@pytest.mark.parametrize("cut", [False, True])
def test_a_token_sums_the_rows_it_has(cut):
    """The token side against the parent's ``k`` gathers, on a plan
    whose tokens hold 0, 1, 2, 3 and 4 pairs, in fp32 to one ulp — on
    the whole plan and on its first rows."""
    T, c, held, k, pub, first = 640, 128, 4, 4, 16, 2
    rng = np.random.default_rng(5)
    idx = np.empty((T, k), np.int32)
    away = [e for e in range(pub) if not first <= e < first + held]
    for t in range(T):
        n = t % 5                                   # pairs this token holds
        idx[t] = rng.permutation(np.concatenate(
            [rng.choice(np.arange(first, first + held), n, replace=False),
             rng.choice(away, k - n, replace=False)]))
    p = moe.plan(jnp.asarray(idx), first, held, pub)
    is_held = (idx >= first) & (idx < first + held)
    pairs = is_held.sum(1)
    assert sorted(set(pairs.tolist())) == [0, 1, 2, 3, 4]
    # the row of each pair, as PR 33's plan held it
    pair_of = np.asarray(p.row_pair)
    pos = np.zeros(T * k, np.int32)
    pos[pair_of[pair_of >= 0]] = np.flatnonzero(pair_of >= 0)
    pos = pos.reshape(T, k)
    if cut:
        assert int(p.n_tiles[0]) * TILE_M <= p.load_rows < p.row_pair.shape[0]
        p = moe._first_rows(p, p.load_rows)
    buf = jax.random.normal(jax.random.PRNGKey(2),
                            (p.row_pair.shape[0], c), jnp.float32) * 3
    got = np.asarray(moe._rows_of_pairs(buf, p))
    want = np.asarray(_parent_rows_of_pairs(buf, is_held, pos))
    assert got.dtype == np.float32
    # one ulp of what is summed: three and four terms add up in the
    # product's order, not the parent's; up to two are the same sum
    ulp = np.spacing(np.asarray(
        _parent_rows_of_pairs(jnp.abs(buf), is_held, pos)))
    assert (np.abs(got - want) <= ulp).all()
    assert (got[pairs <= 2] == want[pairs <= 2]).all()
    assert (pairs == 0).any() and not got[pairs == 0].any()


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
    (the backward scan's too) with ``FLASH_KEPT_BYTES`` at 0, and the
    loss and every gradient are the same bits."""
    import re
    mesh = build_mesh(devices=jax.devices()[:1])
    runs = len(hybrid_trunk.layer_runs(toy.cfg.layer_types))
    ids = jnp.asarray(toy.batches[0])

    def loss_and_grads(flash_fwd_runs):
        with mesh:
            params = toy.cell.family.make_params(toy.cfg, SEED, mesh)
            fn = jax.value_and_grad(make_forward(toy.cfg, mesh))
            assert len(re.findall(
                r"name=flash_fwd\b",
                str(jax.make_jaxpr(fn)(params, ids)))) == flash_fwd_runs
            return jax.jit(fn)(params, ids)

    kept, kept_g = loss_and_grads(runs)
    monkeypatch.setattr(llama_pretrain, "FLASH_KEPT_BYTES", 0)
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
