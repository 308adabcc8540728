"""The whole toy model of the family ``solar_kda_moe`` through the REAL
``make_train_step`` (``models/hybrid_trunk.py``'s kinds ``kda_moe`` /
``gqa_gated_moe``) against
``benchmark/models/solar_kda_moe_reference.py`` on seeded weights — loss,
every leaf's first gradient, the two-step change — and the shares adding
up to the uncut expert layer."""

import dataclasses

import jax
import jax.numpy as jnp

from _solar_toy import F32, ref, sound, toy  # noqa: F401
from _toy_cell import SOUND, first_step_gap, worst_gap
from benchmark import reference
from benchmark.models import solar_kda_moe_reference as blk
from paddle_tpu.models import hybrid_trunk


def test_the_toy_model_follows_the_reference(sound, ref):
    """Loss, every leaf's first gradient, the two-step change: fp32
    against fp32 to ``_toy_cell.SOUND`` (measured 7e-8, 2e-7, 5e-7).  The
    family's embedding rows at std 2 matter here too: at 1/sqrt(hidden) a
    token's third and fourth scores of eight tie to within the 1e-5 by
    which the chunked recurrence and the position-by-position one differ,
    that token goes to another expert in one of the two, and the first
    gradient is 8e-3 apart."""
    assert first_step_gap(sound, ref) < SOUND
    assert worst_gap(sound, ref) < SOUND


def test_the_shares_and_the_shared_expert_once_are_the_whole_layer(toy):
    """The four shares' routed parts (experts 0-1, 2-3, 4-5, 6-7 of the
    toy's 8; 0-7 .. 312-319 of the cell's 320) plus the shared expert
    counted ONCE are what the UNCUT reference gives for the whole expert
    layer; a share alone, with the shared expert every chip computes, is
    the reference's share."""
    c, f = toy.cfg.hidden_size, toy.cfg.moe_intermediate_size
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    n = lambda k, shape, fan: jax.random.normal(k, shape, F32) / fan ** 0.5
    w = {"w_router": n(ks[0], (c, 8), c),
         "we_gate_up": n(ks[1], (8, c, 2 * f), c),
         "we_down": n(ks[2], (8, f, c), f), "ws_gate": n(ks[3], (c, f), c),
         "ws_up": n(ks[4], (c, f), c), "ws_down": n(ks[5], (f, c), f)}
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 300, c), F32)
    whole = dict(blk.dims_of(dict(toy.conf, n_routed_experts=8,
                                  expert_first=0)))
    mm = lambda a, b: reference.matmul(a, b, "f32")
    idx, g = blk._route(u, w, whole, mm)
    assert float(jnp.max(jnp.abs(jnp.sum(g, -1) - 1))) < 1e-5
    want = blk._experts(u, idx, g, w, whole, mm)
    shared = mm(jax.nn.silu(mm(u, w["ws_gate"])) * mm(u, w["ws_up"]),
                w["ws_down"])

    def share(first, held=2):
        return dict(w, we_gate_up=w["we_gate_up"][first:first + held],
                    we_down=w["we_down"][first:first + held])

    def part(first):
        cfg = dataclasses.replace(toy.cfg, expert_first=first,
                                  experts_held=2)
        return hybrid_trunk._expert_layer(share(first), u, cfg)
    parts = [part(first) for first in (0, 2, 4, 6)]
    scale = float(jnp.max(jnp.abs(want)))
    # every share computed the shared expert: three of the four are taken
    # off again
    assert float(jnp.max(jnp.abs(sum(parts) - 3 * shared - want))) \
        < 1e-5 * scale
    one = blk._experts(u, idx, g, share(2), dict(whole, first=2, held=2), mm)
    assert float(jnp.max(jnp.abs(parts[1] - one))) < 1e-5 * scale
    assert all(float(jnp.max(jnp.abs(p - shared))) > 0.01 * scale
               for p in parts)
