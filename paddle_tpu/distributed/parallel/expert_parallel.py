"""Expert parallelism: MoE with all-to-all token dispatch over a mesh axis.

Reference behavior: incubate/distributed/models/moe/moe_layer.py:263
(MoELayer forward: gate → global_scatter → local experts → global_gather
→ combine) and distributed/utils/moe_utils.py:20,:153 — the
global_scatter/global_gather CUDA all-to-all kernels that move tokens to
the ranks owning their routed experts.

TPU-native realization: the GShard dense-capacity formulation.  Each
device builds fixed-shape per-expert capacity buffers with a one-hot
dispatch einsum (MXU work, no dynamic shapes), then two
``lax.all_to_all`` ops move buffers expert-wise across the ``ep`` axis
— exactly the role of global_scatter/global_gather, but with static
shapes so one XLA program covers every routing outcome:

    [E, C, h]  --all_to_all-->  [E/P, P*C, h]   (tokens to expert owners)
    experts (vmapped over local E/P)
    [E/P, P*C, h]  --all_to_all-->  [E, C, h]   (results back to sources)

Capacity overflow drops tokens (their combine weight is zero), matching
the reference's capacity semantics.  The load-balancing auxiliary loss
is psum-averaged over the group.

WHAT THIS MODULE IS FOR: Paddle's capacity API (``MoELayer`` and the
``global_scatter`` / ``global_gather`` surface), at the sizes that API is
used at.  Its one-hot ``[T, k, E, C]`` dispatch cannot exist at a
training step's 16,384 tokens over 64 experts, and it drops.  THE
TRAINER's expert layer is ``paddle_tpu/ops/moe.py`` (routing without
capacity and without dropping, rows sorted by expert, the grouped
products of ``ops/pallas/grouped_mm.py``), which
``models/hybrid_trunk.py``'s kind ``mla_moe`` runs.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_route", "moe_layer_ep", "moe_layer_ep_local",
           "swiglu_expert", "init_expert_params"]


def moe_route(logits, top_k: int, capacity: int):
    """GShard top-k routing with per-source capacity.

    logits [T, E] -> (dispatch [T, k, E, C] binary, combine [T, k, E, C]
    weighted, l_aux scalar).  Pure function; differentiable through the
    combine weights (dispatch/positions use stop-gradient one-hots, like
    the reference's index-based scatter).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)              # [T, k]
    oh = jax.nn.one_hot(topi, E, dtype=logits.dtype)      # [T, k, E]
    flat = oh.reshape(-1, E)
    pos = jnp.cumsum(flat, axis=0) - flat                 # [T*k, E]
    pos = (pos * flat).sum(-1).reshape(T, top_k).astype(jnp.int32)
    keep = (pos < capacity).astype(logits.dtype)
    weights = topv * keep
    denom = jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    weights = weights / denom
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=logits.dtype)
    disp = oh[..., None] * pos_oh[:, :, None, :] * keep[..., None, None]
    combine = disp * weights[:, :, None, None]
    me = probs.mean(0)
    ce = oh.sum((0, 1)) / jnp.maximum(oh.sum(), 1.0)
    l_aux = (me * ce).sum() * E
    return disp, combine, l_aux, me, ce


def swiglu_expert(p, x):
    """Default expert: LLaMA-style gated MLP.  p: {'w_gate','w_up',
    'w_down'}; x [C, h]."""
    gate = jax.nn.silu(x @ p["w_gate"])
    return (gate * (x @ p["w_up"])) @ p["w_down"]


def init_expert_params(key, num_expert: int, d_model: int, d_hidden: int,
                       dtype=jnp.float32):
    """Stacked expert weights with a leading [E] axis (shard over 'ep')."""
    k1, k2, k3 = jax.random.split(key, 3)
    std = 1.0 / math.sqrt(d_model)
    return {
        "w_gate": jax.random.normal(
            k1, (num_expert, d_model, d_hidden), dtype) * std,
        "w_up": jax.random.normal(
            k2, (num_expert, d_model, d_hidden), dtype) * std,
        "w_down": jax.random.normal(
            k3, (num_expert, d_hidden, d_model), dtype) / math.sqrt(d_hidden),
    }


def moe_layer_ep_local(xf, gate_w, expert_params, *, axis: str,
                       num_expert: int, top_k: int = 2,
                       capacity_factor: float = 2.0,
                       expert_fn: Callable = swiglu_expert):
    """Runs INSIDE shard_map.  xf: [T_local, h] (tokens sharded over
    ``axis``); expert_params: leading dim E/P (experts sharded over
    ``axis``); gate_w [h, E] replicated.

    Returns (out [T_local, h], l_aux) — l_aux already psum-averaged.
    """
    p = jax.lax.axis_size(axis)
    E = num_expert
    if E % p != 0:
        raise ValueError(f"num_expert {E} must divide by ep={p}")
    T, h = xf.shape
    cap = int(math.ceil(capacity_factor * T * top_k / E))

    logits = xf @ gate_w                                   # [T, E]
    disp, combine, _, me, ce = moe_route(logits, top_k, cap)
    # group-global aux loss: average the per-expert stats FIRST, then
    # take the product — mean(me_s·ce_s) over shards is not the GShard
    # loss; mean(me)·mean(ce) is (equal-size shards)
    l_aux = (jax.lax.pmean(me, axis) *
             jax.lax.pmean(ce, axis)).sum() * E

    expert_in = jnp.einsum("tkec,th->ech", disp, xf)       # [E, C, h]
    # tokens -> expert owners: [E, C, h] -> [E/P, P*C, h]
    expert_in = jax.lax.all_to_all(expert_in, axis, split_axis=0,
                                   concat_axis=1, tiled=True)
    expert_out = jax.vmap(expert_fn)(expert_params, expert_in)
    # results -> token sources: [E/P, P*C, h] -> [E, C, h]
    expert_out = jax.lax.all_to_all(expert_out, axis, split_axis=1,
                                    concat_axis=0, tiled=True)
    out = jnp.einsum("tkec,ech->th", combine, expert_out)
    return out, l_aux


def moe_layer_ep(x, gate_w, expert_params, mesh: Mesh, *,
                 axis: str = "mp", num_expert: int, top_k: int = 2,
                 capacity_factor: float = 2.0,
                 expert_fn: Callable = swiglu_expert):
    """Global-array expert-parallel MoE layer.

    x [..., T, h] with tokens shardable over ``axis`` (the reference's
    moe_group is its data-parallel group — any mesh axis works);
    expert_params carry a leading [E] dim sharded over ``axis``.
    Returns (out like x, l_aux).  Differentiable.
    """
    orig_shape = x.shape
    h = orig_shape[-1]
    xf = x.reshape(-1, h)
    treedef = jax.tree_util.tree_structure(expert_params)
    g = _ep_shard_map(mesh, axis, num_expert, top_k, capacity_factor,
                      expert_fn, treedef)
    out, l_aux = g(xf, gate_w, expert_params)
    return out.reshape(orig_shape), l_aux


@functools.lru_cache(maxsize=64)
def _ep_shard_map(mesh, axis, num_expert, top_k, capacity_factor,
                  expert_fn, treedef):
    """Cached jitted shard_map per (mesh, routing config, expert tree)
    so eager per-step calls reuse the compiled program."""
    f = functools.partial(moe_layer_ep_local, axis=axis,
                          num_expert=num_expert, top_k=top_k,
                          capacity_factor=capacity_factor,
                          expert_fn=expert_fn)
    ep_spec = jax.tree_util.tree_unflatten(
        treedef, [P(axis)] * treedef.num_leaves)
    g = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), ep_spec),
        out_specs=(P(axis, None), P()),
        axis_names={axis}, check_vma=False)
    return jax.jit(g)
