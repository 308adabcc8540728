"""A trunk of layers by KIND, for the one train step of
``llama_pretrain.py``: a configuration that states ``layer_types`` gets a
parameter tree ``blocks: {kind: {leaf: [layers of the kind, ...]}}`` and
a trunk that walks the published order, a run of equal kinds being one
``lax.scan`` over a slice of that kind's stack, each block under
``jax.checkpoint`` like the dense trunk's.

Kinds.  ``attention`` is ``llama_pretrain``'s own block (the same
functions: what a configuration changes in it — no rotation, its own
score scale, a residual multiplier — it changes there).  ``mamba`` is a
Mamba-2 mixer (Dao & Gu 2024) before the same MLP:

    [z | xBC | dt] = rms_norm(h; ln1) . w_in          (d_inner | conv | H)
    xBC = silu(causal depthwise conv(xBC) + conv_b)   (the last d_conv;
                                                       ops/pallas/causal_conv.py)
    [x | B | C] = xBC                                 (one B/C group)
    y = ssd_scan(x, softplus(dt + dt_bias), -exp(A_log), B, C) + D * x
    h += residual_multiplier * (rms_norm(y * silu(z); gate_norm) . w_out)

The scan is ``ops/ssd_scan.py``.  Its kernels and the convolution's read
their operands where the step before left them — xBC inside the
projection's output, x, B and C inside the convolution's — at channel
offsets in their index maps, where those are whole lane tiles: no piece
is sliced out for a kernel.  What a layer is follows from the
configuration alone.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

KINDS = ("attention", "mamba")


def check(cfg) -> None:
    """What a configuration with ``layer_types`` must state."""
    unknown = set(cfg.layer_types) - set(KINDS)
    if unknown:
        raise ValueError(f"layer_types names {sorted(unknown)}; the trunk "
                         f"has the kinds {KINDS}")
    if len(cfg.layer_types) != cfg.num_hidden_layers:
        raise ValueError(
            f"layer_types states {len(cfg.layer_types)} layers, "
            f"num_hidden_layers {cfg.num_hidden_layers}")
    if "mamba" in cfg.layer_types:
        if min(cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state) < 1:
            raise ValueError("a 'mamba' layer needs mamba_n_heads, "
                             "mamba_d_head and mamba_d_state")
        if cfg.mamba_n_groups != 1:
            raise NotImplementedError(
                f"mamba_n_groups={cfg.mamba_n_groups}: ops/ssd_scan.py "
                "shares ONE B/C group among the heads")


def check_layout(cfg, mesh, pp: int) -> None:
    """Layers by kind run on one device or, without a state-space kind,
    not at all split: what is missing is named, nothing runs wrong."""
    split = {} if mesh is None else {
        a: n for a, n in mesh.shape.items() if n > 1}
    if pp > 1 or split:
        raise NotImplementedError(
            f"layers by kind on a mesh split over {split or {'pp': pp}}: "
            "the trunk by kind has no pipeline stages (a stage would hold "
            "runs of its own), the Mamba-2 mixer no head-parallel "
            "projections (mp), no state hand-over between sequence shards "
            "(sep) and its kernel no shard_map over the batch (dp, "
            "sharding); one device runs it")


# ---------------------------------------------------------------------------
# the tree by kind
# ---------------------------------------------------------------------------
def mamba_dims(cfg) -> Tuple[int, int, int]:
    """(d_inner, conv channels, in_proj width)."""
    d_inner = cfg.mamba_n_heads * cfg.mamba_d_head
    conv = d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state
    return d_inner, conv, d_inner + conv + cfg.mamba_n_heads


def kind_shapes(cfg, kind: str) -> Dict[str, Tuple[int, ...]]:
    """One layer's leaves.  Both kinds end in the same MLP."""
    from .llama_pretrain import _block_shapes
    dense = _block_shapes(cfg)
    if kind == "attention":
        return dense
    h = cfg.hidden_size
    d_inner, conv, proj = mamba_dims(cfg)
    heads = (cfg.mamba_n_heads,)
    out = {"ln1": (h,), "w_in": (h, proj),
           "conv_w": (conv, cfg.mamba_d_conv), "conv_b": (conv,),
           "A_log": heads, "D": heads, "dt_bias": heads,
           "gate_norm": (d_inner,), "w_out": (d_inner, h)}
    out.update({k: dense[k] for k in ("ln2", "w_gate", "w_up", "w_down")})
    return out


def layers_of(cfg, kind: str) -> int:
    return cfg.layer_types.count(kind)


def block_specs(cfg) -> Dict[str, Dict[str, P]]:
    """Every leaf of every kind the configuration has.  The attention
    kind keeps the dense block's Megatron layout; a state-space leaf is
    whole on every device (:func:`check_layout` refuses to split it)."""
    from .llama_pretrain import _block_specs
    dense = _block_specs(cfg, (None,))
    whole = lambda shape: P(*([None] * (len(shape) + 1)))
    return {kind: dense if kind == "attention" else {
        nm: dense[nm] if nm in ("w_gate", "w_up", "w_down") else whole(shape)
        for nm, shape in kind_shapes(cfg, kind).items()}
        for kind in dict.fromkeys(cfg.layer_types)}


def init_leaf(cfg, key, kind: str, name: str, layers: int, dtype=None):
    """``layers`` layers of one leaf, stacked.  Matrices normal at
    1/sqrt(hidden), norms and D ones, the convolution uniform in +-1 /
    sqrt(d_conv), ``A_log = log U[1, 16]`` and ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1] (the Mamba-2
    reference's: decays neither 0 nor 1)."""
    shape = (layers,) + kind_shapes(cfg, kind)[name]
    f32 = jnp.float32
    if name in ("ln1", "ln2", "gate_norm", "D"):
        out = jnp.ones(shape, f32)
    elif name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        out = jax.random.uniform(key, shape, f32, -bound, bound)
    elif name == "A_log":
        out = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        out = dt + jnp.log(-jnp.expm1(-dt))
    else:
        out = jax.random.normal(key, shape, f32) / math.sqrt(cfg.hidden_size)
    return out.astype(dtype or cfg.param_dtype)


def init_blocks(cfg, key) -> Dict[str, Dict[str, Any]]:
    out = {}
    for i, kind in enumerate(dict.fromkeys(cfg.layer_types)):
        names = list(kind_shapes(cfg, kind))
        keys = jax.random.split(jax.random.fold_in(key, i), len(names))
        out[kind] = {nm: init_leaf(cfg, k, kind, nm, layers_of(cfg, kind))
                     for nm, k in zip(names, keys)}
    return out


# ---------------------------------------------------------------------------
# the Mamba-2 block
# ---------------------------------------------------------------------------
def _mamba_mixer(bp, v, cfg):
    from ..ops.pallas import causal_conv
    from ..ops.ssd_scan import ssd_scan_xbc
    from .llama_pretrain import _rms_norm
    b, s, _ = v.shape
    dt_ = cfg.dtype
    nh, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    d_inner, conv, _ = mamba_dims(cfg)
    with jax.named_scope("ssm_in_proj"):
        zxbcdt = v @ bp["w_in"].astype(dt_)
        # the convolution's kernels read xBC inside zxbcdt where its
        # offset is whole lane tiles; else out of a slice
        at = d_inner if causal_conv.takes(zxbcdt, bp["conv_w"], d_inner) \
            else 0
        if at:
            # a kernel takes an array row-major: the product is to leave
            # it so (forward it would not, and XLA would copy all of it)
            zxbcdt = with_layout_constraint(
                zxbcdt, Layout(major_to_minor=(0, 1, 2)))
        z = zxbcdt[..., :d_inner]
        dt = zxbcdt[..., d_inner + conv:]
    with jax.named_scope("ssm_conv"):
        xbc = zxbcdt if at else zxbcdt[..., d_inner:d_inner + conv]
        if causal_conv.takes(xbc, bp["conv_w"], at):
            xbc = causal_conv.causal_conv_silu(xbc, bp["conv_w"],
                                               bp["conv_b"], at)
        else:
            xbc = causal_conv.causal_conv_silu_xla(xbc, bp["conv_w"],
                                                   bp["conv_b"])
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) +
                             bp["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(bp["A_log"].astype(jnp.float32))
        y, x = ssd_scan_xbc(xbc, dt, A, n, cfg.mamba_chunk_size)
        x = x.reshape(b, s, nh, p)
        y = (y.reshape(x.shape).astype(jnp.float32)
             + bp["D"].astype(jnp.float32)[:, None]
             * x.astype(jnp.float32)).astype(dt_).reshape(b, s, d_inner)
    with jax.named_scope("ssm_gate_norm"):
        y = _rms_norm(y * jax.nn.silu(z), bp["gate_norm"], cfg.rms_norm_eps)
    with jax.named_scope("ssm_out_proj"):
        return y @ bp["w_out"].astype(dt_)


def _mamba_block(bp, x, cfg, mesh=None, seg=None):
    from .llama_pretrain import _ffn, _residual, _rms_norm
    with jax.named_scope("block"):
        v = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
        x = _residual(x, _mamba_mixer(bp, v, cfg), cfg)
        with jax.named_scope("mlp"):
            return _ffn(bp, x, cfg)


# ---------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------
def layer_runs(layer_types) -> List[Tuple[str, int, int]]:
    """(kind, first, one past last) within the kind's stack, a run of
    equal kinds at a time, in the published order."""
    runs, seen = [], {}
    for kind in layer_types:
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], at + 1)
        else:
            runs.append((kind, at, at + 1))
    return runs


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _split(stack, cuts):
    """A kind's stacked leaf cut into its runs.  The cotangent is ONE
    concatenation of the runs' (autodiff's own would pad every run to
    the stack and add them up)."""
    return tuple(stack[a:b] for a, b in cuts)


def _split_fwd(stack, cuts):
    return _split(stack, cuts), None


def _split_bwd(cuts, _, parts):
    with jax.named_scope("layer_scan"):
        return (jnp.concatenate(parts, axis=0),)


_split.defvjp(_split_fwd, _split_bwd)


def trunk(blocks, x, cfg, mesh):
    """x [b, s, h] through the layers in ``cfg.layer_types``' order."""
    from .llama_pretrain import _block_forward, _remat_wrap
    body = {"attention": _block_forward, "mamba": _mamba_block}
    if cfg.remat_policy == "flash" and "mamba" in cfg.layer_types:
        raise NotImplementedError(
            "remat_policy='flash' saves the flash kernels' residuals; the "
            "state-space block has none to save: use 'full'")
    runs = layer_runs(cfg.layer_types)

    def runs_of(kind):
        """The kind's stacked leaves, one dict a run of its layers."""
        cuts = tuple((a, b) for k, a, b in runs if k == kind)
        if len(cuts) == 1:
            return [blocks[kind]]
        cut = {nm: _split(leaf, cuts) for nm, leaf in blocks[kind].items()}
        return [{nm: c[i] for nm, c in cut.items()}
                for i in range(len(cuts))]
    with jax.named_scope("layer_scan"):
        parts = {kind: runs_of(kind) for kind in blocks}
        for kind, _, _ in runs:
            fwd = _remat_wrap(body[kind], cfg)
            x, _ = jax.lax.scan(
                lambda carry, bp, fwd=fwd: (fwd(bp, carry, cfg, mesh, None),
                                            None),
                x, parts[kind].pop(0))
    return x
