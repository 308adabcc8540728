"""LLaMA pretraining engine — the flagship SPMD training path.

This is the TPU-native equivalent of the reference's hybrid-parallel LLaMA
path (SURVEY.md §3.4: fleet topology + mpu layers + 1F1B pipeline +
sharded optimizer).  One jitted XLA program implements the whole training
step over a 5-axis mesh:

* dp        — batch sharded; gradient AllReduce inserted by XLA
* mp (tp)   — attention heads / ffn hidden / vocab sharded (Megatron
              layout); sequence-parallel constraints between blocks put
              norm/residual work on the mp axis too
* pp        — transformer trunk pipelined via hybrid shard_map (manual
              over 'pp', GSPMD-auto over dp/mp) with a scan+ppermute
              microbatch rotation (GPipe schedule; same numerics as the
              reference's 1F1B, bubble optimisation tracked for later)
* sharding  — optimizer states (and optionally params) sharded on dim 0
              = ZeRO-1/2/3 as placement
* sep       — reserved axis for Ulysses-style context parallelism

Everything is a pure function of (params, opt_state, tokens) — donated,
so XLA updates in place.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["LlamaPretrainConfig", "init_params", "make_train_step",
           "make_forward", "init_adamw_state", "init_adafactor_state",
           "adafactor_update", "param_specs", "build_mesh", "MESH_AXES"]

MESH_AXES = ("dp", "pp", "sharding", "sep", "mp")


@dataclasses.dataclass
class LlamaPretrainConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # remat_policy: 'full', the one boundary there is: a layer holds its
    # input and the block is recomputed — but for flash attention's
    # forward outputs, and the delta rule's, where KEPT_BYTES allows
    # (_remat_wrap).
    remat_policy: str = "full"
    sequence_parallel: bool = True
    use_pallas_attention: bool = True
    # context parallelism over the 'sep' mesh axis: None, 'ring'
    # (ppermute blockwise attention, O(s/P) memory) or 'ulysses'
    # (head<->seq all_to_all; needs heads % sep == 0).  See
    # distributed/parallel/context_parallel.py.
    context_parallel: Optional[str] = None
    # loss head: >1 = chunked softmax cross-entropy (ops/chunked_loss.py:
    # one scan over that many token chunks, fp32 [B,S,V] logits never
    # materialised; under grad the same scan forms dx and dW, three
    # matmuls a chunk, and the backward only scales them — the residuals
    # are a [B,S,H] activation-dtype dx and an [H,V] dW, alive only
    # between the head's forward and its backward, which are adjacent);
    # 0/1 = plain log_softmax head.  The flattened token count
    # batch*(seq-1) must be divisible by the chunk count.
    loss_chunks: int = 0
    # LAYERS BY KIND (models/hybrid_trunk.py): the kind of every layer in
    # order, 'attention' (this file's block) or 'mamba' (a Mamba-2 mixer
    # before the same MLP).  The tree is then ``blocks: {kind: {leaf:
    # [layers of the kind, ...]}}``.  None: every layer is this file's
    # block under ``blocks: {leaf}``, the program it has always been.
    layer_types: Optional[Tuple[str, ...]] = None
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    # what a published configuration may state beside the widths; each
    # default leaves the program's operations as they are
    position_embedding_type: str = "rope"           # or 'nope'
    attention_multiplier: Optional[float] = None    # None: 1/sqrt(head_dim)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False   # the head is the table: no lm_head
    # LATENT ATTENTION, ROUTED EXPERTS, RESIDUAL STREAMS (hybrid_trunk's
    # kinds 'mla_dense' / 'mla_moe'): ``kv_lora_rank`` > 0 makes every
    # layer one of the two — the first ``first_k_dense_replace`` with the
    # dense MLP, the rest with the expert layer — unless ``layer_types``
    # says otherwise.  The published keys keep their names.  ``hc_mult``
    # 1 (the default) is the plain block on ONE residual stream, ``x +
    # F(norm(x))`` twice; ``hc_mult`` >= 2 puts each sublayer behind a
    # mixer over that many streams.  ``q_lora_rank`` 0 (the default) is
    # a query projected straight from the stream, > 0 one behind its own
    # latent and norm.  The SHARE: the router is ``n_routed_experts``
    # wide (the published count) and this device holds ``experts_held``
    # of them, from ``expert_first`` on (None: all of them).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[Dict[str, Any]] = None   # YaRN's keys
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    num_experts_per_tok: int = 0
    routed_scaling_factor: float = 1.0
    experts_held: Optional[int] = None
    expert_first: int = 0
    hc_mult: int = 1                    # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # a head's width where the configuration states it (None: hidden /
    # heads, what it has always been)
    head_dim: Optional[int] = None
    # WINDOW AND GLOBAL GQA LAYERS BEFORE AN EXPERT LAYER, ONE STREAM
    # (hybrid_trunk's kinds 'gqa_moe_window' / 'gqa_moe_global'): a layer
    # whose entry of ``sliding_window_layout`` is 1 sees the last
    # ``sliding_window_size`` keys, one whose entry is 0 every earlier key;
    # a layer whose entry of ``rope_layout`` is 1 rotates q and k.  The
    # published keys keep their names; ``layer_types`` follows from the
    # two lists.  The router reads the ATTENTION's input and, with
    # ``moe_primary_router_apply_softmax``, takes the top
    # ``num_experts_per_tok`` of its logits and a softmax over the picked;
    # its width, the picks and the experts' width are the fields above
    # (``n_routed_experts`` = the published ``moe_num_primary_experts``,
    # ``num_experts_per_tok`` = ``moe_num_active_primary_experts``,
    # ``moe_intermediate_size`` = ``moe_ffn_hidden_size``), the share
    # ``experts_held`` / ``expert_first``.
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window_size: int = 0
    moe_primary_router_apply_softmax: bool = False
    # GATED SHORT-CONVOLUTION LAYERS AMONG GQA LAYERS WITH PER-HEAD Q/K
    # NORMS, A DENSE LEAD, THEN EXPERT LAYERS PICKED BY A BIAS (LFM2's
    # ``lfm2_moe``; hybrid_trunk's kinds 'conv_dense' / 'conv_moe' /
    # 'gqa_qknorm_moe'): ``conv_L_cache`` > 0 reads ``layer_types`` as the
    # published list of 'conv' / 'full_attention' — the first
    # ``num_dense_layers`` before the dense MLP of ``intermediate_size``,
    # the rest before ``n_routed_experts`` experts of
    # ``moe_intermediate_size`` (the share as above) whose router takes
    # the top ``num_experts_per_tok`` of sigmoid scores PLUS the held
    # ``expert_bias`` (``use_expert_bias``) and gates by the scores alone.
    conv_L_cache: int = 0               # the short convolution's taps
    num_dense_layers: int = 0
    use_expert_bias: bool = False
    # KIMI-DELTA-ATTENTION LAYERS AMONG GATED GQA LAYERS, EVERY ONE BEFORE
    # AN EXPERT LAYER BESIDE A SHARED EXPERT (``solar_open2``;
    # hybrid_trunk's kinds 'kda_moe' / 'gqa_gated_moe'): ``gqa_layers``
    # names the layers that are softmax GQA gated by the sigmoid of a
    # second q-sized projection, rotated only where
    # ``position_embedding_type`` rotates, and every other layer is a
    # delta rule of ``kda_num_heads`` heads of ``kda_head_dim`` keys x
    # values whose decay is a vector a head and whose beta is 2 x a
    # sigmoid, behind a depthwise causal convolution of
    # ``short_conv_kernel_size`` taps; the decay's and the output gate's
    # maps are low-rank pairs through ``kda_head_dim``.  The router is
    # the ``sigmoid`` rule, the experts and the share the fields above.
    gqa_layers: Optional[Tuple[int, ...]] = None
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    short_conv_kernel_size: int = 0

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.sliding_window_layout is not None and \
                self.layer_types is None:
            self.layer_types = tuple(
                "gqa_moe_window" if w else "gqa_moe_global"
                for w in self.sliding_window_layout)
        if self.gqa_layers is not None and self.layer_types is None:
            from .hybrid_trunk import kda_kinds
            self.layer_types = kda_kinds(self.gqa_layers,
                                         self.num_hidden_layers)
        if self.kv_lora_rank and self.layer_types is None:
            dense = min(self.first_k_dense_replace, self.num_hidden_layers)
            self.layer_types = ("mla_dense",) * dense + ("mla_moe",) * (
                self.num_hidden_layers - dense)
        if self.layer_types is not None:
            cut = lambda a: a if a is None else tuple(
                a[:self.num_hidden_layers])
            self.layer_types = cut(self.layer_types)
            from . import hybrid_trunk
            if self.conv_L_cache and not set(self.layer_types) & set(
                    hybrid_trunk.KINDS):        # the published names
                self.layer_types = hybrid_trunk.conv_kinds(
                    self.layer_types, self.num_dense_layers)
            self.rope_layout = cut(self.rope_layout)
            self.sliding_window_layout = cut(self.sliding_window_layout)
            hybrid_trunk.check(self)
        if self.position_embedding_type not in ("rope", "nope"):
            raise ValueError(
                f"position_embedding_type must be 'rope' or 'nope', "
                f"got {self.position_embedding_type!r}")
        if self.remat_policy != "full":
            raise ValueError(
                f"remat_policy must be 'full', got {self.remat_policy!r}")
        if self.context_parallel not in (None, "ring", "ulysses"):
            raise ValueError(
                f"context_parallel must be None, 'ring' or 'ulysses', "
                f"got {self.context_parallel!r}")


def build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    dims = [dp, pp, sharding, sep, mp]
    need = int(np.prod(dims))
    if need != len(devices):
        raise ValueError(f"mesh {dims} needs {need} devices, "
                         f"have {len(devices)}")
    arr = np.array(devices).reshape(dims)
    return Mesh(arr, MESH_AXES)


# ---------------------------------------------------------------------------
# parameter structure + shardings
# ---------------------------------------------------------------------------
def _block_shapes(cfg: LlamaPretrainConfig) -> Dict[str, Tuple[int, ...]]:
    h, f = cfg.hidden_size, cfg.intermediate_size
    qh = cfg.num_attention_heads * cfg.head_dim
    kvh = cfg.num_key_value_heads * cfg.head_dim
    return {
        "ln1": (h,), "ln2": (h,),
        "wq": (h, qh), "wk": (h, kvh), "wv": (h, kvh), "wo": (qh, h),
        "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h),
    }


def _block_specs(cfg, stacked_dims: Tuple[str, ...]) -> Dict[str, P]:
    """Megatron TP layout over 'mp' (+ leading stacked layer dims)."""
    s = stacked_dims
    return {
        "ln1": P(*s, None), "ln2": P(*s, None),
        "wq": P(*s, None, "mp"), "wk": P(*s, None, "mp"),
        "wv": P(*s, None, "mp"), "wo": P(*s, "mp", None),
        "w_gate": P(*s, None, "mp"), "w_up": P(*s, None, "mp"),
        "w_down": P(*s, "mp", None),
    }


def param_specs(cfg: LlamaPretrainConfig, pp: int,
                vpp: int = 1) -> Dict[str, Any]:
    if pp > 1 and vpp > 1:
        stacked = ("pp", None, None)  # [pp, vpp, layers_per_chunk, ...]
    elif pp > 1:
        stacked = ("pp", None)  # [pp, layers_per_stage, ...]
    else:
        stacked = (None,)       # [layers, ...]
    if cfg.layer_types is not None:
        from . import hybrid_trunk
        blocks = hybrid_trunk.block_specs(cfg)
    else:
        blocks = _block_specs(cfg, stacked)
    specs = {
        "embed": P("mp", None),             # vocab-parallel embedding
        "blocks": blocks,
        "final_norm": P(None),
        "lm_head": P(None, "mp"),           # vocab-parallel unembedding
    }
    if cfg.tie_word_embeddings:
        del specs["lm_head"]
    return specs


def init_params(cfg: LlamaPretrainConfig, key, mesh: Mesh,
                pp: int = 1, vpp: int = 1) -> Dict[str, Any]:
    """``vpp > 1`` stacks blocks [pp, vpp, L/(pp*vpp), ...] for the
    interleaved virtual pipeline: element [r, c] holds the layers of
    logical stage ``c*pp + r`` (consecutive layers within a chunk)."""
    h = cfg.hidden_size
    L = cfg.num_hidden_layers
    shapes = _block_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 2)
    std = 1.0 / math.sqrt(h)

    def stacked_shape(shape):
        if pp > 1 and vpp > 1:
            return (pp, vpp, L // (pp * vpp)) + shape
        if pp > 1:
            return (pp, L // pp) + shape
        return (L,) + shape

    if cfg.layer_types is not None:
        from . import hybrid_trunk
        blocks = hybrid_trunk.init_blocks(cfg, keys[0])
    else:
        blocks = {
            name: jnp.ones(stacked_shape(shape), cfg.param_dtype)
            if name.startswith("ln") else jax.random.normal(
                keys[i], stacked_shape(shape), cfg.param_dtype) * std
            for i, (name, shape) in enumerate(shapes.items())}
    params = {
        "embed": jax.random.normal(keys[-2],
                                   (cfg.vocab_size, h),
                                   cfg.param_dtype) * std,
        "blocks": blocks,
        "final_norm": jnp.ones((h,), cfg.param_dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[-1], (h, cfg.vocab_size), cfg.param_dtype) * std
    specs = param_specs(cfg, pp, vpp)
    return jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        params, specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray))


# ---------------------------------------------------------------------------
# model math (pure, bf16 compute)
# ---------------------------------------------------------------------------
def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return (x.astype(jnp.float32) * rstd).astype(
        x.dtype) * w.astype(x.dtype)


def _mm(x, w, dt):
    """Matmul against a weight that is either a plain array or a
    weight-only int8 dict {"q": int8 [K,N], "s": f32 [N]} produced by
    ``models.decode.quantize_params_int8`` (serving path).  The Pallas
    kernel (ops/pallas/int8_matmul) is used when the dims are
    lane-aligned and FLAGS_pallas_int8_matmul is on; otherwise an XLA
    dequant-then-matmul keeps the numerics (without the HBM saving)."""
    if isinstance(w, dict):
        from ..flags import flags
        from ..ops.dispatch import get_op_impl
        impl = get_op_impl("int8_matmul", None)
        K, N = w["q"].shape
        x2 = x.reshape(-1, x.shape[-1])
        if impl is not None and flags.FLAGS_pallas_int8_matmul and \
                K % 128 == 0 and N % 128 == 0:
            out = impl(x2, w["q"], w["s"], out_dtype=dt)
        else:
            out = (x2.astype(dt) @ w["q"].astype(dt)) * \
                w["s"].astype(dt)[None, :]
        return out.reshape(*x.shape[:-1], out.shape[-1])
    return x @ w.astype(dt)


def _per_shard(fn, mesh, q_heads: int, kv_heads: int, n_bshd: int,
               rest_specs=()):
    """Run a Pallas kernel over ``[b, s, heads, d]`` operands PER SHARD
    of ``mesh``.  GSPMD cannot split a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map" — the first thing the TPU compiler said to a multi-chip
    train step), and attention/rope are independent per batch row and
    per head: batch splits over ``dp``, heads over ``mp`` (when both
    head counts divide; else every mp shard keeps all heads).  The
    first ``n_bshd`` operands and the result(s) are ``[b, s, heads,
    d]``; ``rest_specs`` are the specs of the remaining operands.
    One device (or no mesh): ``fn`` itself."""
    if mesh is None or mesh.size == 1:
        return fn
    mp = mesh.shape.get("mp", 1)
    heads = "mp" if q_heads % mp == 0 and kv_heads % mp == 0 else None
    bshd = P("dp", None, heads, None)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(bshd,) * n_bshd + tuple(rest_specs),
                         out_specs=bshd, check_vma=False)


def _rope(q, k, theta, mesh=None):
    # q/k: [b, s, n, d]
    from ..flags import flags
    from ..ops.dispatch import get_op_impl
    d = q.shape[-1]
    s = q.shape[1]
    from ..ops.pallas.rope import rope_tables
    impl = get_op_impl("fused_rope", None)
    cos_t, sin_t = rope_tables(s, d, theta)         # [s, d/2]
    if impl is not None and flags.FLAGS_pallas_rope and d % 128 == 0:
        rot = _per_shard(impl, mesh, q.shape[2], k.shape[2], 1,
                         (P(), P()))
        return rot(q, cos_t, sin_t), rot(k, cos_t, sin_t)
    cos = cos_t[None, :, None, :]
    sin = sin_t[None, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        xc = (x1.astype(jnp.float32) * cos -
              x2.astype(jnp.float32) * sin)
        xs = (x2.astype(jnp.float32) * cos +
              x1.astype(jnp.float32) * sin)
        return jnp.concatenate([xc, xs], -1).astype(x.dtype)

    return rot(q), rot(k)


def _attention(q, k, v, cfg, mesh=None, seg=None, window=None):
    """Causal attention [b, s, n, d].  Routes to context-parallel
    attention over the sep axis when configured, else the Pallas flash
    kernel when registered (ops/pallas), else the fused XLA composite.

    ``window``: a query sees its last ``window`` keys, itself among
    them (the flash kernels' windowed form, or the composite's mask; no
    context-parallel or packed path has one).

    ``seg`` [b, s] int32 enables PACKED-pretrain attention: sequences
    concatenated along s attend only within their own segment, via the
    block-skipping segmented flash kernel (ops/pallas/flash_varlen.py
    — the reference's flash_attn_unpadded/varlen path)."""
    from ..ops.dispatch import get_op_impl
    from ..flags import flags

    def full_heads(k, v, heads=q.shape[2]):
        # paths that cannot group natively repeat K/V up to q heads;
        # the dense kernels, under a mesh, up to the count mp divides
        if k.shape[2] != heads:
            rep = heads // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return k, v

    if window is not None and (seg is not None or (
            cfg.context_parallel and mesh is not None
            and mesh.shape.get("sep", 1) > 1)):
        raise NotImplementedError(
            "a window over packed segments or sequence shards")
    if cfg.context_parallel and mesh is not None and \
            mesh.shape.get("sep", 1) > 1:
        if seg is not None:
            raise NotImplementedError(
                "packed segment attention with context parallelism is "
                "not supported; use sep for single long sequences")
        from ..distributed.parallel.context_parallel import (
            ring_attention, ulysses_attention)
        cp = ring_attention if cfg.context_parallel == "ring" \
            else ulysses_attention
        k, v = full_heads(k, v)
        return cp(q, k, v, mesh, axis="sep", causal=True)
    if seg is not None:
        # GQA-NATIVE: both the segmented kernel and the oracle take
        # nkv < n heads directly — no repeated K/V is materialised
        from ..ops.pallas.flash_varlen import (
            flash_attention_segmented, xla_segmented_sdpa)
        if cfg.use_pallas_attention and flags.FLAGS_pallas_flash_attention:
            return _per_shard(
                lambda q, k, v, seg: flash_attention_segmented(
                    q, k, v, seg, causal=True),
                mesh, q.shape[2], k.shape[2], 3,
                (P("dp", None),))(q, k, v, jnp.asarray(seg, jnp.int32))
        return xla_segmented_sdpa(q, k, v, jnp.asarray(seg, jnp.int32),
                                  True)
    impl = get_op_impl("flash_attention", None)
    if impl is not None and cfg.use_pallas_attention and \
            flags.FLAGS_pallas_flash_attention:
        # GQA-NATIVE as well: the kernels index K/V by head // group.
        # Only where mp splits the query heads and not the KV heads
        # (MQA over mp) are K/V repeated, up to the least count that mp
        # divides, so that the heads still split
        mp = 1 if mesh is None else mesh.shape.get("mp", 1)
        if q.shape[2] % mp == 0:
            k, v = full_heads(k, v, math.lcm(k.shape[2], mp))
        if window is not None:
            impl = functools.partial(impl, window=window)
        return _per_shard(lambda q, k, v: impl(q, k, v, causal=True),
                          mesh, q.shape[2], k.shape[2], 3)(q, k, v)
    k, v = full_heads(k, v)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    s = logits.shape[-1]
    mask = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        mask &= ~jnp.tril(mask, -window)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(v.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _block_pre_attn(bp: Dict[str, Any], x, cfg: LlamaPretrainConfig,
                    mesh: Optional[Mesh] = None):
    """ln1 + QKV projections + rope -> q [b, s, n, d], k, v [b, s, nkv,
    d]: K/V at their own head count."""
    with jax.named_scope("attn_qkv"):
        y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
    return _qkv(bp, y, cfg, mesh, cfg.position_embedding_type == "rope")


def _qkv(bp: Dict[str, Any], y, cfg: LlamaPretrainConfig,
         mesh: Optional[Mesh], rotate: bool):
    """The projections of y = ln1(x), rotated where ``rotate`` says (a
    configuration's one ``position_embedding_type``, or a kind's own
    rule: hybrid_trunk's window layers rotate, its global ones do
    not); q and k normed a head first where the layer holds
    ``q_layernorm`` / ``k_layernorm``."""
    b, s, h = y.shape
    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype
    with jax.named_scope("attn_qkv"):
        q = (y @ bp["wq"].astype(dt)).reshape(b, s, n, d)
        k = (y @ bp["wk"].astype(dt)).reshape(b, s, nkv, d)
        v = (y @ bp["wv"].astype(dt)).reshape(b, s, nkv, d)
        if cfg.attention_multiplier is not None:
            # the kernels score at 1/sqrt(d): the rest of the
            # configuration's own scale rides on q
            q = q * (cfg.attention_multiplier * math.sqrt(d))
    if "q_layernorm" in bp:
        # a kind that holds them: every head of q and of k normed over
        # its own ``head_dim``, BEFORE the rotation
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q, bp["q_layernorm"], cfg.rms_norm_eps)
            k = _rms_norm(k, bp["k_layernorm"], cfg.rms_norm_eps)
    if rotate:
        with jax.named_scope("rope"):
            q, k = _rope(q, k, cfg.rope_theta, mesh)
    # GQA stays UN-repeated here: _attention's flash kernels, dense and
    # segmented, index kv heads by group natively (the whole point of
    # GQA — nkv heads of K/V HBM traffic, not n); the paths that need
    # full heads (context parallel, the XLA composite) repeat at their
    # own entry.  The [b, s, n, d] views are reshapes of the projections'
    # [b, s, n*d]: at head dim % 128 == 0 rope and dense flash address
    # them as such, and no array with the heads on its tiles' sublanes
    # is ever made
    return q, k, v


def _residual(x, out, cfg):
    """``x + residual_multiplier * out``, one rounding."""
    if cfg.residual_multiplier == 1.0:
        return x + out
    return (x.astype(jnp.float32) + cfg.residual_multiplier *
            out.astype(jnp.float32)).astype(x.dtype)


def _block_post_attn(bp: Dict[str, Any], x, attn,
                     cfg: LlamaPretrainConfig):
    """Output projection + residual + FFN.  Weight entries may be plain
    arrays (training) or weight-only int8 dicts (the decode serving
    path) — see :func:`_mm`."""
    b, s, h = x.shape
    with jax.named_scope("attn_out"):
        x = _residual(x, _mm(attn.reshape(b, s, -1), bp["wo"], cfg.dtype),
                      cfg)
    with jax.named_scope("mlp"):
        return _ffn(bp, x, cfg)


def _swiglu(y, w_gate, w_up, w_down, dt):
    gate = jax.nn.silu(_mm(y, w_gate, dt))
    up = _mm(y, w_up, dt)
    return _mm(gate * up, w_down, dt)


def _ffn(bp: Dict[str, Any], x, cfg: LlamaPretrainConfig):
    """ln2 + gated FFN + residual: the ``mlp`` scope's body."""
    y = _rms_norm(x, bp["ln2"], cfg.rms_norm_eps)
    return _residual(x, _swiglu(y, bp["w_gate"], bp["w_up"], bp["w_down"],
                                cfg.dtype), cfg)


def _block_forward(bp: Dict[str, Any], x, cfg: LlamaPretrainConfig,
                   mesh: Optional[Mesh] = None, seg=None):
    """One transformer block; x [b, s, h] in compute dtype."""
    with jax.named_scope("block"):
        q, k, v = _block_pre_attn(bp, x, cfg, mesh)
        with jax.named_scope("attn"):
            attn = _attention(q, k, v, cfg, mesh, seg)
        return _block_post_attn(bp, x, attn, cfg)


# Full remat holds a layer's input and runs the block again in the
# backward pass, with three exceptions.  Two are a forward kernel's
# results, kept where their bytes over all the layers that run the
# kernel fit ONE budget a trunk, this much (a sixteenth of a v5e's HBM):
# ``flash_fwd``'s ``o`` and ``lse``, reckoned first
# (:func:`keeps_flash_outputs`), and ``kda_chunk_fwd``'s ``o`` and
# entering states, kept where they fit beside what flash keeps
# (``hybrid_trunk.kept_outputs``).  The recompute then reads them and
# the kernel runs once a layer a step; past the budget the whole block
# is recomputed, as before.  Bytes, because HBM is what the choice
# spends: the expert cell keeps 5 x 136 MB and the hybrid cell 69 MB,
# the dense cell's 18 x 68 MB = 1.23 GB would buy back the compiler's
# own rematerialization (PERF.md section 6, PR 43); the delta-rule cell
# keeps 136 MB + 3 x 268 MB = 942 MB, and at a row of 16,384 the three
# layers' 1.61 GB are recomputed (PR 55).
# The third exception has no bound: an expert layer's ROUTING
# (``ops/moe.ROUTING_NAMES``: the picks, their scores and the plan's
# integer arrays, 2-6 MB a layer beside the 84 MB of its kept input) is
# kept wherever a layer routes, so the recompute has no router's
# product, no ``top_k`` and no sort (PERF.md section 6, PR 46).
KEPT_BYTES = 1 << 30


def flash_output_bytes(batch: int, seq: int, heads: int, value_dim: int,
                       dtype, layers: int) -> int:
    """``layers`` flash layers' ``o`` ``[batch, seq, heads, value_dim]``
    in ``dtype`` and fp32 ``lse`` ``[batch, heads, seq]``, as the trunk
    sees them."""
    o = batch * seq * heads * value_dim * jnp.dtype(dtype).itemsize
    lse = batch * seq * heads * 4
    return layers * (o + lse)


def keeps_flash_outputs(batch: int, seq: int, heads: int, value_dim: int,
                        dtype, layers: int) -> bool:
    """The rule of :data:`KEPT_BYTES` for flash, which is reckoned
    first: :func:`flash_output_bytes` against the whole budget."""
    return 0 < flash_output_bytes(batch, seq, heads, value_dim, dtype,
                                  layers) <= KEPT_BYTES


def _remat_wrap(fwd, cfg, keep_flash: bool = False, routes: bool = False,
                keep_kda: bool = False):
    """``fwd`` under full remat, but for what the boundary's policy
    keeps: ``keep_flash`` (what :func:`keeps_flash_outputs` said of the
    trunk's flash layers) ``flash_fwd``'s outputs, ``routes`` (the layer
    holds routed experts) what ``ops/moe`` names of a layer's routing,
    ``keep_kda`` (``hybrid_trunk.kept_outputs``, for the layers that run
    the delta rule) ``kda_chunk_fwd``'s outputs."""
    if not cfg.remat:
        return fwd
    names = ()
    if keep_flash:
        from ..ops.pallas.flash_attention import FWD_OUTPUT_NAMES
        names += FWD_OUTPUT_NAMES
    if routes:
        from ..ops.moe import ROUTING_NAMES
        names += ROUTING_NAMES
    if keep_kda:
        from ..ops.pallas.kda_chunk import FWD_OUTPUT_NAMES
        names += FWD_OUTPUT_NAMES
    policy = jax.checkpoint_policies.save_only_these_names(*names) \
        if names else None
    return jax.checkpoint(fwd, static_argnums=(2, 3), policy=policy)


def _trunk_scan(blocks, x, cfg, mesh, seg=None):
    """pp == 1: scan over the layer-stacked block params with remat."""
    fwd = _remat_wrap(_block_forward, cfg, keeps_flash_outputs(
        x.shape[0], x.shape[1], cfg.num_attention_heads, cfg.head_dim,
        cfg.dtype, cfg.num_hidden_layers))
    # Megatron-SP activation constraints are a TPU optimisation; XLA:CPU's
    # AllReducePromotion/partitioner passes crash on the collectives they
    # produce inside scan+remat, so they're disabled when the MESH is made
    # of CPU devices (mp weight shardings are still exercised there).  The
    # mesh decides, not the default backend: a step compiled for described
    # TPU devices from a CPU host keeps the constraint.
    sp_on = (cfg.sequence_parallel and mesh is not None and
             mesh.shape.get("mp", 1) > 1 and
             mesh.devices.flat[0].platform != "cpu")

    def step(carry, bp):
        out = fwd(bp, carry, cfg, mesh, seg)
        if sp_on:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P("dp", "mp", None)))
        return out, None

    with jax.named_scope("layer_scan"):
        x, _ = jax.lax.scan(step, x, blocks)
    return x


def _trunk_pipeline(blocks, x_mb, cfg, mesh, pp: int, vpp: int = 1):
    """pp > 1: the reusable pipeline engines from distributed/parallel/
    pipeline.py — hybrid shard_map, manual over 'pp', auto over dp/mp.
    GPipe rotation for vpp == 1, interleaved virtual pipeline for
    vpp > 1 (blocks stacked [pp, vpp, Lc, ...]).

    ``x_mb``: [M, mb, s, h] microbatches (replicated over pp); each
    stage scans its own layer-stacked blocks.
    """
    from ..distributed.parallel.pipeline import (gpipe_forward,
                                                 interleaved_forward)

    # every microbatch's outputs are held at once: the whole batch counts
    fwd = _remat_wrap(_block_forward, cfg, keeps_flash_outputs(
        x_mb.shape[0] * x_mb.shape[1], x_mb.shape[2],
        cfg.num_attention_heads, cfg.head_dim, cfg.dtype,
        cfg.num_hidden_layers))

    def stage_fn(stage_bp, x):
        def step(carry, bp):
            return fwd(bp, carry, cfg, None), None
        out, _ = jax.lax.scan(step, x, stage_bp)
        return out

    if vpp > 1:
        return interleaved_forward(stage_fn, blocks, x_mb, mesh, pp, vpp)
    return gpipe_forward(stage_fn, blocks, x_mb, mesh, pp)


def make_forward(cfg: LlamaPretrainConfig, mesh: Optional[Mesh] = None,
                 pp: int = 1, microbatches: int = 1, vpp: int = 1):
    """Returns pure fn(params, tokens[B,S]) -> logits or loss parts."""

    if cfg.layer_types is not None:
        from . import hybrid_trunk
        hybrid_trunk.check_layout(cfg, mesh, pp)

    def forward_loss(params, tokens, segment_ids=None):
        """``segment_ids`` [B, S] enables packed pretraining: attention
        stays within segments (segmented flash kernel) and the loss
        masks the cross-segment boundary targets — the last token of a
        packed sequence must not be trained to predict the next
        sequence's first token (reference: packed/varlen pretrain over
        flash_attn_unpadded)."""
        dt = cfg.dtype
        inputs = tokens[:, :-1]
        targets = tokens[:, 1:]
        seg_in = seg_tg = None
        if segment_ids is not None:
            if pp > 1:
                raise NotImplementedError(
                    "packed segment pretraining with pp > 1 is not "
                    "supported yet")
            seg_all = jnp.asarray(segment_ids, jnp.int32)
            seg_in = seg_all[:, :-1]
            seg_tg = seg_all[:, 1:]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], inputs, axis=0)
            if cfg.embedding_multiplier != 1.0:
                x = x * cfg.embedding_multiplier
            x = x.astype(dt)
        cp_on = False
        if mesh is not None:
            cp_on = bool(cfg.context_parallel and
                         mesh.shape.get("sep", 1) > 1)
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(
                    mesh, P("dp", "sep" if cp_on else None, None)))
        if pp > 1:
            if cp_on:
                # the pipeline stage runs inside a shard_map manual over
                # 'pp' and does not thread the mesh into attention, so
                # the sep path would silently degrade to full-sequence
                # GSPMD attention — refuse rather than quietly OOM
                raise NotImplementedError(
                    "context_parallel with pp > 1 is not supported yet; "
                    "use sep parallelism with pp == 1")
            B = x.shape[0]
            mb = B // microbatches
            x_mb = x.reshape(microbatches, mb, *x.shape[1:])
            x = _trunk_pipeline(params["blocks"], x_mb, cfg, mesh, pp,
                                vpp)
            x = x.reshape(B, *x.shape[2:])
        elif cfg.layer_types is not None:
            if seg_in is not None:
                raise NotImplementedError(
                    "packed segments with layers by kind: the state-space "
                    "mixer and the delta rule's matrix state have no reset "
                    "at a document boundary")
            x = hybrid_trunk.trunk(params["blocks"], x, cfg, mesh)
        else:
            x = _trunk_scan(params["blocks"], x, cfg, mesh, seg_in)
        with jax.named_scope("loss_head"):
            x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            if cfg.loss_chunks > 1 and seg_in is not None:
                import warnings
                warnings.warn(
                    "packed segment pretraining uses the unchunked loss "
                    "head (masked chunked CE not implemented); at large "
                    "vocab this materialises full [B,S,V] logits",
                    stacklevel=2)
            tied = cfg.tie_word_embeddings
            head = params["embed"] if tied else params["lm_head"]
            if cfg.loss_chunks > 1 and seg_in is None:
                from ..ops.chunked_loss import chunked_softmax_cross_entropy
                return chunked_softmax_cross_entropy(
                    x, head, targets, cfg.loss_chunks, dt,
                    1.0 / cfg.logits_scaling, tied)
            if tied:
                head = head.T
            logits = (x @ head.astype(dt)).astype(jnp.float32)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            logp = jax.nn.log_softmax(logits, -1)
            ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
            if seg_in is not None:
                # mask boundary targets AND padding (negative segment ids)
                valid = jnp.logical_and(seg_in == seg_tg, seg_tg >= 0)
                valid = valid.astype(jnp.float32)
                return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
            return -jnp.mean(ll)

    return forward_loss


# ---------------------------------------------------------------------------
# optimizer state: born where the jitted step leaves it.  A state leaf
# handed in on another placement than the step returns it with (a bare
# ``jnp.zeros`` carries no mesh) makes the step's SECOND call a new
# program: traced and compiled again.
# ---------------------------------------------------------------------------
def _mesh_of(p) -> Optional[Mesh]:
    sh = getattr(p, "sharding", None)
    if isinstance(sh, NamedSharding) and isinstance(sh.mesh, Mesh):
        return sh.mesh
    return None


def _zeros_less(p, axis: int):
    """fp32 zeros for ``p``'s mean over ``axis``: on ``p``'s mesh with
    the axes that are left sharded as ``p`` shards them — what the
    reduction leaves; a plain array where ``p`` carries no mesh."""
    keep = [a for a in range(p.ndim) if a != axis % p.ndim]
    mesh = _mesh_of(p)
    where = None
    if mesh is not None:
        spec = tuple(p.sharding.spec) + (None,) * p.ndim
        where = NamedSharding(mesh, P(*(spec[a] for a in keep)))
    return jnp.zeros([p.shape[a] for a in keep], jnp.float32, device=where)


def _step_count(params):
    """``t`` = 0, on every device of the parameters' mesh."""
    mesh = next(filter(None, map(_mesh_of,
                                 jax.tree_util.tree_leaves(params))), None)
    return jnp.zeros((), jnp.int32,
                     device=mesh and NamedSharding(mesh, P()))


# ---------------------------------------------------------------------------
# fused AdamW (sharded states = ZeRO-1/2)
# ---------------------------------------------------------------------------
def init_adamw_state(params, mesh: Optional[Mesh] = None,
                     zero_axis: Optional[str] = "sharding",
                     moment_dtype: Any = None):
    """AdamW state.  ``moment_dtype`` (e.g. ``jnp.bfloat16``) stores the
    moments quantized — halves optimizer HBM, the compute stays fp32
    (read -> upcast -> update -> store).  Same trade as the reference's
    multi-precision / low-precision optimizer paths
    (/root/reference/python/paddle/optimizer/adamw.py multi_precision)."""
    def make(p):
        dt = moment_dtype or p.dtype
        # zeros_like inherits the param's NamedSharding (mp/pp layouts);
        # the zero_axis branch below then re-lays-out for ZeRO placement
        m = jnp.zeros_like(p, dtype=dt)
        v = jnp.zeros_like(p, dtype=dt)
        if mesh is not None and zero_axis and \
                mesh.shape.get(zero_axis, 1) > 1 and p.ndim >= 1 and \
                p.shape[0] % mesh.shape[zero_axis] == 0:
            sh = NamedSharding(mesh, P(*([zero_axis] + [None] *
                                         (p.ndim - 1))))
            m = jax.device_put(m, sh)
            v = jax.device_put(v, sh)
        return {"m": m, "v": v}

    return {"t": _step_count(params),
            "moments": jax.tree_util.tree_map(make, params)}


def adamw_update(params, grads, state, lr=3e-4, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1):
    t = state["t"] + 1
    tf = t.astype(jnp.float32)

    def upd(p, g, mo):
        from ..ops.dispatch import get_op_impl
        impl = get_op_impl("fused_adamw", None)
        g = g.astype(jnp.float32)
        mdt = mo["m"].dtype
        if impl is not None and mdt == jnp.float32:
            return impl(p, g, mo["m"], mo["v"], tf, lr, b1, b2, eps,
                        weight_decay)
        m = b1 * mo["m"].astype(jnp.float32) + (1 - b1) * g
        v = b2 * mo["v"].astype(jnp.float32) + (1 - b2) * g * g
        mhat = m / (1 - b1 ** tf)
        vhat = v / (1 - b2 ** tf)
        new_p = p * (1 - lr * weight_decay) - lr * mhat / (
            jnp.sqrt(vhat) + eps)
        return new_p.astype(p.dtype), {"m": m.astype(mdt),
                                       "v": v.astype(mdt)}

    flat_p, tree = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = tree.flatten_up_to(state["moments"])
    new_p, new_m = [], []
    for p, g, mo in zip(flat_p, flat_g, flat_m):
        np_, nm = upd(p, g, mo)
        new_p.append(np_)
        new_m.append(nm)
    return (jax.tree_util.tree_unflatten(tree, new_p),
            {"t": t, "moments": jax.tree_util.tree_unflatten(tree,
                                                             new_m)})


# ---------------------------------------------------------------------------
# Adafactor (factored second moment) — the TPU-native memory-efficient
# optimizer (Shazeer & Stern 2018; how T5/PaLM pretrained on TPU pods).
# For a [.., A, B] matrix the second moment is stored as a row EMA [.., A]
# plus a column EMA [.., B] instead of [.., A, B]: optimizer HBM drops
# from 2x params (AdamW fp32) to ~per-row/col vectors, which is what lets
# a >1B-param model train on one 16GB v5e chip.  The reference has no
# Adafactor; its answer to optimizer memory is sharding/offload
# (group_sharded_stage3.py) which needs multiple devices — on a single
# chip factoring is the only move, and it is a TPU-lineage one.
# ---------------------------------------------------------------------------
def _factored(p) -> bool:
    return p.ndim >= 2 and p.shape[-1] >= 128 and p.shape[-2] >= 128


def init_adafactor_state(params, mesh: Optional[Mesh] = None,
                         zero_axis: Optional[str] = "sharding",
                         beta1: float = 0.0,
                         moment_dtype: Any = jnp.bfloat16):
    """Adafactor state: factored second moment for matrices, full vector
    for 1-D params; optional first moment (``beta1 > 0``) stored in
    ``moment_dtype``."""
    def make(p):
        st = {}
        if _factored(p):
            # vr/vc are per-row/col vectors (KBs): the means over the
            # last axis and over the last but one
            st["vr"] = _zeros_less(p, -1)
            st["vc"] = _zeros_less(p, -2)
        else:
            # full copy for small params: inherit the param's sharding
            st["v"] = jnp.zeros_like(p, dtype=jnp.float32)
        if beta1 > 0.0:
            m = jnp.zeros_like(p, dtype=moment_dtype)
            if mesh is not None and zero_axis and \
                    mesh.shape.get(zero_axis, 1) > 1 and p.ndim >= 1 and \
                    p.shape[0] % mesh.shape[zero_axis] == 0:
                m = jax.device_put(m, NamedSharding(
                    mesh, P(*([zero_axis] + [None] * (p.ndim - 1)))))
            st["m"] = m
        return st

    return {"t": _step_count(params),
            "moments": jax.tree_util.tree_map(make, params)}


def _rms(x, lead: int = 0):
    """Root mean square of ONE tensor: of everything, or — for a leaf
    that stacks tensors over its first ``lead`` axes — of each."""
    axes = tuple(range(lead, x.ndim)) if lead else None
    return jnp.sqrt(jnp.mean(jnp.square(x), axis=axes,
                             keepdims=bool(lead)) + 1e-30)


def adafactor_update(params, grads, state, lr=1e-2, weight_decay=0.0,
                     beta1: float = 0.0, clip_threshold=1.0, eps1=1e-30,
                     eps2=1e-3, decay_pow=0.8, stack_dims: int = 1):
    """One Adafactor step.  ``lr`` is the relative step size: the actual
    update is ``lr * max(eps2, rms(p)) * u_clipped`` (scale_parameter
    semantics), with beta2_t = 1 - t**-decay_pow (built-in warmup).
    ``beta1`` must match the ``init_adafactor_state`` value (momentum is
    used iff the state carries an ``m`` slot).

    One LAYER's leaf is one Adafactor tensor: a leaf under ``blocks``
    stacks its layers over its first ``stack_dims`` axes (1; 2 and 3
    under a pipeline), and the update is clipped by the rms of each
    layer's own update and scaled by the rms of each layer's own leaf,
    as the second moment is already factored a layer at a time."""
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    beta2 = 1.0 - tf ** (-decay_pow)

    def upd(p, g, st, lead):
        if ("m" in st) != (beta1 > 0.0):
            raise ValueError(
                f"beta1={beta1} disagrees with the optimizer state "
                f"({'has' if 'm' in st else 'no'} momentum slot) — pass "
                f"the same beta1 to init_adafactor_state and "
                f"adafactor_update/make_train_step")
        g = g.astype(jnp.float32)
        g2 = g * g + eps1
        new_st = {}
        if "vr" in st:
            vr = beta2 * st["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
            vc = beta2 * st["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
            new_st["vr"], new_st["vc"] = vr, vc
            # vhat = outer(vr, vc) / mean(vr) — the rank-1 reconstruction
            r = vr / jnp.mean(vr, axis=-1, keepdims=True)
            u = g * jax.lax.rsqrt(r[..., :, None] * vc[..., None, :])
        else:
            v = beta2 * st["v"] + (1 - beta2) * g2
            new_st["v"] = v
            u = g * jax.lax.rsqrt(v)
        u = u / jnp.maximum(1.0, _rms(u, lead) / clip_threshold)
        alpha = lr * jnp.maximum(eps2, _rms(p.astype(jnp.float32), lead))
        step_ = alpha * u
        if "m" in st:
            m = beta1 * st["m"].astype(jnp.float32) + (1 - beta1) * step_
            new_st["m"] = m.astype(st["m"].dtype)
            step_ = m
        new_p = p.astype(jnp.float32) * (1 - alpha * weight_decay) - step_
        return new_p.astype(p.dtype), new_st

    with_path, tree = jax.tree_util.tree_flatten_with_path(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_s = tree.flatten_up_to(state["moments"])
    new_p, new_s = [], []
    for (path, p), g, st in zip(with_path, flat_g, flat_s):
        stacked = getattr(path[0], "key", None) == "blocks"
        np_, ns = upd(p, g, st, stack_dims if stacked else 0)
        new_p.append(np_)
        new_s.append(ns)
    return (jax.tree_util.tree_unflatten(tree, new_p),
            {"t": t,
             "moments": jax.tree_util.tree_unflatten(tree, new_s)})


def make_train_step(cfg: LlamaPretrainConfig, mesh: Mesh, pp: int = 1,
                    microbatches: int = 1, lr: float = 3e-4,
                    weight_decay: float = 0.1, accum_steps: int = 1,
                    optimizer: str = "adamw", beta1: float = 0.0,
                    vpp: int = 1):
    """One donated, jitted XLA program: fwd + bwd + optimizer.

    ``optimizer``: "adamw" (opt_state from ``init_adamw_state``) or
    "adafactor" (``init_adafactor_state``; ``lr`` becomes the relative
    step size and ``beta1`` the optional momentum).

    ``accum_steps > 1`` runs gradient accumulation over microbatches via
    ``lax.scan``.  On TPU this is the preferred memory/FLOPs trade: each
    microbatch's activations are live only inside its own scan iteration,
    so ``cfg.remat`` can stay off — full rematerialisation costs ~30%
    extra trunk FLOPs, while accumulation costs none (the optimizer and
    its HBM traffic also amortise over the larger global batch).
    """
    fwd = make_forward(cfg, mesh, pp, microbatches, vpp)
    if optimizer not in ("adamw", "adafactor"):
        raise ValueError(f"optimizer must be adamw/adafactor, "
                         f"got {optimizer!r}")

    def step(params, opt_state, tokens):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(fwd)(params, tokens)
        else:
            tb = tokens.reshape(accum_steps, -1, tokens.shape[-1])

            def mb_step(g_acc, tok):
                loss, g = jax.value_and_grad(fwd)(params, tok)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return g_acc, loss

            with jax.named_scope("grad_accum"):
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, losses = jax.lax.scan(mb_step, g0, tb)
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum_steps, grads)
                loss = jnp.mean(losses)
        with jax.named_scope("optimizer"):
            if optimizer == "adafactor":
                params, opt_state = adafactor_update(
                    params, grads, opt_state, lr=lr,
                    weight_decay=weight_decay, beta1=beta1,
                    stack_dims=1 if pp == 1 else 2 if vpp == 1 else 3)
            else:
                params, opt_state = adamw_update(
                    params, grads, opt_state, lr=lr,
                    weight_decay=weight_decay)
        return params, opt_state, loss

    if mesh.size == 1:      # nothing to pin: the program it has been
        return jax.jit(step, donate_argnums=(0, 1))
    return _PinnedStep(step)


class _PinnedStep:
    """``step(params, opt_state, tokens)`` on a mesh of several devices:
    jitted with its first two operands donated and every leaf it
    returns for them pinned where the donated leaf lives, one program a
    placement of the operands.  Left to the compiler an output comes
    back on a sharding of ITS choosing (AdamW under ZeRO returned the
    tensor-parallel weights replicated), and the second and the third
    call each compiled a program for what the call before had left.  A
    leaf that carries no mesh is left to the compiler."""

    def __init__(self, step):
        self._step, self._jitted = step, {}

    def _for(self, params, opt_state):
        flat, tree = jax.tree_util.tree_flatten((params, opt_state))
        where = tuple(x.sharding if _mesh_of(x) is not None else None
                      for x in flat)
        if (tree, where) not in self._jitted:
            pinned = jax.tree_util.tree_unflatten(tree, where)
            self._jitted[tree, where] = jax.jit(
                self._step, donate_argnums=(0, 1),
                out_shardings=(*pinned, None))
        return self._jitted[tree, where]

    def __call__(self, params, opt_state, tokens):
        return self._for(params, opt_state)(params, opt_state, tokens)

    def lower(self, params, opt_state, tokens):
        return self._for(params, opt_state).lower(params, opt_state, tokens)

    def _cache_size(self) -> int:
        return sum(j._cache_size() for j in self._jitted.values())
