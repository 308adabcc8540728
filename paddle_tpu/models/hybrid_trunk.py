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

``mla_dense`` and ``mla_moe`` are a layer of latent attention (MLA)
before a dense SwiGLU MLP or before an expert layer (routed experts
without dropping, ``ops/moe.py``, beside shared experts).  What
``hc_mult`` states is the residual they run on.  ``hc_mult == 1``, ONE
stream — the block every model of the family is built on, the trunk
carrying ``[b, s, C]``, no mixer leaf and no pass over streams:

    x1 = x  + MLA(rms_norm(x; ln1))
    x2 = x1 + F(rms_norm(x1; ln2))      F: the MLP or the expert layer
      dense:   F(u) = (silu(u . w_gate) * (u . w_up)) . w_down
      experts: s = sigmoid(u . w_router)  (fp32, ``n_routed_experts``
               wide);  picks = the top ``num_experts_per_tok`` of s
               g_e = routed_scaling_factor s_e / (sum of the picked s +
                     1e-20)
               F(u) = sum over the picks whose expert is HELD here of
                      g_e E_e(u)  +  SwiGLU(u; ws_*)   (the shared
                      experts as one SwiGLU of n_shared_experts x the
                      experts' width)

``hc_mult = n >= 2``, n STREAMS (manifold-constrained hyper-connections,
arXiv:2512.24880 over arXiv:2409.19606).  A token's state is ``X [n,
C]``; the trunk carries it flat, ``[b, s, n * C]`` (stream i is lanes
``i C .. (i + 1) C``): the table's row is copied into the n streams, the
streams are summed before the final norm.  Each of a layer's two
sublayers F (attention; the MLP or the expert layer) has mixer leaves
``phi [n C, n^2 + 2 n]``, ``alpha [3]``, ``b [n^2 + 2 n]``:

    u = vec(X) / rms(vec(X));  m = u . phi                (fp32 from here)
    H_pre = sigmoid(alpha_1 m[:n] + b[:n])
    H_post = 2 sigmoid(alpha_2 m[n:2n] + b[n:2n])
    H_res = Sinkhorn(exp(clip(alpha_3 mat(m[2n:]) + mat(b[2n:]))))
            (``hc_sinkhorn_iters`` rounds of row then column
            normalisation, denominators + ``hc_eps``)
    h = H_pre . X;  y = F(rms_norm(h));  X' = H_res . X + H_post^T (x) y

The passes over the streams (m and h; X'; their backwards) are the four
kernels of ``ops/pallas/hc_mix.py`` where ``hc_mix.takes`` takes the
shape, each reading the streams once; H_post and H_res — a few numbers
a token — are XLA's in either form.

    MLA on x = rms_norm(h; ln1), in either form of the residual:
    q = rms_norm(x . w_qa; q_norm) . [w_qb_nope | w_qb_rope]   [H, 128 | 64]
        (``q_lora_rank`` > 0), or, WITHOUT a latent (``q_lora_rank`` 0),
    q = x . [w_q_nope | w_q_rope]                 (no norm of the query)
    [c | k_r] = x . w_kva  (kv_lora_rank | 64);  [k_nope | v] =
    rms_norm(c; kv_norm) . [w_kvb_k | w_kvb_v]                 [H, 128 | 128]
    q_r, k_r rotated (rotate-half; a published INTERLEAVED pairing is a
    fixed permutation of the rope columns, which the leaves hold) at
    ``rope_theta``'s frequencies or YaRN's blend of them, k_r ONE vector
    a token for all heads;  S = (q_nope . k_nope^T + q_r . k_r^T)
    * (128 + 64)^-1/2 * mscale^2  — ``flash_attention_split``: two
    operand pairs, no 192-wide operand, no 32-fold copy of k_r.

``gqa_moe_window`` and ``gqa_moe_global`` are a layer of grouped-query
attention (``llama_pretrain``'s own projections, rotation and flash
entry, heads of ``head_dim``) before an expert layer WITHOUT a shared
expert, on ONE residual stream; the router reads the attention's input:

    x = rms_norm(h; ln1)
    z = x . w_router  (fp32, ``n_routed_experts`` wide);  picks = the top
    ``num_experts_per_tok`` of z;  g = softmax(z over the picks)
    q, k, v = x . wq, x . wk, x . wv                      [H | KV, head_dim]
    window: q, k rotated (rotate-half, rope_theta); key j is visible to
            query i iff i - sliding_window_size < j <= i  (``flash_win_*``)
    global: no rotation; j <= i                            (``flash_*``)
    h1 = h + softmax(q k^T / sqrt(head_dim) over the visible keys) v . wo
    u = rms_norm(h1; ln2)
    h2 = h1 + sum over the picks whose expert is HELD here of
              g_e (relu(u . w_gate_e) * (u . w_up_e)) . w_down_e

The route and the plan (``moe_route``, ``moe_dispatch``) are issued from
x BEFORE attention: they depend on nothing attention computes, so XLA
may run them beside it.  Both run ONCE a layer a step: the trunk's
checkpoint boundary keeps what ``ops/moe.ROUTING_NAMES`` names for the
kinds in ``ROUTED_KINDS``, and the recompute reads it.

``conv_dense``, ``conv_moe`` and ``gqa_qknorm_moe`` are the layers of a
short-convolution / attention model (LFM2's ``lfm2_moe``): an OPERATOR —
a gated short convolution, or grouped-query attention whose q and k
heads are normed — before a dense SwiGLU MLP (the leading layers) or an
expert layer WITHOUT a shared expert whose router picks by a bias it
does not gate by, on ONE residual stream:

    y = rms_norm(h; ln1)
    conv:  [B | Cg | X] = y . w_in                     (three groups of C)
           v[t] = sum_{k<K} conv_w[:, k] (B X)[t - (K-1) + k]   (zeros
                  before the row; no bias, no activation; K =
                  conv_L_cache;  ops/pallas/causal_conv.py, gated form)
           h1 = h + (Cg * v) . w_out
    gqa_qknorm: q, k, v = y . wq, y . wk, y . wv         [H | KV, head_dim]
           q = rms_norm(q; q_layernorm), k = rms_norm(k; k_layernorm),
           a head at a time, BEFORE the rotation (rotate-half, rope_theta)
           h1 = h + softmax(q k^T / sqrt(head_dim), j <= i) v . wo
    u = rms_norm(h1; ln2)
    conv_dense: h2 = h1 + (silu(u . w_gate) * (u . w_up)) . w_down
    *_moe: s = sigmoid(u . w_router)  (fp32, ``n_routed_experts`` wide)
           picks = the top ``num_experts_per_tok`` of s + expert_bias
                   (held, fp32; under ``stop_gradient``: it selects only)
           g_e = routed_scaling_factor s_e / (sum of the picked s + 1e-6)
           h2 = h1 + sum over the picks whose expert is HELD here of
                     g_e (silu(u . w_gate_e) * (u . w_up_e)) . w_down_e

The three groups are read where the in-projection left them, and the
convolution's backward writes dB | dCg | dX as the one array that
product's backward reads.

``kda_moe`` and ``gqa_gated_moe`` are the layers of a Kimi-Delta-
Attention / gated-attention expert model (``solar_open2``; KDA is Kimi
Linear's, arXiv:2510.26692): a layer of ``gqa_layers`` is softmax GQA
whose output is gated, the others a delta rule whose decay is a vector a
head behind a depthwise causal convolution, every one before an expert
layer BESIDE a shared expert, on ONE residual stream (H =
``kda_num_heads`` heads of K = V = ``kda_head_dim``):

    y  = rms_norm(h; ln1);   h1 = h + Mixer(y)
    u  = rms_norm(h1; ln2);  h2 = h1 + Experts(u)
    Experts(u) = sum over the picks whose expert is HELD here of
                   g_e (silu(u . w_gate_e) * (u . w_up_e)) . w_down_e
               + (silu(u . ws_gate) * (u . ws_up)) . ws_down       (shared)
      s = sigmoid(u . w_router)  (fp32);  picks = the top k of s
      g_e = routed_scaling_factor s_e / (sum of the picked s + 1e-20)
    gqa_gated_moe:
      q, k, v = y . wq [H, d], y . wk [KV, d], y . wv [KV, d];  z = y . wg
      a = softmax(q k^T / sqrt(d) over j <= i) v   (rotated only where the
                                                    configuration rotates)
      Mixer = (a * sigmoid(z)) . wo
    kda_moe:
      [q | k | v] = y . w_qkv                                      [3 H K]
      q, k, v = silu(conv(.))   depthwise, causal, ``short_conv_kernel_size``
                                taps [conv_q | conv_k | conv_v], zeros before
                                the row, no bias (ops/pallas/causal_conv.py)
      q = q / sqrt(sum_K q^2 + 1e-6) / sqrt(K);  k = k / sqrt(sum_K k^2 +
          1e-6), a head at a time                (inside ops/kda.kda_chunk)
      g = -exp(A_log_h) softplus((y . w_fa) . w_fb + dt_bias)   fp32 [s, H, K]
      beta = 2 sigmoid(y . w_beta)   (an eigenvalue of I - beta k k^T may
                                      reach -1)
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t          S [K, V] fp32 a head, S_0 = 0   (ops/kda.py)
      Mixer = (rms_norm(o_t; o_norm [V], a head at a time)
               * sigmoid((y . w_ga) . w_gb + gate_b)) . wo

q | k | v are read where the convolution left them and the recurrence's
backward writes dq | dk | dv as the one array the convolution's backward
reads.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

KINDS = ("attention", "mamba", "mla_dense", "mla_moe", "gqa_moe_global",
         "gqa_moe_window", "conv_dense", "conv_moe", "gqa_qknorm_moe",
         "kda_moe", "gqa_gated_moe")
MLA_KINDS = ("mla_dense", "mla_moe")
GQA_MOE_KINDS = ("gqa_moe_global", "gqa_moe_window")
CONV_KINDS = ("conv_dense", "conv_moe", "gqa_qknorm_moe")
KDA_KINDS = ("kda_moe", "gqa_gated_moe")
ROUTED_KINDS = ("mla_moe",) + GQA_MOE_KINDS + CONV_KINDS[1:] + KDA_KINDS


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
    if set(cfg.layer_types) & set(GQA_MOE_KINDS):
        _check_gqa_moe(cfg)
    if set(cfg.layer_types) & set(CONV_KINDS):
        _check_conv(cfg)
    if set(cfg.layer_types) & set(KDA_KINDS):
        _check_kda(cfg)
    if set(cfg.layer_types) & set(MLA_KINDS):
        if not set(cfg.layer_types) <= set(MLA_KINDS):
            raise NotImplementedError(
                f"layer_types {sorted(set(cfg.layer_types))}: the kinds "
                f"{MLA_KINDS} are one model's layers and mix with no "
                "other (at hc_mult >= 2 they carry hc_mult residual "
                "streams and a trunk has one carry; at hc_mult 1 the "
                "kept flash outputs are reckoned at one value width)")
        if min(cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim) < 1 \
                or cfg.q_lora_rank < 0 \
                or cfg.qk_nope_head_dim != cfg.v_head_dim \
                or cfg.v_head_dim % 128 or cfg.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention is built with kv_lora_rank > 0, "
                "q_lora_rank > 0 (a query behind its own latent and norm) "
                "or 0 (a direct query projection), qk_rope_head_dim even "
                "and qk_nope_head_dim = v_head_dim in whole lane tiles "
                "(flash_attention_split reads them where the projections "
                "wrote them)")
        if cfg.hc_mult < 1:
            raise ValueError(
                f"hc_mult={cfg.hc_mult}: the latent-attention kinds are "
                "built on ONE residual stream (hc_mult 1: x + F(norm(x))) "
                "or on hc_mult >= 2 streams behind their mixers")
        if "mla_moe" in cfg.layer_types and not (
                0 < cfg.num_experts_per_tok <= cfg.n_routed_experts
                and 0 < cfg.experts_held and cfg.n_shared_experts > 0
                and 0 <= cfg.expert_first
                and cfg.expert_first + cfg.experts_held
                <= cfg.n_routed_experts and cfg.moe_intermediate_size > 0):
            raise ValueError(
                "an 'mla_moe' layer needs n_routed_experts (the router's "
                "width), num_experts_per_tok, moe_intermediate_size, "
                "n_shared_experts, and the share: experts_held from "
                "expert_first on, inside the published count")


def _check_gqa_moe(cfg) -> None:
    """What the kinds ``gqa_moe_window`` / ``gqa_moe_global`` must
    state."""
    if not (0 < cfg.num_experts_per_tok <= cfg.n_routed_experts
            and 0 < cfg.experts_held and 0 <= cfg.expert_first
            and cfg.expert_first + cfg.experts_held <= cfg.n_routed_experts
            and cfg.moe_intermediate_size > 0):
        raise ValueError(
            "a 'gqa_moe_*' layer needs n_routed_experts (the router's "
            "width), num_experts_per_tok, moe_intermediate_size, and the "
            "share: experts_held from expert_first on, inside the "
            "published count")
    if not cfg.moe_primary_router_apply_softmax:
        raise NotImplementedError(
            "moe_primary_router_apply_softmax=False: the 'gqa_moe_*' "
            "router takes a softmax over the picked logits; a sigmoid of "
            "them is not built")
    if cfg.n_shared_experts or cfg.hc_mult != 1:
        raise NotImplementedError(
            f"n_shared_experts={cfg.n_shared_experts}, hc_mult="
            f"{cfg.hc_mult}: the 'gqa_moe_*' kinds have no shared expert "
            "and one residual stream")
    if "gqa_moe_window" in cfg.layer_types and cfg.sliding_window_size < 1:
        raise ValueError("a 'gqa_moe_window' layer needs "
                         "sliding_window_size")
    windows = tuple(int(k == "gqa_moe_window") for k in cfg.layer_types
                    if k in GQA_MOE_KINDS)
    for name in ("rope_layout", "sliding_window_layout"):
        stated = getattr(cfg, name)
        if stated is not None and tuple(stated) != windows:
            raise NotImplementedError(
                f"{name} {stated} against layer_types' windows {windows}: "
                "a window layer rotates and a global layer does not — a "
                "rotated global layer or an unrotated window layer is a "
                "kind the trunk lacks")


def conv_kinds(layer_types, num_dense_layers: int) -> Tuple[str, ...]:
    """The kinds of a published list of 'conv' / 'full_attention': the
    first ``num_dense_layers`` layers before the dense MLP, the rest
    before the expert layer."""
    def kind(i, t):
        if t == "conv":
            return "conv_dense" if i < num_dense_layers else "conv_moe"
        if t == "full_attention" and i >= num_dense_layers:
            return "gqa_qknorm_moe"
        raise NotImplementedError(
            f"layer {i} is {t!r}"
            f"{' before a dense MLP' if t == 'full_attention' else ''}: "
            "with conv_L_cache the trunk has a 'conv' layer before either "
            "MLP and a 'full_attention' layer before the expert layer")
    return tuple(kind(i, t) for i, t in enumerate(layer_types))


def _check_conv(cfg) -> None:
    """What the kinds ``conv_dense`` / ``conv_moe`` / ``gqa_qknorm_moe``
    must state."""
    if not set(cfg.layer_types) <= set(CONV_KINDS):
        raise NotImplementedError(
            f"layer_types {sorted(set(cfg.layer_types))}: the kinds "
            f"{CONV_KINDS} are one model's layers and mix with no other")
    if cfg.conv_L_cache < 1:
        raise ValueError("a 'conv_*' layer needs conv_L_cache, the taps")
    if cfg.n_shared_experts or cfg.hc_mult != 1:
        raise NotImplementedError(
            f"n_shared_experts={cfg.n_shared_experts}, hc_mult="
            f"{cfg.hc_mult}: the kinds {CONV_KINDS} have no shared expert "
            "and one residual stream")
    if not set(cfg.layer_types) & set(ROUTED_KINDS):
        return
    if not (0 < cfg.num_experts_per_tok <= cfg.n_routed_experts
            and 0 < cfg.experts_held and 0 <= cfg.expert_first
            and cfg.expert_first + cfg.experts_held <= cfg.n_routed_experts
            and cfg.moe_intermediate_size > 0):
        raise ValueError(
            "a 'conv_moe' / 'gqa_qknorm_moe' layer needs n_routed_experts "
            "(the router's width), num_experts_per_tok, "
            "moe_intermediate_size, and the share: experts_held from "
            "expert_first on, inside the published count")
    if not cfg.use_expert_bias:
        raise NotImplementedError(
            "use_expert_bias=False: the router of these kinds picks by "
            "its scores plus a held bias; without one it is ops/moe's "
            "rule 'sigmoid' at another epsilon, which no kind here takes")


def kda_kinds(gqa_layers, depth: int) -> Tuple[str, ...]:
    """The kinds of ``depth`` layers of which the published ``gqa_layers``
    are gated GQA and the others Kimi Delta Attention."""
    return tuple("gqa_gated_moe" if i in gqa_layers else "kda_moe"
                 for i in range(depth))


def _check_kda(cfg) -> None:
    """What the kinds ``kda_moe`` / ``gqa_gated_moe`` must state."""
    if not set(cfg.layer_types) <= set(KDA_KINDS):
        raise NotImplementedError(
            f"layer_types {sorted(set(cfg.layer_types))}: the kinds "
            f"{KDA_KINDS} are one model's layers and mix with no other")
    if cfg.hc_mult != 1:
        raise NotImplementedError(
            f"hc_mult={cfg.hc_mult}: the kinds {KDA_KINDS} have one "
            "residual stream")
    if not (0 < cfg.num_experts_per_tok <= cfg.n_routed_experts
            and 0 < cfg.experts_held and 0 <= cfg.expert_first
            and cfg.expert_first + cfg.experts_held <= cfg.n_routed_experts
            and cfg.moe_intermediate_size > 0):
        raise ValueError(
            "a 'kda_moe' / 'gqa_gated_moe' layer needs n_routed_experts "
            "(the router's width), num_experts_per_tok, "
            "moe_intermediate_size, and the share: experts_held from "
            "expert_first on, inside the published count")
    if "kda_moe" not in cfg.layer_types:
        return
    if min(cfg.kda_num_heads, cfg.kda_head_dim,
           cfg.short_conv_kernel_size) < 1:
        raise ValueError("a 'kda_moe' layer needs kda_num_heads, "
                         "kda_head_dim and short_conv_kernel_size")


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
            "sharding); 'mla_dense' / 'mla_moe' / 'gqa_moe_*' / 'conv_moe' "
            "/ 'gqa_qknorm_moe' / 'kda_moe' / 'gqa_gated_moe' no exchange "
            "of routed rows between the devices that share a layer's "
            "experts and no shard_map around flash_attention_split, the "
            "grouped products and the gated short convolution of "
            "'conv_dense' / 'conv_moe'; 'kda_moe' no head-parallel "
            "projections, no hand-over of its matrix state between "
            "sequence shards and no shard_map around the kda_chunk "
            "kernels; one device runs it")


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
    if kind in MLA_KINDS:
        return _mla_shapes(cfg, kind)
    dense = _block_shapes(cfg)
    if kind in CONV_KINDS:
        return _conv_shapes(cfg, kind, dense)
    if kind in KDA_KINDS:
        return _kda_shapes(cfg, kind, dense)
    if kind in GQA_MOE_KINDS:
        c, f, e = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.experts_held)
        out = {nm: dense[nm] for nm in ("ln1", "wq", "wk", "wv", "wo",
                                        "ln2")}
        out.update({"w_router": (c, cfg.n_routed_experts),
                    "we_gate_up": (e, c, 2 * f), "we_down": (e, f, c)})
        return out
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


def _conv_shapes(cfg, kind: str, dense) -> Dict[str, Tuple[int, ...]]:
    c, d = cfg.hidden_size, cfg.head_dim
    if kind == "gqa_qknorm_moe":
        out = {nm: dense[nm] for nm in ("ln1", "wq", "wk", "wv", "wo")}
        out.update({"q_layernorm": (d,), "k_layernorm": (d,)})
    else:
        out = {"ln1": (c,), "w_in": (c, 3 * c),
               "conv_w": (c, cfg.conv_L_cache), "w_out": (c, c)}
    out["ln2"] = (c,)
    if kind == "conv_dense":
        out.update({nm: dense[nm] for nm in ("w_gate", "w_up", "w_down")})
        return out
    f, e = cfg.moe_intermediate_size, cfg.experts_held
    out.update({"w_router": (c, cfg.n_routed_experts),
                "expert_bias": (cfg.n_routed_experts,),
                "we_gate_up": (e, c, 2 * f), "we_down": (e, f, c)})
    return out


def _kda_shapes(cfg, kind: str, dense) -> Dict[str, Tuple[int, ...]]:
    c, heads, d = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    if kind == "gqa_gated_moe":
        out = {nm: dense[nm] for nm in ("ln1", "wq", "wk", "wv")}
        out["wg"], out["wo"] = dense["wq"], dense["wo"]
    else:
        wide, taps = heads * d, cfg.short_conv_kernel_size
        out = {"ln1": (c,), "w_qkv": (c, 3 * wide),
               "conv_q": (wide, taps), "conv_k": (wide, taps),
               "conv_v": (wide, taps),
               "w_fa": (c, d), "w_fb": (d, wide), "A_log": (heads,),
               "dt_bias": (wide,), "w_beta": (c, heads),
               "w_ga": (c, d), "w_gb": (d, wide), "gate_b": (wide,),
               "o_norm": (d,), "wo": (wide, c)}
    f, e = cfg.moe_intermediate_size, cfg.experts_held
    fs = f * cfg.n_shared_experts
    out.update({"ln2": (c,), "w_router": (c, cfg.n_routed_experts),
                "we_gate_up": (e, c, 2 * f), "we_down": (e, f, c)})
    if fs:
        out.update({"ws_gate": (c, fs), "ws_up": (c, fs),
                    "ws_down": (fs, c)})
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
        nm: dense[nm] if nm in ("w_gate", "w_up", "w_down")
        and kind == "mamba" else whole(shape)
        for nm, shape in kind_shapes(cfg, kind).items()}
        for kind in dict.fromkeys(cfg.layer_types)}


# The seeded ``expert_bias``: of the order of the gap between a token's
# fourth and fifth sigmoid score of 64 (logits of unit size: ~0.016 in
# the mean), so that a measurable share of the picks is the bias's doing
# — and no larger, because a bias moves the LOAD: one expert's picks
# change by ~29 % a 0.02 of bias, so at std 0.02 the pairs a chip's 16
# experts keep spread by +-5.5 % from seed to seed (and its step time
# with them: PERF.md section 6, PR 48), at 0.005 by +-1.6 %, at none by
# +-0.9 %.
EXPERT_BIAS_STD = 0.005


def init_leaf(cfg, key, kind: str, name: str, layers: int, dtype=None):
    """``layers`` layers of one leaf, stacked.  Matrices normal at
    1/sqrt(hidden) (the latent-attention and ``gqa_moe_*`` kinds': at
    1/sqrt(the width the
    matrix contracts), the mixers' alpha ones and b zeros, so that m =
    u . phi is of unit size and H_res differs from token to token), norms
    and D ones, the convolution uniform in +-1 /
    sqrt(d_conv), ``A_log = log U[1, 16]`` and ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1] (the Mamba-2
    reference's: decays neither 0 nor 1; the delta-rule kind's ``A_log``
    [heads] and ``dt_bias`` [heads x head_dim] by the same rule: a channel
    decays by between ~0.2 and ~0.999 a step; its ``gate_b`` zeros).  The
    short-convolution and delta-rule kinds': the taps normal at 1/sqrt(the
    taps), so that the convolution's
    result is of its input's size, and ``expert_bias`` normal at
    ``EXPERT_BIAS_STD`` — seeded NON-ZERO, since the rule that would move
    it off zero (the balance update) is not built."""
    shape = (layers,) + kind_shapes(cfg, kind)[name]
    f32 = jnp.float32
    if name in ("ln1", "ln2", "gate_norm", "D", "q_norm", "kv_norm",
                "q_layernorm", "k_layernorm", "o_norm") \
            or name.endswith("_alpha"):
        out = jnp.ones(shape, f32)
    elif name.endswith("_b"):
        out = jnp.zeros(shape, f32)
    elif name == "expert_bias":
        out = jax.random.normal(key, shape, f32) * EXPERT_BIAS_STD
    elif name in ("conv_w", "conv_q", "conv_k", "conv_v") \
            and kind in CONV_KINDS + KDA_KINDS:
        out = jax.random.normal(key, shape, f32) / math.sqrt(shape[-1])
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
        # a matrix: normal at 1/sqrt(its rows), the width it contracts
        out = jax.random.normal(key, shape, f32) / math.sqrt(
            shape[-2] if kind in MLA_KINDS + GQA_MOE_KINDS + CONV_KINDS
            + KDA_KINDS else cfg.hidden_size)
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
# latent attention, the expert layer, the residual streams
# ---------------------------------------------------------------------------
def _mla_shapes(cfg, kind: str) -> Dict[str, Tuple[int, ...]]:
    c, n, heads = cfg.hidden_size, cfg.hc_mult, cfg.num_attention_heads
    maps = n * n + 2 * n
    nope, rope = heads * cfg.qk_nope_head_dim, heads * cfg.qk_rope_head_dim
    # the query's two leaves, nope | rope columns apart, behind a latent
    # or straight from the stream: ``flash_attention_split`` reads whole
    # lane tiles where either projection wrote them
    query = {"w_qa": (c, cfg.q_lora_rank), "q_norm": (cfg.q_lora_rank,),
             "w_qb_nope": (cfg.q_lora_rank, nope),
             "w_qb_rope": (cfg.q_lora_rank, rope)} if cfg.q_lora_rank \
        else {"w_q_nope": (c, nope), "w_q_rope": (c, rope)}
    out = {"ln1": (c,), **query,
           "w_kva": (c, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
           "kv_norm": (cfg.kv_lora_rank,),
           "w_kvb_k": (cfg.kv_lora_rank, nope),
           "w_kvb_v": (cfg.kv_lora_rank, heads * cfg.v_head_dim),
           "wo": (heads * cfg.v_head_dim, c), "ln2": (c,)}
    for pre in ("hc1", "hc2") if n > 1 else ():
        out.update({pre + "_phi": (n * c, maps), pre + "_alpha": (3,),
                    pre + "_b": (maps,)})
    if kind == "mla_dense":
        f = cfg.intermediate_size
        out.update({"w_gate": (c, f), "w_up": (c, f), "w_down": (f, c)})
        return out
    f, e = cfg.moe_intermediate_size, cfg.experts_held
    fs = f * cfg.n_shared_experts
    out.update({"w_router": (c, cfg.n_routed_experts),
                "we_gate_up": (e, c, 2 * f), "we_down": (e, f, c),
                "ws_gate": (c, fs), "ws_up": (c, fs), "ws_down": (fs, c)})
    return out


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """The ``dim / 2`` rotation frequencies: plain RoPE's, or (``type:
    yarn``) each blended between its own and its ``factor``-th by a ramp
    over the pairs whose wavelength, at the original context, makes
    between ``beta_slow`` and ``beta_fast`` turns.  A static table."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return inv.astype(np.float32)
    if scaling["type"] != "yarn":
        raise NotImplementedError(f"rope_scaling {scaling}: yarn is built")
    orig = scaling["original_max_position_embeddings"]

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return (inv / scaling["factor"] * ramp + inv * (1 - ramp)).astype(
        np.float32)


def yarn_mscale(scaling, key: str) -> float:
    """YaRN's attention-temperature term for ``mscale`` or
    ``mscale_all_dim``: ``0.1 m ln(factor) + 1``."""
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling.get(key, 1) * math.log(scaling["factor"]) + 1.0


def mla_score_scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 \
        * yarn_mscale(cfg.rope_scaling, "mscale_all_dim") ** 2


def _rotate_half(x, cos, sin):
    """x [b, s, (heads,) d] at positions 0..s-1, in fp32, one rounding."""
    from ..ops.pallas.rope import _rotate
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    return _rotate(x.astype(jnp.float32), cos, sin).astype(x.dtype)


def _mla_attention(bp, x, cfg):
    from ..ops.pallas.flash_attention import flash_attention_split
    from .llama_pretrain import _rms_norm
    b, s, _ = x.shape
    dt, eps, heads = cfg.dtype, cfg.rms_norm_eps, cfg.num_attention_heads
    rope = cfg.qk_rope_head_dim
    with jax.named_scope("mla_q"):
        if "w_qa" in bp:
            qa = _rms_norm(x @ bp["w_qa"].astype(dt), bp["q_norm"], eps)
            w_nope, w_rope = bp["w_qb_nope"], bp["w_qb_rope"]
        else:
            qa, w_nope, w_rope = x, bp["w_q_nope"], bp["w_q_rope"]
        q = (qa @ w_nope.astype(dt)).reshape(b, s, heads, -1)
        q_r = (qa @ w_rope.astype(dt)).reshape(b, s, heads, rope)
    with jax.named_scope("mla_kv"):
        ckr = x @ bp["w_kva"].astype(dt)
        c = _rms_norm(ckr[..., :cfg.kv_lora_rank], bp["kv_norm"], eps)
        k_r = ckr[..., cfg.kv_lora_rank:]
        k = (c @ bp["w_kvb_k"].astype(dt)).reshape(b, s, heads, -1)
        v = (c @ bp["w_kvb_v"].astype(dt)).reshape(b, s, heads, -1)
    with jax.named_scope("rope"):
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(
            rope, cfg.rope_theta, cfg.rope_scaling)
        m = yarn_mscale(cfg.rope_scaling, "mscale") \
            / yarn_mscale(cfg.rope_scaling, "mscale_all_dim")
        cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
        q_r, k_r = _rotate_half(q_r, cos, sin), _rotate_half(k_r, cos, sin)
    with jax.named_scope("attn"):
        o = flash_attention_split(q, q_r, k, k_r, v, mla_score_scale(cfg))
    with jax.named_scope("attn_out"):
        return o.reshape(b, s, -1) @ bp["wo"].astype(dt)


def _routing(bp, x, cfg, rule: str = "sigmoid"):
    """The picks' gates and their plan from the ROUTER's input x [b, s,
    C]: the experts' own input, or whatever else a kind routes on."""
    from ..ops import moe
    rows = x.reshape(-1, x.shape[-1])
    with jax.named_scope("moe_route"):
        idx, gate = moe.route(rows, bp["w_router"], cfg.num_experts_per_tok,
                              cfg.routed_scaling_factor, rule,
                              bp.get("expert_bias"))
    with jax.named_scope("moe_dispatch"):
        return gate, moe.plan(idx, cfg.expert_first, cfg.experts_held,
                              cfg.n_routed_experts)


def _expert_layer(bp, x, cfg, routing=None, act: str = "silu"):
    """The routed experts held here, for the pairs routed to them, beside
    the shared experts where the layer has them: x [b, s, C] (normed) ->
    [b, s, C].  ``routing``: what :func:`_routing` gave for another input
    than x (None: x routes itself).  ``bp["we_stacks"]`` and
    ``bp["we_layer"]`` (the trunk's loops hand them on): the kind's
    whole ``we_gate_up`` / ``we_down`` and this layer's index in them,
    which the grouped products read in place of the layer's own two
    leaves."""
    from ..ops import moe
    from .llama_pretrain import _swiglu
    b, s, c = x.shape
    gate, p = routing or _routing(bp, x, cfg)
    routed = moe.routed_ffn(x.reshape(b * s, c), gate, bp["we_gate_up"],
                            bp["we_down"], p, act,
                            bp["we_stacks"] + (bp["we_layer"],)
                            if "we_stacks" in bp else None).reshape(b, s, c)
    if "ws_gate" not in bp:
        return routed
    with jax.named_scope("moe_shared"):
        return routed + _swiglu(x, bp["ws_gate"], bp["ws_up"],
                                bp["ws_down"], cfg.dtype)


def _gqa_moe_block(bp, x, cfg, mesh=None, seg=None, *, window: bool):
    """One ``gqa_moe_window`` / ``gqa_moe_global`` layer on x [b, s, C]
    (the module docstring has the equations)."""
    from .llama_pretrain import _attention, _qkv, _residual, _rms_norm
    b, s, _ = x.shape
    with jax.named_scope("block"):
        with jax.named_scope("attn_qkv"):
            y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
        # before attention, whose input it shares: nothing below until
        # the experts reads it
        routing = _routing(bp, y, cfg, "softmax_of_picks")
        q, k, v = _qkv(bp, y, cfg, mesh, rotate=window)
        with jax.named_scope("attn"):
            attn = _attention(q, k, v, cfg, mesh, seg,
                              cfg.sliding_window_size if window else None)
        with jax.named_scope("attn_out"):
            x = _residual(x, attn.reshape(b, s, -1)
                          @ bp["wo"].astype(cfg.dtype), cfg)
        u = _rms_norm(x, bp["ln2"], cfg.rms_norm_eps)
        return _residual(x, _expert_layer(bp, u, cfg, routing, "relu"), cfg)


def _short_conv(bp, y, cfg):
    """The convolution operator on y [b, s, C] (normed) -> [b, s, C]."""
    from ..ops.pallas import causal_conv
    dt = cfg.dtype
    with jax.named_scope("conv_in_proj"):
        bcx = y @ bp["w_in"].astype(dt)
        kernel = causal_conv.takes_gated(bcx, bp["conv_w"])
        if kernel:
            # a kernel takes an array row-major: the product is to leave
            # it so
            bcx = with_layout_constraint(
                bcx, Layout(major_to_minor=(0, 1, 2)))
    with jax.named_scope("short_conv"):
        op = causal_conv.short_conv_gated if kernel \
            else causal_conv.short_conv_gated_xla
        v = op(bcx, bp["conv_w"])
    with jax.named_scope("conv_out_proj"):
        return v @ bp["w_out"].astype(dt)


def _conv_block(bp, x, cfg, mesh=None, seg=None):
    """One ``conv_dense`` / ``conv_moe`` / ``gqa_qknorm_moe`` layer on x
    [b, s, C] (the module docstring has the equations): which operator
    and which MLP follow from the leaves the layer holds."""
    from .llama_pretrain import (_attention, _ffn, _qkv, _residual,
                                 _rms_norm)
    b, s, _ = x.shape
    with jax.named_scope("block"):
        if "w_in" in bp:
            y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
            x = _residual(x, _short_conv(bp, y, cfg), cfg)
        else:
            with jax.named_scope("attn_qkv"):
                y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
            q, k, v = _qkv(bp, y, cfg, mesh, rotate=True)
            with jax.named_scope("attn"):
                attn = _attention(q, k, v, cfg, mesh, seg)
            with jax.named_scope("attn_out"):
                x = _residual(x, attn.reshape(b, s, -1)
                              @ bp["wo"].astype(cfg.dtype), cfg)
        if "w_router" not in bp:
            with jax.named_scope("mlp"):
                return _ffn(bp, x, cfg)
        u = _rms_norm(x, bp["ln2"], cfg.rms_norm_eps)
        return _residual(x, _expert_layer(
            bp, u, cfg, _routing(bp, u, cfg, "sigmoid_biased_picks")), cfg)


def _kda_mixer(bp, y, cfg):
    """Kimi Delta Attention on y [b, s, C] (normed) -> [b, s, C]."""
    from ..ops import kda
    from ..ops.pallas import causal_conv
    from .llama_pretrain import _rms_norm
    b, s, _ = y.shape
    dt, f32 = cfg.dtype, jnp.float32
    heads, d = cfg.kda_num_heads, cfg.kda_head_dim
    # a layer's two wide matrices are cast where they are used: XLA would
    # otherwise cast the kind's whole fp32 stacks once, ahead of the loop
    # over the layers, and hold the copies (0.77 GB at three layers of
    # 4096 x 24,576 and 8192 x 4096) through the step
    w_qkv, wo = jax.lax.optimization_barrier((bp["w_qkv"], bp["wo"]))
    with jax.named_scope("kda_in_proj"):
        qkv = y @ w_qkv.astype(dt)
        taps = jnp.concatenate([bp["conv_q"], bp["conv_k"], bp["conv_v"]])
        kernel = causal_conv.takes(qkv, taps)
        if kernel:
            # a kernel takes an array row-major: the product is to leave
            # it so
            qkv = with_layout_constraint(
                qkv, Layout(major_to_minor=(0, 1, 2)))
        beta_in = jnp.dot(y, bp["w_beta"].astype(dt),
                          preferred_element_type=f32)

    # The two low-rank maps give ``[s, H K]`` arrays from ``[s,
    # kda_head_dim]`` factors by a 128-deep product: each is formed
    # inside a checkpoint of its own with what reads it, so that the
    # backward pass forms it again and holds no fp32 ``[s, H K]`` array
    # for it (beside g itself, which the recurrence's backward reads).
    @jax.checkpoint
    def log_decay(y, w_fa, w_fb, a_log, dt_bias):
        with jax.named_scope("kda_in_proj"):
            # fp32 out: a rounding of the softplus's input is a relative
            # error of the log decay
            x = jnp.dot(y @ w_fa.astype(dt), w_fb.astype(dt),
                        preferred_element_type=f32)
        with jax.named_scope("kda_gates"):
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                x.reshape(b, s, heads, d)
                + dt_bias.astype(f32).reshape(heads, d))
            return g.reshape(b, s, heads * d)

    @jax.checkpoint
    def out_gate(o, y, w_ga, w_gb, gate_b, o_norm):
        with jax.named_scope("kda_in_proj"):
            x = (y @ w_ga.astype(dt)) @ w_gb.astype(dt)
        with jax.named_scope("kda_out_gate"):
            gate = jax.nn.sigmoid(x.astype(f32) + gate_b.astype(f32))
            return _rms_norm(o.reshape(b, s, heads, d), o_norm,
                             cfg.rms_norm_eps).reshape(b, s, heads * d) \
                * gate.astype(dt)
    with jax.named_scope("kda_conv"):
        # the state-space mixer's form, silu(bias + conv(x)), at a zero
        # bias
        conv = causal_conv.causal_conv_silu if kernel \
            else causal_conv.causal_conv_silu_xla
        qkv = conv(qkv, taps, jnp.zeros(taps.shape[:1], f32))
    g = log_decay(y, bp["w_fa"], bp["w_fb"], bp["A_log"], bp["dt_bias"])
    with jax.named_scope("kda_gates"):
        beta = 2.0 * jax.nn.sigmoid(beta_in)
    with jax.named_scope("kda_chunk"):
        o = kda.kda_chunk(qkv, g, beta, heads)
    o = out_gate(o, y, bp["w_ga"], bp["w_gb"], bp["gate_b"], bp["o_norm"])
    with jax.named_scope("kda_out_proj"):
        return o @ wo.astype(dt)


def _kda_block(bp, x, cfg, mesh=None, seg=None):
    """One ``kda_moe`` / ``gqa_gated_moe`` layer on x [b, s, C] (the
    module docstring has the equations): which mixer follows from the
    leaves the layer holds."""
    from .llama_pretrain import _attention, _qkv, _residual, _rms_norm
    b, s, _ = x.shape
    with jax.named_scope("block"):
        if "w_qkv" in bp:
            y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
            x = _residual(x, _kda_mixer(bp, y, cfg), cfg)
        else:
            with jax.named_scope("attn_qkv"):
                y = _rms_norm(x, bp["ln1"], cfg.rms_norm_eps)
            q, k, v = _qkv(bp, y, cfg, mesh,
                           rotate=cfg.position_embedding_type == "rope")
            with jax.named_scope("attn"):
                attn = _attention(q, k, v, cfg, mesh, seg).reshape(b, s, -1)
            with jax.named_scope("attn_gate"):
                z = y @ bp["wg"].astype(cfg.dtype)
                attn = attn * jax.nn.sigmoid(
                    z.astype(jnp.float32)).astype(cfg.dtype)
            with jax.named_scope("attn_out"):
                x = _residual(x, attn @ bp["wo"].astype(cfg.dtype), cfg)
        u = _rms_norm(x, bp["ln2"], cfg.rms_norm_eps)
        return _residual(x, _expert_layer(bp, u, cfg), cfg)


class _Mixer(NamedTuple):
    """What of the configuration a mixer's maps read."""
    n: int
    eps: float
    iters: int
    hc_eps: float
    lo: float
    hi: float


def _mixer_of(cfg) -> _Mixer:
    return _Mixer(cfg.hc_mult, cfg.rms_norm_eps, cfg.hc_sinkhorn_iters,
                  cfg.hc_eps, cfg.mhc_h_res_clamp_min,
                  cfg.mhc_h_res_clamp_max)


def _hc_small_maps(m, alpha, bias, mx: _Mixer):
    """``H_post [n, T]`` and ``H_res [n, n, T]`` (row j: what stream j of
    the output takes of each input stream) from m ``[n^2 + 2 n, T]``:
    fp32, the TOKENS on the lanes — a few numbers a token, XLA's in
    both forms of the sublayer and under jax's own autodiff."""
    n, f32 = mx.n, jnp.float32
    alpha, bias = alpha.astype(f32), bias.astype(f32)[:, None]
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
    r = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, -1)
    r = jnp.exp(jnp.clip(r, mx.lo, mx.hi))
    for _ in range(mx.iters):
        r = r / (jnp.sum(r, axis=1, keepdims=True) + mx.hc_eps)     # rows
        r = r / (jnp.sum(r, axis=0, keepdims=True) + mx.hc_eps)     # columns
    return h_post, r


def hc_maps(bp, pre: str, x, cfg):
    """The three maps of one sublayer's mixer from the streams x [b, s,
    n C]: ``H_pre [n]``, ``H_post [n]`` and ``H_res [n][n]``, every entry
    fp32 ``[b, s, 1]``.  The product with phi runs on the streams as they
    are (the compute dtype, fp32 sums) and is scaled by 1 / rms
    afterwards; the rest is fp32, with the TOKENS on the lanes."""
    n, f32 = cfg.hc_mult, jnp.float32
    b, s, _ = x.shape
    var = jnp.mean(jnp.square(x.astype(f32)), -1, keepdims=True)
    m = jnp.einsum("bsk,kj->bsj", x, bp[pre + "_phi"].astype(x.dtype),
                   preferred_element_type=f32) \
        * jax.lax.rsqrt(var + cfg.rms_norm_eps)
    m = m.reshape(b * s, -1).T                              # [n^2 + 2n, T]
    alpha, bias = bp[pre + "_alpha"], bp[pre + "_b"]
    h_pre = jax.nn.sigmoid(alpha[0].astype(f32) * m[:n]
                           + bias.astype(f32)[:n, None])
    h_post, r = _hc_small_maps(m, alpha, bias, _mixer_of(cfg))
    tok = lambda a: a.reshape(b, s, 1)
    return ([tok(h_pre[i]) for i in range(n)],
            [tok(h_post[i]) for i in range(n)],
            [[tok(r[j, i]) for i in range(n)] for j in range(n)])


def _hc_maps_lanes(mr, alpha, bias, mx: _Mixer):
    """``ops/pallas/hc_mix``'s view of the small maps: from mr ``[T,
    128]`` (m in the first lanes) to maps ``[T, 128]`` — H_res row-major
    in the first n^2 lanes, H_post in the n after, the tokens on the
    sublanes so that a map is a column."""
    from ..ops.pallas.hc_mix import LANES
    n = mx.n
    # a transposition that XLA may write as a layout leaves the TOKENS on
    # the sublanes, a lane in eight in use, through all of Sinkhorn's
    # rounds and their backward (a cotangent takes the same constraint):
    # both arrays are to lie row-major
    on_lanes = lambda a: with_layout_constraint(
        a, Layout(major_to_minor=(0, 1)))
    h_post, r = _hc_small_maps(on_lanes(mr[:, :n * n + 2 * n].T), alpha,
                               bias, mx)
    maps = on_lanes(jnp.concatenate([r.reshape(n * n, -1), h_post])).T
    return jnp.pad(maps, ((0, 0), (0, LANES - n * n - n)))


# The two halves of a sublayer on the kernels of ``ops/pallas/hc_mix``.
# F's backward lies between the halves' backwards, and both need dX':
# ``_hc_post`` hands its x the cotangent of its OUTPUT, dX' as it came,
# and ``_hc_pre`` — which returns x for that purpose and holds H_res —
# takes it through H_res while it forms the whole of dX in one pass.
# Together they are the derivative; the x that ``_hc_pre`` returns goes
# to ``_hc_post`` and nowhere else.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _hc_pre(x, phi, alpha, bias, mx: _Mixer):
    """x [T, n C] -> h [T, C] = H_pre . x, the maps ``_hc_post`` reads
    [T, 128], and x."""
    return _hc_pre_fwd(x, phi, alpha, bias, mx)[0]


def _hc_pre_fwd(x, phi, alpha, bias, mx):
    from ..ops.pallas import hc_mix
    with jax.named_scope("hc_pre"):
        h, mr = hc_mix.hc_pre_fwd(x, phi, alpha[0], bias, mx.n, mx.eps)
        maps, pull = jax.vjp(
            functools.partial(_hc_maps_lanes, mx=mx), mr, alpha, bias)
    return (h, maps, x), (x, phi, alpha, bias, mr, maps, pull)


def _hc_pre_bwd(mx, res, cts):
    from ..ops.pallas import hc_mix
    x, phi, alpha, bias, mr, maps, pull = res
    dh, dmaps, g = cts
    n, f32 = mx.n, jnp.float32
    with jax.named_scope("hc_pre"):
        dmr, dalpha, dbias = pull(dmaps)
        dx, dz, dphit = hc_mix.hc_pre_bwd(g, x, dh, mr, dmr, maps, phi,
                                          alpha[0], bias, n)
        dz = dz[:, :n]
        dalpha = dalpha.astype(f32).at[0].add(jnp.sum(dz * mr[:, :n]))
        dbias = dbias.astype(f32).at[:n].add(jnp.sum(dz, axis=0))
        dphi = dphit[:phi.shape[1]].T
    return (dx, dphi.astype(phi.dtype), dalpha.astype(alpha.dtype),
            dbias.astype(bias.dtype))


_hc_pre.defvjp(_hc_pre_fwd, _hc_pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _hc_post(x, y, maps, n: int):
    """x' [T, n C] = H_res . x + H_post^T (x) y."""
    return _hc_post_fwd(x, y, maps, n)[0]


def _hc_post_fwd(x, y, maps, n):
    from ..ops.pallas import hc_mix
    with jax.named_scope("hc_post"):
        return hc_mix.hc_post_fwd(x, y, maps, n), (x, y, maps)


def _hc_post_bwd(n, res, g):
    from ..ops.pallas import hc_mix
    x, y, maps = res
    with jax.named_scope("hc_post"):
        dy, dmaps = hc_mix.hc_post_bwd(g, x, y, maps, n)
    return g, dy, dmaps


_hc_post.defvjp(_hc_post_fwd, _hc_post_bwd)


def _hc_sublayer(bp, pre: str, x, fn, cfg):
    """x' = H_res . x + H_post^T (x) fn(H_pre . x) on x [b, s, n C]: on
    the kernels where they take the shape, else as XLA's fusions."""
    from ..ops.pallas import hc_mix
    n, c, f32 = cfg.hc_mult, cfg.hidden_size, jnp.float32
    b, s, _ = x.shape
    if hc_mix.takes(x, n, c):
        h, maps, rows = _hc_pre(
            x.reshape(b * s, n * c), bp[pre + "_phi"], bp[pre + "_alpha"],
            bp[pre + "_b"], _mixer_of(cfg))
        y = fn(h.reshape(b, s, c)).reshape(b * s, c).astype(x.dtype)
        return _hc_post(rows, y, maps, n).reshape(x.shape)
    with jax.named_scope("hc_pre"):
        h_pre, h_post, h_res = hc_maps(bp, pre, x, cfg)
        streams = [x[..., i * c:(i + 1) * c].astype(f32) for i in range(n)]
        h = sum(w * xi for w, xi in zip(h_pre, streams)).astype(x.dtype)
    y = fn(h)
    with jax.named_scope("hc_post"):
        y = y.astype(f32)
        return jnp.concatenate(
            [(sum(w * xi for w, xi in zip(h_res[j], streams))
              + h_post[j] * y).astype(x.dtype) for j in range(n)], axis=-1)


def _mla_block(bp, x, cfg, mesh=None, seg=None):
    """One ``mla_dense`` / ``mla_moe`` layer (the module docstring has
    the equations): on x [b, s, C] each sublayer is added to the one
    stream; on x [b, s, n C] it runs between its mixer's two halves."""
    from .llama_pretrain import _residual, _rms_norm, _swiglu
    eps = cfg.rms_norm_eps

    def attn(h):
        return _mla_attention(bp, _rms_norm(h, bp["ln1"], eps), cfg)

    def ffn(h):
        y = _rms_norm(h, bp["ln2"], eps)
        if "w_router" in bp:
            return _expert_layer(bp, y, cfg)
        with jax.named_scope("mlp"):
            return _swiglu(y, bp["w_gate"], bp["w_up"], bp["w_down"],
                           cfg.dtype)
    with jax.named_scope("block"):
        if cfg.hc_mult == 1:
            x = _residual(x, attn(x), cfg)
            return _residual(x, ffn(x), cfg)
        x = _hc_sublayer(bp, "hc1", x, attn, cfg)
        return _hc_sublayer(bp, "hc2", x, ffn, cfg)


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


def flash_kinds(cfg) -> Tuple[str, ...]:
    """The kinds of ``cfg``'s trunk whose blocks call the flash kernels
    (``check``: latent attention does not mix with the others)."""
    if cfg.layer_types[0] in MLA_KINDS:
        return MLA_KINDS
    return ("attention", "gqa_qknorm_moe", "gqa_gated_moe") + GQA_MOE_KINDS


def kept_outputs(cfg, batch: int, seq: int) -> Tuple[bool, bool]:
    """What full remat keeps of the trunk's forward kernels' results for
    ``batch`` rows of ``seq``, from shapes alone: (``flash_fwd``'s,
    ``kda_chunk_fwd``'s).  ONE budget a trunk,
    ``llama_pretrain.KEPT_BYTES``: the flash layers' ``o`` and ``lse``
    are reckoned first, against all of it; the ``kda_moe`` layers'
    ``o`` and entering states (``kda_chunk.kept_bytes``) are kept where
    they fit beside what flash keeps — and where the kernels take the
    shapes: ``kda_chunked_xla`` names nothing."""
    from ..ops import kda
    from ..ops.pallas import kda_chunk
    from .llama_pretrain import (KEPT_BYTES, flash_output_bytes,
                                 keeps_flash_outputs)
    mla = cfg.layer_types[0] in MLA_KINDS
    flash = (batch, seq, cfg.num_attention_heads,
             cfg.v_head_dim if mla else cfg.head_dim, cfg.dtype,
             sum(kind in flash_kinds(cfg) for kind in cfg.layer_types))
    keep_flash = keeps_flash_outputs(*flash)
    layers = cfg.layer_types.count("kda_moe")
    if not layers or not kda_chunk.takes(jax.ShapeDtypeStruct(
            (batch, seq, 3 * cfg.kda_num_heads * cfg.kda_head_dim),
            cfg.dtype), cfg.kda_num_heads, kda.CHUNK):
        return keep_flash, False
    held = flash_output_bytes(*flash) if keep_flash else 0
    return keep_flash, held + layers * kda_chunk.kept_bytes(
        batch, seq, cfg.kda_num_heads, cfg.dtype, kda.CHUNK) <= KEPT_BYTES


def trunk(blocks, x, cfg, mesh):
    """x [b, s, h] through the layers in ``cfg.layer_types``' order.
    At ``hc_mult`` >= 2 the latent-attention kinds carry that many
    streams, ``[b, s, n h]``: the row that comes in is copied into each,
    and their sum goes out."""
    from .llama_pretrain import _block_forward, _remat_wrap
    body = {"attention": _block_forward, "mamba": _mamba_block,
            "mla_dense": _mla_block, "mla_moe": _mla_block,
            "gqa_moe_global": functools.partial(_gqa_moe_block,
                                                window=False),
            "gqa_moe_window": functools.partial(_gqa_moe_block,
                                                window=True),
            "conv_dense": _conv_block, "conv_moe": _conv_block,
            "gqa_qknorm_moe": _conv_block,
            "kda_moe": _kda_block, "gqa_gated_moe": _kda_block}
    mla = cfg.layer_types[0] in MLA_KINDS
    keep_flash, keep_kda = kept_outputs(cfg, x.shape[0], x.shape[1])
    flash = flash_kinds(cfg)
    runs = layer_runs(cfg.layer_types)
    streams = cfg.hc_mult if mla else 1
    if streams > 1:
        with jax.named_scope("hc_pre"):
            # whole lane tiles side by side: a copy.  ``jnp.tile`` goes
            # through [b, s, n, C], which XLA lays out for the kernels
            # that take x by moving every stream twice
            x = jnp.concatenate([x] * streams, axis=-1)

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
        for kind, a, b in runs:
            fwd = _remat_wrap(body[kind], cfg,
                              keep_flash and kind in flash,
                              kind in ROUTED_KINDS,
                              keep_kda and kind == "kda_moe")
            layers, whole = parts[kind].pop(0), {}
            if kind in ROUTED_KINDS:
                # the grouped products read a layer's experts out of the
                # kind's WHOLE stacks, at the layer's index in them
                # (``ops/moe.routed_ffn``): the loop's own slices of the
                # two leaves — and a run's piece of them — take the
                # cotangents and are read by no pass
                whole = {"we_stacks": tuple(
                    jax.lax.stop_gradient(blocks[kind][leaf])
                    for leaf in ("we_gate_up", "we_down"))}
                layers = dict(layers,
                              we_layer=jnp.arange(a, b, dtype=jnp.int32))
            x, _ = jax.lax.scan(
                lambda carry, bp, fwd=fwd, whole=whole: (
                    fwd({**bp, **whole}, carry, cfg, mesh, None), None),
                x, layers)
    if streams > 1:
        with jax.named_scope("hc_post"):
            c = x.shape[-1] // streams
            x = sum(x[..., i * c:(i + 1) * c].astype(jnp.float32)
                    for i in range(streams)).astype(x.dtype)
    return x
