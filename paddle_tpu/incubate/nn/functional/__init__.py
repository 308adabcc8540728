"""Fused functional ops (reference: python/paddle/incubate/nn/functional/).

Each is written as one fusable XLA expression (or a Pallas kernel via the
op table) — the TPU analog of the reference's hand-written CUDA fusions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ....ops.dispatch import apply, as_tensor, get_op_impl
from ....tensor.tensor import Tensor
from ....tensor.math import add
from ....nn import functional as F

__all__ = ["fused_rms_norm", "fused_layer_norm",
           "fused_rotary_position_embedding", "swiglu",
           "fused_bias_act", "fused_linear",
           "fused_linear_activation", "fused_dropout_add",
           "fused_multi_head_attention", "masked_multihead_attention",
           "fused_feedforward", "fused_matmul_bias",
           "fused_bias_dropout_residual_layer_norm", "fused_ec_moe",
           "fused_multi_transformer",
           "variable_length_memory_efficient_attention",
           "blha_get_max_len", "block_multihead_attention"]


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kw):
    from ....nn.functional import rms_norm
    out = rms_norm(x, norm_weight, epsilon=epsilon)
    if norm_bias is not None:
        from ....tensor.math import add
        out = add(out, norm_bias)
    return (out,)


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=1, **kw):
    from ....nn.functional import layer_norm
    shape = list(x.shape[begin_norm_axis:])
    return (layer_norm(x, shape, norm_weight, norm_bias, epsilon),)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000):
    """Reference: incubate fused_rotary_position_embedding.py.
    Layout [b, s, h, d]."""
    q = as_tensor(q)

    def make_sincos(s, d, dtype):
        # single source of the table math: ops/pallas/rope.rope_tables
        from ....ops.pallas.rope import rope_tables
        cos_h, sin_h = rope_tables(s, d, float(rotary_emb_base))
        return (jnp.concatenate([sin_h, sin_h], -1).astype(dtype),
                jnp.concatenate([cos_h, cos_h], -1).astype(dtype))

    def rope_one(x, sin_e, cos_e):
        # x: [b, s, h, d]; tables [s, d] (shared) or [b, s, d]
        d = x.shape[-1]
        if use_neox_rotary_style:
            x1, x2 = x[..., : d // 2], x[..., d // 2:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
        else:
            x1 = x[..., ::2]
            x2 = x[..., 1::2]
            rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)

        def expand(t):
            return t[None, :, None, :] if t.ndim == 2 else \
                t[:, :, None, :]

        return x * expand(cos_e) + rot * expand(sin_e)

    outs = []
    tensors = [t for t in (q, k, v) if t is not None]

    def fn(*arrs):
        s, d = arrs[0].shape[1], arrs[0].shape[-1]
        # Pallas fused-rope kernel lane (rotate-half == neox style with
        # [s, d/2] tables); measured +2.7% on the 1.3B bench (PERF.md)
        from ....flags import flags as _flags
        from ....ops.dispatch import get_op_impl
        impl = get_op_impl("fused_rope", None)
        if (impl is not None and _flags.FLAGS_pallas_rope and
                use_neox_rotary_style and position_ids is None and
                sin is None and d % 128 == 0):
            from ....ops.pallas.rope import rope_tables
            cos_t, sin_t = rope_tables(s, d, float(rotary_emb_base))
            return tuple(impl(a, cos_t, sin_t) for a in arrs)
        if sin is None:
            if position_ids is not None:
                # tables at the given absolute positions (decode with a
                # KV cache: the appended token sits at cache_len, not 0
                # — reference fused_rope position_ids semantics).
                # Shapes: [s] (shared across batch) or [b, s] per the
                # reference API.  Computed directly from the positions
                # (trace-safe), frequencies from the single source.
                from ....ops.pallas.rope import rope_inv_freq
                pos = as_tensor(position_ids)._data
                inv = rope_inv_freq(d, float(rotary_emb_base))
                freqs = pos.astype(jnp.float32)[..., None] * inv
                emb = jnp.concatenate([freqs, freqs], axis=-1)
                sin_e = jnp.sin(emb).astype(arrs[0].dtype)
                cos_e = jnp.cos(emb).astype(arrs[0].dtype)
            else:
                sin_e, cos_e = make_sincos(s, d, arrs[0].dtype)
        else:
            sin_e = as_tensor(sin)._data.reshape(s, d)
            cos_e = as_tensor(cos)._data.reshape(s, d)
        return tuple(rope_one(a, sin_e, cos_e) for a in arrs)

    ts = [as_tensor(t) for t in tensors]
    outs = apply("fused_rope", fn, *ts, n_outputs=len(ts))
    if not isinstance(outs, tuple):
        outs = (outs,)
    result = []
    it = iter(outs)
    for t in (q, k, v):
        result.append(next(it) if t is not None else None)
    return tuple(result)


def swiglu(x, y=None, name=None):
    """Reference: incubate swiglu — silu(x) * y (or split last dim)."""
    if y is None:
        def fn(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jax.nn.silu(a1) * a2
        return apply("swiglu", fn, as_tensor(x))
    return apply("swiglu", lambda a, b: jax.nn.silu(a) * b,
                 as_tensor(x), as_tensor(y))


def fused_bias_act(x, bias=None, act_method="gelu", **kw):
    from ....nn import functional as F
    if bias is not None:
        from ....tensor.math import add
        x = add(x, bias)
    return getattr(F, act_method)(x)


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    def fn(a, w, *b):
        if transpose_weight:
            w = w.T
        out = a @ w
        if b:
            out = out + b[0]
        return out
    args = [as_tensor(x), as_tensor(weight)]
    if bias is not None:
        args.append(as_tensor(bias))
    return apply("fused_linear", fn, *args)


fused_matmul_bias = fused_linear


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    from ....nn import functional as F
    def fn(a, w, b):
        if trans_x:
            a = a.T
        if trans_y:
            w = w.T
        return a @ w + b
    out = apply("fused_linear_act", fn, as_tensor(x), as_tensor(y),
                as_tensor(bias))
    return getattr(F, activation)(out)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    from ....nn import functional as F
    from ....tensor.math import add
    return add(F.dropout(x, p=p, training=training, mode=mode), y)


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None,
                               ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None,
                               cache_kv=None, attn_mask=None,
                               dropout_rate=0.5, attn_dropout_rate=0.5,
                               ln_epsilon=1e-5, training=True,
                               mode="upscale_in_train", ring_id=-1,
                               add_residual=True, num_heads=None,
                               transpose_qkv_wb=False, name=None):
    """Composite MHA matching the reference's fused_attention semantics."""
    from ....nn import functional as F
    from ....tensor.manipulation import reshape, transpose as ttranspose
    from ....tensor.math import add
    residual = x
    if pre_layer_norm and pre_ln_scale is not None:
        x = F.layer_norm(x, [x.shape[-1]], pre_ln_scale, pre_ln_bias,
                         pre_ln_epsilon)
    b, s, h = x.shape
    qkvw = as_tensor(qkv_weight)
    if transpose_qkv_wb:
        nh = num_heads
        hd = h // nh
    else:
        # weight [3, n_heads, head_dim, h]
        nh = qkv_weight.shape[1]
        hd = qkv_weight.shape[2]

    def qkv_fn(a, w, *bias):
        if not transpose_qkv_wb:
            wmat = jnp.transpose(w.reshape(3 * nh * hd, h) if False
                                 else w.reshape(3, nh * hd, h),
                                 (0, 2, 1)).reshape(h, 3 * nh * hd)
        else:
            wmat = w
        out = a @ wmat
        if bias:
            out = out + bias[0].reshape(-1)
        return out

    args = [x, qkvw]
    if qkv_bias is not None:
        args.append(as_tensor(qkv_bias))
    qkv = apply("fused_qkv", qkv_fn, *args)
    qkv = reshape(qkv, [b, s, 3, nh, hd])
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    ctx = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0,
        training=training)
    ctx = reshape(ctx, [b, s, nh * hd])
    out = F.linear(ctx, linear_weight, linear_bias)
    out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    if add_residual:
        out = add(residual, out)
    if not pre_layer_norm and ln_scale is not None:
        out = F.layer_norm(out, [out.shape[-1]], ln_scale, ln_bias,
                           ln_epsilon)
    return out


def masked_multihead_attention(x, cache_kv=None, bias=None,
                               src_mask=None, **kw):
    raise NotImplementedError(
        "masked_multihead_attention (decode-time MQA cache op) lands with "
        "the inference engine; use scaled_dot_product_attention with a "
        "cache for now")


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode=
                      "upscale_in_train", ring_id=-1, name=None):
    from ....nn import functional as F
    from ....tensor.math import add
    residual = x
    if pre_layer_norm and ln1_scale is not None:
        x = F.layer_norm(x, [x.shape[-1]], ln1_scale, ln1_bias,
                         ln1_epsilon)
    out = F.linear(x, linear1_weight, linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, p=dropout1_rate, training=training, mode=mode)
    out = F.linear(out, linear2_weight, linear2_bias)
    out = F.dropout(out, p=dropout2_rate, training=training, mode=mode)
    out = add(residual, out)
    if not pre_layer_norm and ln2_scale is not None:
        out = F.layer_norm(out, [out.shape[-1]], ln2_scale, ln2_bias,
                           ln2_epsilon)
    return out


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """layer_norm(residual + dropout(x + bias)) — one XLA fusion group
    (reference: incubate/nn/functional/fused_transformer.py
    fused_bias_dropout_residual_layer_norm)."""
    out = x if bias is None else add(x, bias)
    out = F.dropout(out, p=dropout_rate, training=training, mode=mode)
    out = add(residual, out)
    return F.layer_norm(out, [out.shape[-1]], ln_scale, ln_bias, ln_epsilon)


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type="gelu", name=None):
    """Expert-choice MoE FFN: softmax gate over experts, two batched
    matmuls (reference: incubate/nn/functional/fused_ec_moe.py — the
    cutlass grouped-GEMM there is jnp.einsum here; XLA maps it onto the
    MXU batched)."""
    from ....ops.dispatch import apply as _apply, as_tensor as _at
    import jax

    def fn(xa, ga, w0, b0, w1, b1):
        # xa: [B, S, D]; w0: [E, D, H]; w1: [E, H, D]; ga: [B, S, E]
        probs = jax.nn.softmax(ga, axis=-1)
        h = jnp.einsum("bsd,edh->ebsh", xa, w0) + b0[:, None, None]
        if act_type == "gelu":
            h = jax.nn.gelu(h)
        else:
            h = jax.nn.relu(h)
        y = jnp.einsum("ebsh,ehd->ebsd", h, w1) + b1[:, None, None]
        return jnp.einsum("ebsd,bse->bsd", y, probs)

    return _apply("fused_ec_moe", fn, _at(x), _at(gate), _at(bmm0_weight),
                  _at(bmm0_bias), _at(bmm1_weight), _at(bmm1_bias))


def variable_length_memory_efficient_attention(
        query, key, value, seq_lens, kv_seq_lens, mask=None, scale=None,
        causal=False, pre_cache_length=0, name=None):
    """Attention over per-sequence valid lengths (reference:
    incubate/nn/functional/variable_length_memory_efficient_attention.py).
    q/k/v: [B, H, S, D]; invalid key positions are masked out."""
    from ....ops.dispatch import apply as _apply, as_tensor as _at
    import jax
    import math as _math

    def fn(q, k, v, sl, kvl, *m):
        B, H, S, D = q.shape
        sc = scale if scale is not None else 1.0 / _math.sqrt(D)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sc
        kpos = jnp.arange(k.shape[2])
        valid = kpos[None, :] < kvl.reshape(-1, 1)
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        if causal:
            # end-aligned diagonal handles cross-length (cached-decode)
            # shapes: query i sees keys j with j <= i + (K - S)
            K = k.shape[2]
            qpos = jnp.arange(S)[:, None] + (K - S)
            s = jnp.where(qpos >= kpos[None, :][None, None], s, -1e30)
        if m:
            s = s + m[0]
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(q.dtype)

    args = [_at(query), _at(key), _at(value), _at(seq_lens),
            _at(kv_seq_lens)]
    if mask is not None:
        args.append(_at(mask))
    return _apply("variable_length_memory_efficient_attention", fn, *args)


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size,
                     name=None):
    """Max encoder/decoder lengths for block attention scheduling
    (reference: incubate/nn/functional/blha_get_max_len.py)."""
    from ....ops.dispatch import apply as _apply, as_tensor as _at

    def fn(enc, dec):
        return jnp.max(enc), jnp.max(dec)

    return _apply("blha_get_max_len", fn, _at(seq_lens_encoder),
                  _at(seq_lens_decoder), n_outputs=2)


def fused_multi_transformer(
        x, ln_scales, ln_biases, qkv_weights, qkv_biases, linear_weights,
        linear_biases, ffn_ln_scales, ffn_ln_biases, ffn1_weights,
        ffn1_biases, ffn2_weights, ffn2_biases, pre_layer_norm=True,
        epsilon=1e-5, cache_kvs=None, pre_caches=None, rotary_embs=None,
        time_step=None, attn_mask=None, dropout_rate=0.0, activation="gelu",
        training=False, mode="upscale_in_train", ring_id=-1, name=None):
    """Whole pre-LN transformer stack in one call (reference:
    incubate/nn/functional/fused_transformer.py fused_multi_transformer —
    the CUDA mega-kernel is one jitted XLA region here).  Supports the
    encoder path (no cache) with optional additive attn_mask."""
    if cache_kvs is not None or time_step is not None:
        raise NotImplementedError(
            "decode-with-cache path: drive generation through "
            "paddle_tpu.models (kv-cache attention lives there)")
    num_layers = len(qkv_weights)
    out = x
    for i in range(num_layers):
        residual = out
        h = F.layer_norm(out, [out.shape[-1]], ln_scales[i], ln_biases[i],
                         epsilon) if pre_layer_norm else out
        from ....tensor.manipulation import reshape as _reshape
        w = qkv_weights[i]
        b = qkv_biases[i]
        if w.ndim == 4:
            # reference layout [3, num_heads, head_dim, embed]: flatten to
            # a [embed, 3*H*Dh] matmul and remember the head split
            heads, head_dim = int(w.shape[1]), int(w.shape[2])
            wm = _reshape(w, [3 * heads * head_dim, w.shape[3]]).t()
            if b is not None and b.ndim > 1:
                b = _reshape(b, [-1])
        else:
            heads, head_dim = 1, None
            wm = w
        qkv = fused_linear(h, wm, b)
        B, S = qkv.shape[0], qkv.shape[1]
        if head_dim is None:
            head_dim = qkv.shape[-1] // 3
        q, k, v = (t.squeeze(2) for t in _reshape(
            qkv, [B, S, 3, -1]).split(3, axis=2))
        q = _reshape(q, [B, S, heads, head_dim])
        k = _reshape(k, [B, S, heads, head_dim])
        v = _reshape(v, [B, S, heads, head_dim])
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None)
        attn = _reshape(attn, [B, S, -1])
        attn = fused_linear(attn, linear_weights[i], linear_biases[i])
        out = add(residual, F.dropout(attn, p=dropout_rate,
                                      training=training, mode=mode))
        residual = out
        h = F.layer_norm(out, [out.shape[-1]], ffn_ln_scales[i],
                         ffn_ln_biases[i], epsilon) if pre_layer_norm \
            else out
        h = fused_linear(h, ffn1_weights[i], ffn1_biases[i])
        h = F.gelu(h) if activation == "gelu" else F.relu(h)
        h = fused_linear(h, ffn2_weights[i], ffn2_biases[i])
        out = add(residual, F.dropout(h, p=dropout_rate,
                                      training=training, mode=mode))
    return out


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None,
        cache_k_quant_scales=None, cache_v_quant_scales=None,
        cache_k_dequant_scales=None, cache_v_dequant_scales=None,
        qkv_out_scale=None, qkv_bias=None, out_shift=None,
        out_smooth=None, max_enc_len_this_time=None,
        max_dec_len_this_time=None, rope_emb=None, mask=None,
        tgt_mask=None, max_seq_len=-1, block_size=64,
        use_neox_style=False, **kwargs):
    """Paged (block-table) KV-cache attention — reference:
    incubate/nn/functional/block_multihead_attention.py:19 (the
    vLLM-style serving op over CUDA block-cache kernels).

    TPU-native: the caches are page POOLS ``[num_pages, kv_heads,
    block_size, head_dim]`` and the decode phase runs the
    block-table-indexed Pallas kernel
    (ops/pallas/paged_attention.paged_decode_attention) — HBM traffic
    per row scales with its real context length.  The prefill (encoder)
    phase runs the segmented varlen flash program over the packed
    tokens (ops/pallas/flash_varlen).  See models/paged_decode.py for
    the allocator + full generation loop.

    Supported surface: ``qkv [T, 3, n, d]`` (or ``[T, 3*n*d]``), a
    uniform phase per call — all-encoder (prefill) or all-decoder
    (one token per row).  Quant scales / pre-caches / shift-smooth are
    rejected loudly.  Returns ``(out [T, n, d], qkv, key_cache,
    value_cache)`` like the reference.
    """
    for name, v in (("cache_k_quant_scales", cache_k_quant_scales),
                    ("cache_v_quant_scales", cache_v_quant_scales),
                    ("cache_k_dequant_scales", cache_k_dequant_scales),
                    ("cache_v_dequant_scales", cache_v_dequant_scales),
                    ("pre_key_cache", pre_key_cache),
                    ("pre_value_cache", pre_value_cache),
                    ("qkv_out_scale", qkv_out_scale),
                    ("qkv_bias", qkv_bias),
                    ("out_shift", out_shift),
                    ("out_smooth", out_smooth),
                    ("rope_emb", rope_emb), ("mask", mask),
                    ("tgt_mask", tgt_mask)):
        if v is not None:
            raise NotImplementedError(
                f"block_multihead_attention: {name} is not supported "
                "on the TPU paged path")
    import numpy as np
    from ....ops.pallas.paged_attention import paged_decode_attention
    from ....ops.pallas.flash_varlen import flash_attention_segmented
    from ....tensor.tensor import wrap_array

    qkv_t = as_tensor(qkv)
    kc = as_tensor(key_cache)._data
    vc = as_tensor(value_cache)._data
    for name, c in (("key_cache", kc), ("value_cache", vc)):
        if not jnp.issubdtype(c.dtype, jnp.floating):
            # an int8 pool here (quant-scale args already rejected
            # above) would silently truncate bf16 K/V to garbage via
            # .astype on the cache write — fail loudly instead
            raise NotImplementedError(
                f"block_multihead_attention: {name} dtype {c.dtype} — "
                "quantised caches are not supported on this op; use "
                "models.paged_decode.PagedKVCache(kv_quant='int8')")
    tables = jnp.asarray(as_tensor(block_tables)._data, jnp.int32)
    enc = np.asarray(as_tensor(seq_lens_encoder).numpy()).astype(np.int64)
    dec = np.asarray(as_tensor(seq_lens_decoder).numpy()).astype(np.int64)
    this = np.asarray(
        as_tensor(seq_lens_this_time).numpy()).astype(np.int64)
    num_pages, nkv, page, d = kc.shape
    arr = qkv_t._data
    T = arr.shape[0]
    if arr.ndim == 2:
        n = arr.shape[1] // (3 * d)
        arr = arr.reshape(T, 3, n, d)
    else:
        n = arr.shape[2]

    if np.all(this == 1):                      # ---- decode phase ----
        B = T
        q = arr[:, 0]                           # [B, n, d]
        k = arr[:, 1].reshape(B, n, d)[:, :nkv]
        v = arr[:, 2].reshape(B, n, d)[:, :nkv]
        lens = jnp.asarray(dec.copy(), jnp.int32)
        page_ids = tables[jnp.arange(B), lens // page]
        slots = lens % page
        kc = kc.at[page_ids, :, slots, :].set(k.astype(kc.dtype))
        vc = vc.at[page_ids, :, slots, :].set(v.astype(vc.dtype))
        out = paged_decode_attention(q, kc, vc, tables, lens + 1)
        return (wrap_array(out), qkv_t, wrap_array(kc), wrap_array(vc))

    if np.any(dec > 0):
        raise NotImplementedError(
            "block_multihead_attention: mixed encoder/decoder batches "
            "are not supported — issue prefill and decode as separate "
            "calls")
    # ---- prefill (encoder) phase: packed varlen over segments ----
    from ....ops.pallas.flash_varlen import segment_ids_from_cu_seqlens
    cu = np.cumsum(np.concatenate([[0], this]))
    if cu[-1] != T:
        raise ValueError(
            f"block_multihead_attention: seq_lens_this_time sums to "
            f"{int(cu[-1])} but qkv has {T} tokens")
    seg = np.asarray(segment_ids_from_cu_seqlens(
        jnp.asarray(cu, jnp.int32), T))
    pad = (-T) % 128 if T >= 128 else 128 - T
    seg_full = jnp.asarray(np.concatenate(
        [seg, np.full(pad, -1, np.int32)])[None])
    ap = jnp.pad(arr, ((0, pad), (0, 0), (0, 0), (0, 0)))
    # GQA consistency with the decode phase: ONLY the first nkv head
    # slots carry k/v; repeat them across the query-head groups for the
    # prefill attention (decode's kernel does the same grouping)
    g = n // nkv
    kk = ap[:, 1, :nkv]
    vv = ap[:, 2, :nkv]
    if g > 1:
        kk = jnp.repeat(kk, g, axis=1)
        vv = jnp.repeat(vv, g, axis=1)
    out = flash_attention_segmented(
        ap[None, :, 0], kk[None], vv[None], seg_full,
        causal=True)[0, :T]
    # write every row's K/V pages in ONE batched scatter (per-row
    # .at[].set calls would copy the whole multi-GB pool per row)
    tables_np = np.asarray(tables)
    all_ids, all_kb, all_vb = [], [], []
    for b in range(len(this)):
        L = int(this[b])
        if L == 0:
            continue
        o = int(cu[b])
        npg = (L + page - 1) // page
        Lp = npg * page
        kb = jnp.pad(arr[o:o + L, 1, :nkv], ((0, Lp - L), (0, 0), (0, 0)))
        vb = jnp.pad(arr[o:o + L, 2, :nkv], ((0, Lp - L), (0, 0), (0, 0)))
        all_kb.append(kb.reshape(npg, page, nkv, d).transpose(0, 2, 1, 3))
        all_vb.append(vb.reshape(npg, page, nkv, d).transpose(0, 2, 1, 3))
        all_ids.append(tables_np[b, :npg])
    if all_ids:
        ids = np.concatenate(all_ids).copy()
        kc = kc.at[ids].set(
            jnp.concatenate(all_kb, axis=0).astype(kc.dtype))
        vc = vc.at[ids].set(
            jnp.concatenate(all_vb, axis=0).astype(vc.dtype))
    return (wrap_array(out), qkv_t, wrap_array(kc), wrap_array(vc))
