"""Functional nn ops (reference: python/paddle/nn/functional/).

Convolutions/pools use jax.lax conv primitives (NCHW layouts preserved for
API parity — XLA re-layouts internally for the MXU); attention routes to the
Pallas flash kernel when enabled (ops/pallas/), else the jnp composite.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.dispatch import apply, as_tensor, get_op_impl
from ...framework import dtype as dtypes
from ...framework import random as framework_random
from ...tensor.tensor import Tensor, wrap_array

__all__ = [
    # activations
    "relu", "relu_", "relu6", "leaky_relu", "prelu", "elu", "selu", "celu",
    "gelu", "silu", "swish", "mish", "hardshrink", "hardsigmoid",
    "hardswish", "hardtanh", "softshrink", "softsign", "tanhshrink",
    "thresholded_relu", "log_sigmoid", "maxout", "softplus", "sigmoid",
    "tanh", "softmax", "log_softmax", "gumbel_softmax", "glu", "rrelu",
    # linear / conv / pool
    "linear", "bilinear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
    "conv2d_transpose", "conv3d_transpose", "max_pool1d", "max_pool2d",
    "max_pool3d", "avg_pool1d", "avg_pool2d", "avg_pool3d",
    "adaptive_avg_pool1d", "adaptive_avg_pool2d", "adaptive_avg_pool3d",
    "adaptive_max_pool1d", "adaptive_max_pool2d",
    # norm / dropout
    "batch_norm", "layer_norm", "instance_norm", "group_norm", "rms_norm",
    "local_response_norm", "normalize", "dropout", "dropout2d", "dropout3d",
    "alpha_dropout",
    # embedding / misc
    "embedding", "one_hot", "pad", "interpolate", "upsample", "pixel_shuffle",
    "pixel_unshuffle", "channel_shuffle", "unfold", "fold", "affine_grid",
    "grid_sample", "cosine_similarity", "linear_interp",
    # losses
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss",
    "smooth_l1_loss", "nll_loss", "kl_div", "margin_ranking_loss",
    "hinge_embedding_loss", "cosine_embedding_loss", "ctc_loss",
    "sigmoid_focal_loss", "triplet_margin_loss", "soft_margin_loss",
    "square_error_cost", "log_loss",
    # attention
    "scaled_dot_product_attention", "sequence_mask",
    # long tail (extras.py)
    "pairwise_distance", "label_smooth", "zeropad2d", "lp_pool1d",
    "lp_pool2d", "adaptive_max_pool3d", "max_pool2d_with_index",
    "max_unpool1d", "max_unpool2d", "max_unpool3d",
    "fractional_max_pool2d", "fractional_max_pool3d", "dice_loss",
    "poisson_nll_loss", "npair_loss", "multi_label_soft_margin_loss",
    "hsigmoid_loss", "margin_cross_entropy", "multi_margin_loss",
    "triplet_margin_with_distance_loss", "gaussian_nll_loss",
    "gather_tree", "rnnt_loss", "temporal_shift", "class_center_sample",
    "sparse_attention", "adaptive_log_softmax_with_loss",
    "flash_attn_qkvpacked", "flash_attn_varlen_qkvpacked",
    "flash_attn_unpadded", "flash_attention_with_sparse_mask",
    # in-place aliases
    "elu_", "hardtanh_", "leaky_relu_", "softmax_", "tanh_",
    "thresholded_relu_",
]


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------
def _act(name, jfn):
    def op(x, name=None):
        from ...ops.dispatch import resolve_impl
        return apply(op.__name__, resolve_impl(op.__name__, jfn),
                     as_tensor(x))
    op.__name__ = name
    return op


relu = _act("relu", jax.nn.relu)
relu6 = _act("relu6", jax.nn.relu6)
silu = _act("silu", jax.nn.silu)
swish = _act("swish", jax.nn.silu)
mish = _act("mish", lambda a: a * jnp.tanh(jax.nn.softplus(a)))
softsign = _act("softsign", jax.nn.soft_sign)
tanhshrink = _act("tanhshrink", lambda a: a - jnp.tanh(a))
log_sigmoid = _act("log_sigmoid", jax.nn.log_sigmoid)
sigmoid = _act("sigmoid", jax.nn.sigmoid)
tanh = _act("tanh", jnp.tanh)
hardsigmoid = _act("hardsigmoid",
                   lambda a: jnp.clip(a / 6.0 + 0.5, 0.0, 1.0))
hardswish = _act("hardswish",
                 lambda a: a * jnp.clip(a + 3.0, 0.0, 6.0) / 6.0)


def relu_(x, name=None):
    return x._inplace_assign(relu(x))


def elu_(x, alpha=1.0, name=None):
    return x._inplace_assign(elu(x, alpha))


def hardtanh_(x, min=-1.0, max=1.0, name=None):
    return x._inplace_assign(hardtanh(x, min, max))


def leaky_relu_(x, negative_slope=0.01, name=None):
    return x._inplace_assign(leaky_relu(x, negative_slope))


def softmax_(x, axis=-1, dtype=None, name=None):
    return x._inplace_assign(softmax(x, axis, dtype))


def tanh_(x, name=None):
    return x._inplace_assign(tanh(x))


def thresholded_relu_(x, threshold=1.0, value=0.0, name=None):
    return x._inplace_assign(thresholded_relu(x, threshold, value))


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply("leaky_relu",
                 lambda a: jax.nn.leaky_relu(a, negative_slope),
                 as_tensor(x))


def prelu(x, weight, data_format="NCHW", name=None):
    x, weight = as_tensor(x), as_tensor(weight)

    def fn(a, w):
        if w.size > 1:
            shape = [1] * a.ndim
            ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
            shape[ch_axis] = w.size
            w = w.reshape(shape)
        return jnp.where(a >= 0, a, w * a)

    return apply("prelu", fn, x, weight)


def elu(x, alpha=1.0, name=None):
    return apply("elu", lambda a: jax.nn.elu(a, alpha), as_tensor(x))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply("selu",
                 lambda a: scale * jnp.where(a > 0, a,
                                             alpha * jnp.expm1(a)),
                 as_tensor(x))


def celu(x, alpha=1.0, name=None):
    return apply("celu", lambda a: jax.nn.celu(a, alpha), as_tensor(x))


def gelu(x, approximate=False, name=None):
    from ...ops.dispatch import resolve_impl
    impl = resolve_impl("gelu",
                        lambda a: jax.nn.gelu(a, approximate=approximate),
                        approximate=approximate)
    return apply("gelu", impl, as_tensor(x))


def hardshrink(x, threshold=0.5, name=None):
    return apply("hardshrink",
                 lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0),
                 as_tensor(x))


def softshrink(x, threshold=0.5, name=None):
    return apply("softshrink",
                 lambda a: jnp.sign(a) * jnp.maximum(
                     jnp.abs(a) - threshold, 0.0), as_tensor(x))


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply("hardtanh", lambda a: jnp.clip(a, min, max), as_tensor(x))


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    return apply("thresholded_relu",
                 lambda a: jnp.where(a > threshold, a, value), as_tensor(x))


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply("softplus",
                 lambda a: jnp.where(a * beta > threshold, a,
                                     jax.nn.softplus(a * beta) / beta),
                 as_tensor(x))


def maxout(x, groups, axis=1, name=None):
    x = as_tensor(x)
    ax = axis % x.ndim

    def fn(a):
        c = a.shape[ax]
        new_shape = (a.shape[:ax] + (c // groups, groups) +
                     a.shape[ax + 1:])
        return jnp.max(a.reshape(new_shape), axis=ax + 1)

    return apply("maxout", fn, x)


def softmax(x, axis=-1, dtype=None, name=None):
    from ...ops.dispatch import resolve_impl
    x = as_tensor(x)
    jdt = dtypes.to_jax_dtype(dtype) if dtype is not None else None
    impl = resolve_impl("softmax", lambda a: jax.nn.softmax(a, axis=axis),
                        axis=axis)

    def fn(a):
        if jdt is not None:
            a = a.astype(jdt)
        return impl(a)

    return apply("softmax", fn, x)


def log_softmax(x, axis=-1, dtype=None, name=None):
    from ...ops.dispatch import resolve_impl
    x = as_tensor(x)
    jdt = dtypes.to_jax_dtype(dtype) if dtype is not None else None
    impl = resolve_impl("log_softmax",
                        lambda a: jax.nn.log_softmax(a, axis=axis),
                        axis=axis)

    def fn(a):
        if jdt is not None:
            a = a.astype(jdt)
        return impl(a)

    return apply("log_softmax", fn, x)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    x = as_tensor(x)
    key = framework_random.next_key()

    def fn(a):
        g = jax.random.gumbel(key, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.put_along_axis(jnp.zeros_like(y), idx, 1.0,
                                        axis=axis, inplace=False)
            # straight-through estimator
            y = y_hard + (y - jax.lax.stop_gradient(y))
        return y

    return apply("gumbel_softmax", fn, x)


def glu(x, axis=-1, name=None):
    def fn(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)
    return apply("glu", fn, as_tensor(x))


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    x = as_tensor(x)
    if training:
        key = framework_random.next_key()

        def fn(a):
            r = jax.random.uniform(key, a.shape, a.dtype, lower, upper)
            return jnp.where(a >= 0, a, r * a)
    else:
        mid = (lower + upper) / 2.0

        def fn(a):
            return jnp.where(a >= 0, a, mid * a)

    return apply("rrelu", fn, x)


# ---------------------------------------------------------------------------
# linear / bilinear
# ---------------------------------------------------------------------------
def linear(x, weight, bias=None, name=None):
    """y = x @ W + b, W shaped [in, out] (reference: functional/common.py).
    The MXU hot path — executes as a single XLA dot_general."""
    x, weight = as_tensor(x), as_tensor(weight)
    if bias is not None:
        return apply("linear", lambda a, w, b: a @ w + b, x, weight,
                     as_tensor(bias))
    return apply("linear", lambda a, w: a @ w, x, weight)


def bilinear(x1, x2, weight, bias=None, name=None):
    x1, x2, weight = as_tensor(x1), as_tensor(x2), as_tensor(weight)

    def fn(a, b, w, *bias_arr):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bias_arr:
            out = out + bias_arr[0]
        return out

    if bias is not None:
        return apply("bilinear", fn, x1, x2, weight, as_tensor(bias))
    return apply("bilinear", fn, x1, x2, weight)


# ---------------------------------------------------------------------------
# convolutions (NC* layouts like the reference; XLA handles MXU tiling)
# ---------------------------------------------------------------------------
def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


def _conv_nd(name, x, weight, bias, stride, padding, dilation, groups,
             nd, data_format, transpose=False, output_padding=0):
    x, weight = as_tensor(x), as_tensor(weight)
    stride = _norm_tuple(stride, nd)
    dilation = _norm_tuple(dilation, nd)
    channel_last = data_format.endswith("C")
    if isinstance(padding, str):
        pad = padding.upper()  # "SAME"/"VALID"
    else:
        if isinstance(padding, (list, tuple)) and len(padding) == 2 * nd:
            pad = [(int(padding[2 * i]), int(padding[2 * i + 1]))
                   for i in range(nd)]
        else:
            p = _norm_tuple(padding, nd)
            pad = [(i, i) for i in p]
    # jax dimension_numbers: lhs NC<sp>, rhs OI<sp>, out NC<sp>
    sp = "DHW"[-nd:] if nd > 1 else "W"
    if channel_last:
        lhs_spec = "N" + sp + "C"
    else:
        lhs_spec = "NC" + sp
    rhs_spec = "OI" + sp
    out_spec = lhs_spec
    dn = jax.lax.conv_dimension_numbers(
        tuple(x.shape), tuple(weight.shape), (lhs_spec, rhs_spec, out_spec))

    if transpose:
        opad = _norm_tuple(output_padding, nd)

        def fn(a, w, *b):
            # conv_transpose: weight layout [in, out/groups, *k] in paddle
            wt = jnp.swapaxes(w, 0, 1)  # -> [out/groups, in, *k]
            if isinstance(pad, str):
                padding_cfg = pad
            else:
                # grad-of-conv padding: (k-1)*d - p
                padding_cfg = [
                    ((w.shape[2 + i] - 1) * dilation[i] - pad[i][0],
                     (w.shape[2 + i] - 1) * dilation[i] - pad[i][1] +
                     opad[i]) for i in range(nd)]
            out = jax.lax.conv_general_dilated(
                a, jnp.flip(wt, axis=tuple(range(2, 2 + nd))),
                window_strides=(1,) * nd,
                padding=padding_cfg,
                lhs_dilation=stride,
                rhs_dilation=dilation,
                dimension_numbers=dn,
                feature_group_count=groups)
            if b:
                bshape = [1] * out.ndim
                bshape[1 if not channel_last else -1] = -1
                out = out + b[0].reshape(bshape)
            return out
    else:
        def fn(a, w, *b):
            out = jax.lax.conv_general_dilated(
                a, w, window_strides=stride, padding=pad,
                rhs_dilation=dilation, dimension_numbers=dn,
                feature_group_count=groups)
            if b:
                bshape = [1] * out.ndim
                bshape[1 if not channel_last else -1] = -1
                out = out + b[0].reshape(bshape)
            return out

    if bias is not None:
        return apply(name, fn, x, weight, as_tensor(bias))
    return apply(name, fn, x, weight)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _conv_nd("conv1d", x, weight, bias, stride, padding, dilation,
                    groups, 1, data_format)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv_nd("conv2d", x, weight, bias, stride, padding, dilation,
                    groups, 2, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv_nd("conv3d", x, weight, bias, stride, padding, dilation,
                    groups, 3, data_format)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    return _conv_nd("conv1d_transpose", x, weight, bias, stride, padding,
                    dilation, groups, 1, data_format, transpose=True,
                    output_padding=output_padding)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    return _conv_nd("conv2d_transpose", x, weight, bias, stride, padding,
                    dilation, groups, 2, data_format, transpose=True,
                    output_padding=output_padding)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _conv_nd("conv3d_transpose", x, weight, bias, stride, padding,
                    dilation, groups, 3, data_format, transpose=True,
                    output_padding=output_padding)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------
def _pool_nd(name, x, kernel, stride, padding, nd, reducer, init,
             ceil_mode=False, count_include_pad=True, average=False):
    x = as_tensor(x)
    kernel = _norm_tuple(kernel, nd)
    stride = _norm_tuple(stride if stride is not None else kernel, nd)
    p = _norm_tuple(padding, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride

    def fn(a):
        pads = [(0, 0), (0, 0)]
        for d in range(nd):
            hi = p[d]
            if ceil_mode:
                # right-pad so the last partial window produces an output
                # element: out = ceil((L + 2p - k)/s) + 1, except that a
                # window starting entirely in right padding is dropped
                # (reference rule: last window must start within input or
                # left padding)
                L = a.shape[2 + d]
                out_len = -(-(L + 2 * p[d] - kernel[d]) // stride[d]) + 1
                if (out_len - 1) * stride[d] >= L + p[d]:
                    out_len -= 1
                hi += max(0, (out_len - 1) * stride[d] + kernel[d]
                          - (L + 2 * p[d]))
            pads.append((p[d], hi))
        pads = tuple(pads)
        out = jax.lax.reduce_window(a, init, reducer, window, strides, pads)
        if average:
            if count_include_pad and not ceil_mode:
                return out / float(np.prod(kernel))
            # denominator: count explicit padding iff count_include_pad;
            # ceil-mode extra cells never count (reference semantics)
            ones = jnp.ones_like(a)
            if count_include_pad:
                ones = jnp.pad(ones, [(0, 0), (0, 0)]
                               + [(p[d], p[d]) for d in range(nd)],
                               constant_values=1.0)
                cpads = tuple((0, pads[i][1] - p[i - 2]) if i >= 2 else (0, 0)
                              for i in range(nd + 2))
            else:
                cpads = pads
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           strides, cpads)
            return out / counts
        return out

    return apply(name, fn, x)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    if return_mask:
        from .extras import max_pool2d_with_index
        if ceil_mode:
            raise NotImplementedError(
                "return_mask with ceil_mode is not supported")
        return max_pool2d_with_index(x, kernel_size, stride, padding)
    return _pool_nd("max_pool2d", x, kernel_size, stride, padding, 2,
                    jax.lax.max, -jnp.inf, ceil_mode=ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool_nd("avg_pool2d", x, kernel_size, stride, padding, 2,
                    jax.lax.add, 0.0, average=True, ceil_mode=ceil_mode,
                    count_include_pad=not exclusive)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    out = _pool_nd("max_pool1d", x, kernel_size, stride, padding, 1,
                   jax.lax.max, -jnp.inf, ceil_mode=ceil_mode)
    return (out, None) if return_mask else out


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    return _pool_nd("avg_pool1d", x, kernel_size, stride, padding, 1,
                    jax.lax.add, 0.0, average=True, ceil_mode=ceil_mode,
                    count_include_pad=not exclusive)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    out = _pool_nd("max_pool3d", x, kernel_size, stride, padding, 3,
                   jax.lax.max, -jnp.inf, ceil_mode=ceil_mode)
    return (out, None) if return_mask else out


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return _pool_nd("avg_pool3d", x, kernel_size, stride, padding, 3,
                    jax.lax.add, 0.0, average=True, ceil_mode=ceil_mode,
                    count_include_pad=not exclusive)


def _adaptive_pool(name, x, output_size, nd, average=True):
    x = as_tensor(x)
    out_sizes = _norm_tuple(output_size, nd)

    def fn(a):
        sp_dims = a.shape[2:]
        res = a
        for d, (insz, outsz) in enumerate(zip(sp_dims, out_sizes)):
            axis = 2 + d
            if insz % outsz == 0:
                k = insz // outsz
                shape = (res.shape[:axis] + (outsz, k) +
                         res.shape[axis + 1:])
                r = res.reshape(shape)
                res = jnp.mean(r, axis=axis + 1) if average else \
                    jnp.max(r, axis=axis + 1)
            else:
                # general case: per-output-bin reduce
                starts = (np.arange(outsz) * insz) // outsz
                ends = ((np.arange(outsz) + 1) * insz + outsz - 1) // outsz
                pieces = []
                for s, e in zip(starts, ends):
                    seg = jax.lax.slice_in_dim(res, int(s), int(e),
                                               axis=axis)
                    red = jnp.mean(seg, axis=axis, keepdims=True) \
                        if average else jnp.max(seg, axis=axis,
                                                keepdims=True)
                    pieces.append(red)
                res = jnp.concatenate(pieces, axis=axis)
        return res

    return apply(name, fn, x)


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool("adaptive_avg_pool1d", x, output_size, 1)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool("adaptive_avg_pool2d", x, output_size, 2)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool("adaptive_avg_pool3d", x, output_size, 3)


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    out = _adaptive_pool("adaptive_max_pool1d", x, output_size, 1,
                         average=False)
    return (out, None) if return_mask else out


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    out = _adaptive_pool("adaptive_max_pool2d", x, output_size, 2,
                         average=False)
    return (out, None) if return_mask else out


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Reference: functional/norm.py batch_norm.  Running stats are updated
    in-place on the provided buffer tensors (host-side rebind)."""
    x = as_tensor(x)
    ch_axis = 1 if data_format[1] == "C" or data_format == "NC" else \
        x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_stats = (not training) if use_global_stats is None else \
        use_global_stats

    shape = [1] * x.ndim
    shape[ch_axis] = -1

    if use_stats:
        args = [x, as_tensor(running_mean), as_tensor(running_var)]

        def fn(a, m, v, *wb):
            out = (a - m.reshape(shape)) / jnp.sqrt(
                v.reshape(shape) + epsilon)
            if len(wb) >= 1:
                out = out * wb[0].reshape(shape)
            if len(wb) == 2:
                out = out + wb[1].reshape(shape)
            return out
    else:
        args = [x]

        def fn(a, *wb):
            m = jnp.mean(a, axis=reduce_axes)
            v = jnp.var(a, axis=reduce_axes)
            out = (a - m.reshape(shape)) / jnp.sqrt(
                v.reshape(shape) + epsilon)
            if len(wb) >= 1:
                out = out * wb[0].reshape(shape)
            if len(wb) == 2:
                out = out + wb[1].reshape(shape)
            return out

    if weight is not None:
        args.append(as_tensor(weight))
    if bias is not None:
        args.append(as_tensor(bias))
    out = apply("batch_norm", fn, *args)

    update_stats = training and running_mean is not None
    if update_stats:
        from ...autograd import tape as _tape
        if _tape.in_functional_trace():
            # under a functional trace, rebind ONLY when the buffer was
            # swapped in by Layer._functional_call (its _data is a
            # tracer) — then return_buffers captures the update and the
            # finally-restore unwinds the live layer.  A trace that did
            # NOT manage this buffer (static_engine / pipeline partial
            # calls) must not have a tracer leaked onto it.
            update_stats = isinstance(as_tensor(running_mean)._data,
                                      jax.core.Tracer)
    if update_stats:
        m_new = jnp.mean(x._data, axis=reduce_axes)
        v_new = jnp.var(x._data, axis=reduce_axes)
        n = x._data.size / x._data.shape[ch_axis]
        unbiased = v_new * n / max(n - 1, 1)
        rm, rv = as_tensor(running_mean), as_tensor(running_var)
        running_mean._data = (momentum * rm._data +
                              (1 - momentum) * m_new).astype(
            rm._data.dtype)
        running_var._data = (momentum * rv._data +
                             (1 - momentum) * unbiased).astype(
            rv._data.dtype)
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    x = as_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    nd = len(normalized_shape)
    axes = tuple(range(x.ndim - nd, x.ndim))

    has_w, has_b = weight is not None, bias is not None

    def _default(a, *wb):
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) / jnp.sqrt(v + epsilon)
        i = 0
        if has_w:
            out = out * wb[i]
            i += 1
        if has_b:
            out = out + wb[i]
        return out

    from ...ops.dispatch import resolve_impl
    fn = resolve_impl("layer_norm", _default, epsilon=epsilon,
                      begin_norm_axis=x.ndim - nd, has_weight=has_w,
                      has_bias=has_b)

    args = [x]
    if has_w:
        args.append(as_tensor(weight))
    if has_b:
        args.append(as_tensor(bias))
    return apply("layer_norm", fn, *args)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm (reference: incubate fused_rms_norm).  Dispatchable to the
    Pallas kernel via register_op_impl('rms_norm', ...)."""
    from ...ops.dispatch import resolve_impl
    x = as_tensor(x)
    rule = resolve_impl("rms_norm", None, epsilon=epsilon)
    if rule is not None:
        if weight is not None:
            return apply("rms_norm", rule, x, as_tensor(weight))
        return apply("rms_norm", rule, x)
    impl = get_op_impl("rms_norm", None)
    if (impl is not None and weight is not None
            and jax.default_backend() == "tpu"):
        # on CPU the Pallas kernel would run in interpret mode — far
        # slower than the jnp composite below, which XLA fuses anyway.
        # Dispatch under the same op name as the composite so AMP
        # list-based casting treats both paths identically.
        return apply("rms_norm",
                     lambda a, w: impl(a, w, epsilon),
                     x, as_tensor(weight))

    def fn(a, *w):
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        out = (a.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)
               ).astype(a.dtype)
        if w:
            out = out * w[0]
        return out

    if weight is not None:
        return apply("rms_norm", fn, x, as_tensor(weight))
    return apply("rms_norm", fn, x)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  eps=1e-5, data_format="NCHW", name=None):
    x = as_tensor(x)
    axes = tuple(range(2, x.ndim))

    def fn(a, *wb):
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) / jnp.sqrt(v + eps)
        shape = [1, -1] + [1] * (a.ndim - 2)
        if len(wb) >= 1:
            out = out * wb[0].reshape(shape)
        if len(wb) == 2:
            out = out + wb[1].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(as_tensor(weight))
    if bias is not None:
        args.append(as_tensor(bias))
    return apply("instance_norm", fn, *args)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = as_tensor(x)

    def fn(a, *wb):
        n, c = a.shape[0], a.shape[1]
        rest = a.shape[2:]
        g = a.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) / jnp.sqrt(v + epsilon)).reshape(a.shape)
        shape = [1, -1] + [1] * (a.ndim - 2)
        if len(wb) >= 1:
            out = out * wb[0].reshape(shape)
        if len(wb) == 2:
            out = out + wb[1].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(as_tensor(weight))
    if bias is not None:
        args.append(as_tensor(bias))
    return apply("group_norm", fn, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = as_tensor(x)

    def fn(a):
        sq = jnp.square(a)
        half = size // 2
        pad_cfg = [(0, 0)] * a.ndim
        pad_cfg[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_cfg)
        window = [1] * a.ndim
        window[1] = size
        summed = jax.lax.reduce_window(
            padded, 0.0, jax.lax.add, tuple(window), (1,) * a.ndim,
            [(0, 0)] * a.ndim)
        return a / jnp.power(k + alpha * summed, beta)

    return apply("local_response_norm", fn, x)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    x = as_tensor(x)

    def fn(a):
        nrm = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(nrm, epsilon)

    return apply("normalize", fn, x)


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------
def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    x = as_tensor(x)
    if not training or p == 0:
        if mode == "downscale_in_infer" and not training:
            return apply("dropout", lambda a: a * (1.0 - p), x)
        return apply("dropout_id", lambda a: a, x)
    key = framework_random.next_key()

    def fn(a):
        shape = list(a.shape)
        if axis is not None:
            ax = [axis] if isinstance(axis, int) else list(axis)
            mask_shape = [s if i in ax else 1 for i, s in enumerate(shape)]
        else:
            mask_shape = shape
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(mask_shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return apply("dropout", fn, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ch_axes = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p=p, axis=list(ch_axes), training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ch_axes = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p=p, axis=list(ch_axes), training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = as_tensor(x)
    if not training or p == 0:
        return apply("alpha_dropout_id", lambda a: a, x)
    key = framework_random.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def fn(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return (a_coef * jnp.where(keep, a, alpha_p) + b_coef).astype(
            a.dtype)

    return apply("alpha_dropout", fn, x)


# ---------------------------------------------------------------------------
# embedding / one-hot / padding
# ---------------------------------------------------------------------------
def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    x, weight = as_tensor(x), as_tensor(weight)

    def fn(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply("embedding", fn, x, weight)


def one_hot(x, num_classes, name=None):
    return apply("one_hot",
                 lambda a: jax.nn.one_hot(a.astype(jnp.int32), num_classes,
                                          dtype=jnp.float32), as_tensor(x))


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    x = as_tensor(x)
    pad = [int(p) for p in (pad.tolist() if isinstance(pad, Tensor)
                            else pad)] if not isinstance(pad, int) else pad

    def build_cfg(a):
        if isinstance(pad, int):
            return [(pad, pad)] * a.ndim
        if len(pad) == 2 * a.ndim:
            # paddle full-form: [before0, after0, before1, after1, ...]
            return [(pad[2 * i], pad[2 * i + 1]) for i in range(a.ndim)]
        # NCHW-style: pad applies to trailing spatial dims, reversed pairs
        nsp = len(pad) // 2
        cfg = [(0, 0)] * a.ndim
        if data_format.endswith("C"):
            sp_start = 1
        else:
            sp_start = a.ndim - nsp
        for i in range(nsp):
            cfg[sp_start + i] = (pad[2 * i], pad[2 * i + 1])
        return cfg

    jmode = {"constant": "constant", "reflect": "reflect",
             "replicate": "edge", "circular": "wrap"}[mode]

    def fn(a):
        cfg = build_cfg(a)
        if jmode == "constant":
            return jnp.pad(a, cfg, mode="constant", constant_values=value)
        return jnp.pad(a, cfg, mode=jmode)

    return apply("pad", fn, x)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    x = as_tensor(x)
    nd = x.ndim - 2
    in_sp = x.shape[2:]
    if size is not None:
        if isinstance(size, Tensor):
            size = size.tolist()
        out_sp = [int(s.item()) if isinstance(s, Tensor) else int(s)
                  for s in (size if isinstance(size, (list, tuple))
                            else [size])]
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else \
            [scale_factor] * nd
        out_sp = [int(i * s) for i, s in zip(in_sp, sf)]
    method = {"nearest": "nearest", "bilinear": "linear",
              "trilinear": "linear", "linear": "linear",
              "bicubic": "cubic", "area": "linear"}[mode]

    def fn(a):
        out_shape = a.shape[:2] + tuple(out_sp)
        return jax.image.resize(a, out_shape, method=method)

    return apply("interpolate", fn, x)


upsample = interpolate
linear_interp = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def fn(a):
        n, c, h, w = a.shape
        oc = c // (r * r)
        out = a.reshape(n, oc, r, r, h, w)
        out = out.transpose(0, 1, 4, 2, 5, 3)
        return out.reshape(n, oc, h * r, w * r)

    return apply("pixel_shuffle", fn, as_tensor(x))


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def fn(a):
        n, c, h, w = a.shape
        out = a.reshape(n, c, h // r, r, w // r, r)
        out = out.transpose(0, 1, 3, 5, 2, 4)
        return out.reshape(n, c * r * r, h // r, w // r)

    return apply("pixel_unshuffle", fn, as_tensor(x))


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def fn(a):
        n, c, h, w = a.shape
        out = a.reshape(n, groups, c // groups, h, w)
        out = out.transpose(0, 2, 1, 3, 4)
        return out.reshape(n, c, h, w)

    return apply("channel_shuffle", fn, as_tensor(x))


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    x = as_tensor(x)
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    p = _norm_tuple(paddings, 2)
    d = _norm_tuple(dilations, 2)

    def fn(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
        oh = (a.shape[2] - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
        ow = (a.shape[3] - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
        patches = []
        for i in range(k[0]):
            for j in range(k[1]):
                sl = a[:, :, i * d[0]: i * d[0] + oh * s[0]: s[0],
                       j * d[1]: j * d[1] + ow * s[1]: s[1]]
                patches.append(sl)
        out = jnp.stack(patches, axis=2)  # n, c, k*k, oh, ow
        return out.reshape(n, c * k[0] * k[1], oh * ow)

    return apply("unfold", fn, x)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    x = as_tensor(x)
    out_sz = _norm_tuple(output_sizes, 2)
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    p = _norm_tuple(paddings, 2)
    d = _norm_tuple(dilations, 2)

    def fn(a):
        n, ckk, L = a.shape
        c = ckk // (k[0] * k[1])
        ph, pw = out_sz[0] + 2 * p[0], out_sz[1] + 2 * p[1]
        oh = (ph - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
        ow = (pw - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
        a = a.reshape(n, c, k[0], k[1], oh, ow)
        out = jnp.zeros((n, c, ph, pw), a.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                out = out.at[:, :, i * d[0]: i * d[0] + oh * s[0]: s[0],
                             j * d[1]: j * d[1] + ow * s[1]: s[1]].add(
                    a[:, :, i, j])
        return out[:, :, p[0]: ph - p[0], p[1]: pw - p[1]]

    return apply("fold", fn, x)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    theta = as_tensor(theta)
    if isinstance(out_shape, Tensor):
        out_shape = [int(v) for v in out_shape.tolist()]
    n, c, h, w = out_shape

    def fn(th):
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) + 0.5) * 2 / h - 1
            xs = (jnp.arange(w) + 0.5) * 2 / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1).reshape(-1, 3)
        out = base @ jnp.swapaxes(th, -1, -2)
        return out.reshape(-1, h, w, 2) if out.ndim == 2 else \
            out.reshape(th.shape[0], h, w, 2)

    return apply("affine_grid", fn, theta)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    x, grid = as_tensor(x), as_tensor(grid)

    def fn(a, g):
        n, c, h, w = a.shape
        gx = (g[..., 0] + 1) * (w - 1) / 2 if align_corners else \
            ((g[..., 0] + 1) * w - 1) / 2
        gy = (g[..., 1] + 1) * (h - 1) / 2 if align_corners else \
            ((g[..., 1] + 1) * h - 1) / 2

        def sample(img, yy, xx):
            yy = jnp.clip(yy, 0, h - 1)
            xx = jnp.clip(xx, 0, w - 1)
            return img[:, :, yy.astype(jnp.int32), xx.astype(jnp.int32)]

        if mode == "nearest":
            out = jax.vmap(
                lambda img, yy, xx: sample(img[None], yy, xx)[0],
                in_axes=(0, 0, 0))(a, jnp.round(gy), jnp.round(gx))
            return out
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        x1, y1 = x0 + 1, y0 + 1
        wa = (x1 - gx) * (y1 - gy)
        wb = (gx - x0) * (y1 - gy)
        wc = (x1 - gx) * (gy - y0)
        wd = (gx - x0) * (gy - y0)

        def bilin(img, y0_, x0_, y1_, x1_, wa_, wb_, wc_, wd_):
            ia = sample(img[None], y0_, x0_)[0]
            ib = sample(img[None], y0_, x1_)[0]
            ic = sample(img[None], y1_, x0_)[0]
            id_ = sample(img[None], y1_, x1_)[0]
            return (wa_ * ia + wb_ * ib + wc_ * ic + wd_ * id_)

        out = jax.vmap(bilin)(a, y0, x0, y1, x1, wa[:, None], wb[:, None],
                              wc[:, None], wd[:, None])
        return out

    return apply("grid_sample", fn, x, grid)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    return apply("cosine_similarity",
                 lambda a, b: jnp.sum(a * b, axis=axis) / (
                     jnp.maximum(jnp.linalg.norm(a, axis=axis) *
                                 jnp.linalg.norm(b, axis=axis), eps)),
                 as_tensor(x1), as_tensor(x2))


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    x = as_tensor(x)
    ml = int(maxlen) if maxlen is not None else int(x.max().item())
    jdt = dtypes.to_jax_dtype(dtype)
    return apply("sequence_mask",
                 lambda a: (jnp.arange(ml) < a[..., None]).astype(jdt), x)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Reference: functional/loss.py cross_entropy."""
    input, label = as_tensor(input), as_tensor(label)

    def fn(logits, lab, *w):
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        nclass = logits.shape[axis]
        if soft_label or (lab.ndim == logits.ndim and
                          lab.shape[axis] == nclass and
                          jnp.issubdtype(lab.dtype, jnp.floating)):
            soft = lab
            if label_smoothing > 0:
                soft = soft * (1 - label_smoothing) + \
                    label_smoothing / nclass
            loss = -jnp.sum(soft * logp, axis=axis)
        else:
            lab_idx = lab.astype(jnp.int32)
            if lab_idx.ndim == logits.ndim:
                lab_idx = jnp.squeeze(lab_idx, axis=axis)
            oh = jax.nn.one_hot(lab_idx, nclass, axis=axis,
                                dtype=logp.dtype)
            if label_smoothing > 0:
                oh = oh * (1 - label_smoothing) + label_smoothing / nclass
            loss = -jnp.sum(oh * logp, axis=axis)
            mask = lab_idx != ignore_index
            loss = jnp.where(mask, loss, 0.0)
            if w:
                wt = jnp.take(w[0], lab_idx, axis=0) * mask
                loss = loss * jnp.take(w[0], lab_idx, axis=0)
                if reduction == "mean":
                    return jnp.sum(loss) / jnp.maximum(jnp.sum(wt), 1e-12)
            if reduction == "mean":
                denom = jnp.maximum(jnp.sum(mask), 1)
                return jnp.sum(loss) / denom
        return _reduce_loss(loss, reduction)

    args = [input, label]
    if weight is not None:
        args.append(as_tensor(weight))
    return apply("cross_entropy", fn, *args)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    from ...tensor.manipulation import unsqueeze
    loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    input, label = as_tensor(input), as_tensor(label)

    def fn(p, y, *w):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w:
            loss = loss * w[0]
        return _reduce_loss(loss, reduction)

    args = [input, label]
    if weight is not None:
        args.append(as_tensor(weight))
    return apply("binary_cross_entropy", fn, *args)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    logit, label = as_tensor(logit), as_tensor(label)

    def fn(z, y, *rest):
        w = rest[0] if weight is not None else None
        pw = rest[-1] if pos_weight is not None else None
        log_sig = jax.nn.log_sigmoid(z)
        log_one_minus = jax.nn.log_sigmoid(-z)
        if pw is not None:
            loss = -(pw * y * log_sig + (1 - y) * log_one_minus)
        else:
            loss = -(y * log_sig + (1 - y) * log_one_minus)
        if w is not None:
            loss = loss * w
        return _reduce_loss(loss, reduction)

    args = [logit, label]
    if weight is not None:
        args.append(as_tensor(weight))
    if pos_weight is not None:
        args.append(as_tensor(pos_weight))
    return apply("bce_with_logits", fn, *args)


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss",
                 lambda a, b: _reduce_loss(jnp.square(a - b), reduction),
                 as_tensor(input), as_tensor(label))


def square_error_cost(input, label):
    return apply("square_error_cost", lambda a, b: jnp.square(a - b),
                 as_tensor(input), as_tensor(label))


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss",
                 lambda a, b: _reduce_loss(jnp.abs(a - b), reduction),
                 as_tensor(input), as_tensor(label))


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def fn(a, b):
        d = a - b
        loss = jnp.where(jnp.abs(d) < delta, 0.5 * d * d / delta,
                         jnp.abs(d) - 0.5 * delta)
        # paddle multiplies by delta
        return _reduce_loss(loss * delta, reduction)
    return apply("smooth_l1_loss", fn, as_tensor(input), as_tensor(label))


def nll_loss(input, label, weight=None, ignore_index=-100,
             reduction="mean", name=None):
    input, label = as_tensor(input), as_tensor(label)

    def fn(logp, y, *w):
        y = y.astype(jnp.int32)
        picked = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0] \
            if logp.ndim == 2 else jnp.take_along_axis(
                logp, y[:, None], axis=1).squeeze(1)
        loss = -picked
        mask = y != ignore_index
        loss = jnp.where(mask, loss, 0.0)
        if w:
            wt = jnp.take(w[0], y, axis=0)
            loss = loss * wt
            if reduction == "mean":
                return jnp.sum(loss) / jnp.sum(wt * mask)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(mask), 1)
        return _reduce_loss(loss, reduction)

    args = [input, label]
    if weight is not None:
        args.append(as_tensor(weight))
    return apply("nll_loss", fn, *args)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def fn(lp, t):
        if log_target:
            loss = jnp.exp(t) * (t - lp)
        else:
            loss = t * (jnp.log(jnp.maximum(t, 1e-30)) - lp)
        if reduction == "batchmean":
            return jnp.sum(loss) / lp.shape[0]
        return _reduce_loss(loss, reduction)
    return apply("kl_div", fn, as_tensor(input), as_tensor(label))


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    def fn(a, b, y):
        loss = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce_loss(loss, reduction)
    return apply("margin_ranking_loss", fn, as_tensor(input),
                 as_tensor(other), as_tensor(label))


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    def fn(a, y):
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce_loss(loss, reduction)
    return apply("hinge_embedding_loss", fn, as_tensor(input),
                 as_tensor(label))


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean", name=None):
    def fn(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1),
            1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce_loss(loss, reduction)
    return apply("cosine_embedding_loss", fn, as_tensor(input1),
                 as_tensor(input2), as_tensor(label))


def soft_margin_loss(input, label, reduction="mean", name=None):
    def fn(a, y):
        loss = jnp.log1p(jnp.exp(-y * a))
        return _reduce_loss(loss, reduction)
    return apply("soft_margin_loss", fn, as_tensor(input), as_tensor(label))


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    def fn(a, pos, neg):
        dp = jnp.sum(jnp.abs(a - pos) ** p, axis=-1) ** (1 / p)
        dn = jnp.sum(jnp.abs(a - neg) ** p, axis=-1) ** (1 / p)
        if swap:
            dpn = jnp.sum(jnp.abs(pos - neg) ** p, axis=-1) ** (1 / p)
            dn = jnp.minimum(dn, dpn)
        loss = jnp.maximum(dp - dn + margin, 0.0)
        return _reduce_loss(loss, reduction)
    return apply("triplet_margin_loss", fn, as_tensor(input),
                 as_tensor(positive), as_tensor(negative))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25,
                       gamma=2.0, reduction="sum", name=None):
    logit, label = as_tensor(logit), as_tensor(label)

    def fn(z, y, *n):
        p = jax.nn.sigmoid(z)
        ce = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce_loss(loss, reduction)

    args = [logit, label]
    if normalizer is not None:
        args.append(as_tensor(normalizer))
    return apply("sigmoid_focal_loss", fn, *args)


def log_loss(input, label, epsilon=1e-4, name=None):
    def fn(p, y):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(
            1 - p + epsilon)
    return apply("log_loss", fn, as_tensor(input), as_tensor(label))


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    import optax
    log_probs = as_tensor(log_probs)
    labels, input_lengths, label_lengths = (as_tensor(labels),
                                            as_tensor(input_lengths),
                                            as_tensor(label_lengths))

    def fn(lp, lab, il, ll):
        # lp: [T, B, C] paddle layout -> optax expects [B, T, C]
        logits = jnp.swapaxes(lp, 0, 1)
        B, T, C = logits.shape
        logit_padding = (jnp.arange(T)[None, :] >= il[:, None]).astype(
            jnp.float32)
        L = lab.shape[1]
        label_padding = (jnp.arange(L)[None, :] >= ll[:, None]).astype(
            jnp.float32)
        loss = optax.ctc_loss(logits, logit_padding, lab.astype(jnp.int32),
                              label_padding, blank_id=blank)
        return _reduce_loss(loss, reduction)

    return apply("ctc_loss", fn, log_probs, labels, input_lengths,
                 label_lengths)


# ---------------------------------------------------------------------------
# attention (reference: functional/flash_attention.py:147,:722)
# ---------------------------------------------------------------------------
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Layouts follow the reference: q/k/v are [batch, seq, heads, dim].

    Routed to the Pallas flash-attention kernel when registered and
    applicable (ops/pallas/flash_attention.py), else an XLA composite that
    still fuses well on the MXU.
    """
    from ...ops.pallas.flash_attention import causal_mask as _causal_mask

    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    if is_causal:
        _causal_mask(q.shape[1], k.shape[1])  # validates q_len <= kv_len
    impl = get_op_impl("flash_attention", None)
    from ...flags import flags as _flags
    if (impl is not None and _flags.FLAGS_pallas_flash_attention
            and attn_mask is None and dropout_p == 0.0):
        def pfn(qq, kk, vv):
            return impl(qq, kk, vv, causal=is_causal)
        return apply("flash_attention", pfn, q, k, v)

    scale = 1.0 / math.sqrt(q.shape[-1])

    def fn(qq, kk, vv, *mask):
        # [b, s, h, d] -> [b, h, s, d]
        qq = jnp.swapaxes(qq, 1, 2)
        kk = jnp.swapaxes(kk, 1, 2)
        vv = jnp.swapaxes(vv, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) * scale
        if is_causal:
            logits = jnp.where(
                _causal_mask(logits.shape[-2], logits.shape[-1]),
                logits, -jnp.inf)
        if mask:
            m = mask[0]
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, -jnp.inf)
            else:
                logits = logits + m
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            vv.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vv)
        return jnp.swapaxes(out, 1, 2)

    if attn_mask is not None:
        out = apply("sdpa", fn, q, k, v, as_tensor(attn_mask))
    else:
        out = apply("sdpa", fn, q, k, v)
    if dropout_p > 0.0 and training:
        out = dropout(out, p=dropout_p, training=training)
    return out


from .extras import *  # noqa: F401,F403,E402
