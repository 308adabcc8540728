"""AMP: auto_cast + GradScaler + decorate.

Reference: python/paddle/amp/auto_cast.py:901 (O1/O2 policy lists),
grad_scaler.py:619 (dynamic loss scaling).

On TPU the native mixed-precision dtype is bfloat16 — no loss scaling
needed (same exponent range as fp32) — but fp16 + dynamic scaling is kept
for API/behaviour parity.  The cast policy hooks into the op-dispatch layer
(ops/dispatch.set_amp_hook): white-listed ops (the MXU set: matmul/conv/
attention) run in the low dtype, black-listed ops stay fp32.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Set

import jax.numpy as jnp
import numpy as np

from ..tensor.tensor import Tensor, wrap_array
from ..framework import dtype as dtypes
from ..ops import dispatch as _dispatch

__all__ = ["auto_cast", "amp_guard", "decorate", "GradScaler",
           "is_float16_supported", "is_bfloat16_supported",
           "AmpScaler", "white_list", "black_list", "is_auto_cast_enabled",
           "get_amp_dtype", "debugging"]

# Reference: auto_cast.py WHITE_LIST/BLACK_LIST (O1)
WHITE_LIST: Set[str] = {
    "matmul", "bmm", "mv", "linear", "conv1d", "conv2d", "conv3d",
    "conv1d_transpose", "conv2d_transpose", "conv3d_transpose", "einsum",
    "sdpa", "flash_attention", "addmm", "mm",
}
BLACK_LIST: Set[str] = {
    "exp", "log", "log2", "log10", "log1p", "logsumexp", "mean", "sum",
    "softmax", "log_softmax", "cross_entropy", "nll_loss", "l1_loss",
    "mse_loss", "binary_cross_entropy", "bce_with_logits", "kl_div",
    "layer_norm", "batch_norm", "instance_norm", "group_norm", "rms_norm",
    "norm", "cumsum", "cumprod", "var", "std", "erf", "erfinv", "pow",
    "divide", "sigmoid_focal_loss", "softmax_with_cross_entropy",
}


class _AmpState(threading.local):
    def __init__(self):
        self.enabled = False
        self.dtype = "bfloat16"
        self.level = "O1"
        self.white = set()
        self.black = set()


_state = _AmpState()


def is_auto_cast_enabled() -> bool:
    return _state.enabled


def get_amp_dtype() -> str:
    return _state.dtype


def white_list():
    return {"float16": WHITE_LIST, "bfloat16": WHITE_LIST}


def black_list():
    return {"float16": BLACK_LIST, "bfloat16": BLACK_LIST}


def _amp_hook(op_name: str, arrays):
    """Called by ops.dispatch.apply before execution."""
    if not _state.enabled:
        return arrays
    low = jnp.bfloat16 if _state.dtype == "bfloat16" else jnp.float16
    if _state.level == "O2":
        # O2: everything low precision except black list
        if op_name in BLACK_LIST or op_name in _state.black:
            target = jnp.float32
        else:
            target = low
    else:
        if op_name in _state.white or (op_name in WHITE_LIST and
                                       op_name not in _state.black):
            target = low
        elif op_name in BLACK_LIST or op_name in _state.black:
            target = jnp.float32
        else:
            return arrays  # gray: leave dtypes alone
    out = []
    for a in arrays:
        if a.dtype in (jnp.float32, jnp.float16, jnp.bfloat16) and \
                a.dtype != target:
            out.append(a.astype(target))
        else:
            out.append(a)
    return tuple(out)


class auto_cast:
    """Context manager mirroring ``paddle.amp.auto_cast``.

    The dispatch hook is installed once at module import and gated purely
    by the thread-local state, so concurrent threads' contexts don't
    disturb each other."""

    def __init__(self, enable=True, custom_white_list=None,
                 custom_black_list=None, level="O1", dtype="bfloat16",
                 use_promote=True):
        if dtype not in ("bfloat16", "float16", "float32"):
            raise ValueError(
                f"auto_cast dtype must be bfloat16/float16/float32, got "
                f"{dtype!r}")
        if level not in ("O0", "O1", "O2"):
            raise ValueError(f"auto_cast level must be O0/O1/O2, got "
                             f"{level!r}")
        if level == "O0" or dtype == "float32":
            enable = False
        self._cfg = (enable, set(custom_white_list or ()),
                     set(custom_black_list or ()), level, dtype)

    def __enter__(self):
        self._prev = (_state.enabled, _state.white, _state.black,
                      _state.level, _state.dtype)
        (_state.enabled, _state.white, _state.black, _state.level,
         _state.dtype) = (self._cfg[0], self._cfg[1], self._cfg[2],
                          self._cfg[3], self._cfg[4])
        return self

    def __exit__(self, *exc):
        (_state.enabled, _state.white, _state.black, _state.level,
         _state.dtype) = self._prev
        return False


# install the hook once; thread-local _state gates it per thread
_dispatch.set_amp_hook(_amp_hook)

amp_guard = auto_cast


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None,
             master_grad=False, excluded_layers=None):
    """Reference: auto_cast.py amp_decorate — O2 casts parameters to the low
    dtype and enables master weights in the optimizer."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    if level == "O2":
        low = dtype
        for m in model_list:
            excluded = set()
            if excluded_layers:
                exc = excluded_layers if isinstance(
                    excluded_layers, (list, tuple)) else [excluded_layers]
                for e in exc:
                    if isinstance(e, type):
                        for sub in m.sublayers(include_self=True):
                            if isinstance(sub, e):
                                excluded.update(
                                    id(p) for p in sub.parameters())
                    else:
                        excluded.update(id(p) for p in e.parameters())
            from ..nn.layer.norm import _BatchNormBase, LayerNorm
            for sub in m.sublayers(include_self=True):
                if isinstance(sub, (_BatchNormBase, LayerNorm)):
                    excluded.update(id(p) for p in sub.parameters())
            for p in m.parameters():
                if id(p) not in excluded and p._data.dtype == jnp.float32:
                    p._data = p._data.astype(
                        jnp.bfloat16 if low == "bfloat16" else jnp.float16)
    if optimizers is None:
        return models if single_model else model_list
    single_opt = not isinstance(optimizers, (list, tuple))
    opt_list = [optimizers] if single_opt else list(optimizers)
    if level == "O2":
        for o in opt_list:
            o._multi_precision = True
    return (models if single_model else model_list,
            optimizers if single_opt else opt_list)


class GradScaler:
    """Dynamic loss scaling (reference: grad_scaler.py:619)."""

    def __init__(self, enable=True, init_loss_scaling=65536.0,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = False

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return self._scale

    def scale(self, var: Tensor) -> Tensor:
        if not self._enable:
            return var
        from ..tensor.math import multiply
        return multiply(var, float(self._scale))

    def unscale_(self, optimizer) -> None:
        if not self._enable or self._unscaled:
            return
        inv = 1.0 / self._scale
        finite_flags = []
        for p in optimizer._params():
            if p._grad is not None:
                g = p._grad * inv
                finite_flags.append(jnp.isfinite(g).all())
                p._grad = g
        # ONE fused reduction + ONE host transfer (not per-param syncs)
        if finite_flags:
            all_finite = finite_flags[0]
            for f in finite_flags[1:]:
                all_finite = jnp.logical_and(all_finite, f)
            self._found_inf = not bool(all_finite)
        else:
            self._found_inf = False
        self._unscaled = True

    def step(self, optimizer) -> None:
        if not self._enable:
            optimizer.step()
            return
        if not self._unscaled:
            self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self._cache_founds = self._found_inf

    def update(self) -> None:
        if not self._enable:
            return
        if self._dynamic:
            if self._found_inf:
                self._bad_steps += 1
                self._good_steps = 0
                if self._bad_steps >= self._decr_every:
                    self._scale = max(self._scale * self._decr_ratio, 1.0)
                    self._bad_steps = 0
            else:
                self._good_steps += 1
                self._bad_steps = 0
                if self._good_steps >= self._incr_every:
                    self._scale *= self._incr_ratio
                    self._good_steps = 0
        self._found_inf = False
        self._unscaled = False

    def minimize(self, optimizer, scaled_loss) -> None:
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def state_dict(self) -> Dict[str, Any]:
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "incr_every_n_steps": self._incr_every,
                "decr_every_n_nan_or_inf": self._decr_every,
                "good_steps": self._good_steps,
                "bad_steps": self._bad_steps,
                "use_dynamic_loss_scaling": self._dynamic}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._scale = state.get("scale", self._scale)
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)

    def get_loss_scaling(self):
        return wrap_array(jnp.asarray(self._scale, jnp.float32))

    def set_init_loss_scaling(self, v):
        self._scale = float(v)


AmpScaler = GradScaler

from . import debugging  # noqa: E402,F401


def is_float16_supported(device=None):
    """fp16 compute support (reference: amp/auto_cast.py).  TPU MXUs are
    bf16-native; fp16 works through XLA but without native rate benefit."""
    import jax
    return jax.devices()[0].platform != "cpu"


def is_bfloat16_supported(device=None):
    import jax
    return True  # bf16 is the native TPU compute dtype; CPU XLA supports it
