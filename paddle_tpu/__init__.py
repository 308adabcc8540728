"""paddle_tpu — a TPU-native deep learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas idioms (see /root/repo/SURVEY.md).

The public namespace mirrors ``paddle``:

    import paddle_tpu as paddle
    x = paddle.to_tensor([[1., 2.], [3., 4.]], stop_gradient=False)
    y = paddle.matmul(x, x)
    y.sum().backward()
    print(x.grad)
"""

from __future__ import annotations

import sys as _sys
import time as _time

# the package's own import as ONE event of the ring (``paddle_tpu.import``,
# handed over at the bottom of this file): two clock reads, on both clocks
# (observability/events.stamp, which cannot be imported yet)
_import_t0 = _time.monotonic()
_jax_preloaded = "jax" in _sys.modules

__version__ = "0.1.0"

# Paddle's default integer dtype is int64 and float64 ops are part of the
# API surface; enable x64 before any array is created.  Compute-path dtypes
# (bf16/f32) are always set explicitly, so this does not slow the TPU path.
import jax as _jax
_jax.config.update("jax_enable_x64", True)

# the compile log listens before anything the package or its caller
# compiles (no flag: docs/OBSERVABILITY.md, "Compile log")
from .observability import compile_log as _compile_log
_compile_log.enable()

# flags must exist before anything reads them
from .flags import get_flags, set_flags, flags  # noqa: F401

from .framework import dtype as _dtype_mod
from .framework.dtype import (  # noqa: F401
    dtype, bool_, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, iinfo, finfo,
    get_default_dtype, set_default_dtype)
bool = bool_  # paddle.bool
from .framework.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, XPUPlace, CustomPlace, CUDAPinnedPlace,
    set_device, get_device, device_count, is_compiled_with_cuda,
    is_compiled_with_xpu, is_compiled_with_tpu, is_compiled_with_rocm,
    is_compiled_with_cinn, is_compiled_with_distribute)
from .framework.random import (  # noqa: F401
    seed, get_rng_state, set_rng_state, get_cuda_rng_state,
    set_cuda_rng_state)

from .tensor.tensor import Tensor, to_tensor, is_tensor  # noqa: F401
from .tensor import creation as _creation  # ensure patching runs
from . import tensor  # noqa: F401
from .tensor import *  # noqa: F401,F403

from . import autograd  # noqa: F401
from .autograd import (  # noqa: F401
    no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad)

# Pallas hot kernels register themselves into the op dispatch table.
from .ops import pallas as _pallas  # noqa: F401,E402

# grad-mode helpers paddle exposes at top level
from .autograd import backward as _autograd_backward  # noqa: F401

# Submodules that mirror paddle.* package structure.
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import observability  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import strings  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import decomposition  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import hapi as _hapi  # noqa: F401,E402
from .hapi import Model, summary  # noqa: F401,E402
from .framework.io import save, load  # noqa: F401,E402
from .nn.layer.layers import (  # noqa: F401,E402
    disable_static, enable_static, in_dynamic_mode)


def DataParallel(layers, *args, **kwargs):
    """Mirror of ``paddle.DataParallel`` (reference: parallel.py:202)."""
    from .distributed.parallel import DataParallel as _DP
    return _DP(layers, *args, **kwargs)


def ParamAttr(name=None, initializer=None, learning_rate=1.0,
              regularizer=None, trainable=True, do_model_average=True,
              need_clip=True):
    from .framework.param import ParamAttr as _PA
    return _PA(name=name, initializer=initializer,
               learning_rate=learning_rate, regularizer=regularizer,
               trainable=trainable, need_clip=need_clip)


from .framework.param import Parameter  # noqa: F401,E402

# paddle.version shim
class _Version:
    full_version = __version__
    major, minor, patch = (int(p) for p in __version__.split("."))

    @staticmethod
    def show():
        print(f"paddle_tpu {__version__} (jax backend)")

    @staticmethod
    def cuda():
        return "False"


version = _Version()

from .observability.events import default_ring as _ring, stamp as _stamp
_import_t1 = _stamp()
_ring().emit("paddle_tpu.import", at=_import_t1,
             dur_s=_import_t1[0] - _import_t0,
             jax_preloaded=_jax_preloaded)
