"""The user-facing Tensor.

TPU-native equivalent of the reference's eager tensor stack:
``paddle::Tensor`` (/root/reference/paddle/phi/api/include/tensor.h:82) +
``AutogradMeta`` (/root/reference/paddle/fluid/eager/autograd_meta.h:61) +
the pybind ``TensorObject`` (/root/reference/paddle/fluid/pybind/eager.cc:68).

A Tensor is a mutable handle over an immutable ``jax.Array`` plus autograd
metadata.  In-place ops rebind ``_data`` (copy-on-write is free on XLA);
the tape snapshots producer edges at record time so mutation never corrupts
recorded history (see autograd/tape.py).

Arithmetic and most methods are monkey-patched onto this class by the op
modules (mirroring python/paddle/base/dygraph/tensor_patch_methods.py).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtypes
from ..framework import place as places
from ..autograd import tape

__all__ = ["Tensor", "is_tensor", "wrap_array", "to_tensor"]


class Tensor:
    __slots__ = ("_data", "stop_gradient", "_grad", "_grad_node", "_out_idx",
                 "_grad_hooks", "name", "persistable", "_is_param",
                 "__weakref__", "__dict__")

    _name_counter = [0]

    def __init__(self, data: Any = None, dtype: Any = None, place=None,
                 stop_gradient: bool = True, name: Optional[str] = None):
        if data is None:
            data = jnp.zeros((), dtypes.to_jax_dtype(dtype or "float32"))
        self._data = _to_jax_array(data, dtype, place)
        self.stop_gradient = stop_gradient
        self._grad = None
        self._grad_node = None
        self._out_idx = 0
        self._grad_hooks: List[Callable] = []
        if name is None:
            Tensor._name_counter[0] += 1
            name = f"generated_tensor_{Tensor._name_counter[0]}"
        self.name = name
        self.persistable = False
        self._is_param = False

    # -- basic meta ---------------------------------------------------------
    @property
    def shape(self) -> List[int]:
        return list(self._data.shape)

    @property
    def dtype(self) -> dtypes.DType:
        return dtypes.convert_dtype(self._data.dtype)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def dim(self):
        return self._data.ndim

    @property
    def size(self) -> int:
        return int(self._data.size)

    @property
    def place(self):
        try:
            dev = list(self._data.devices())[0]
        except Exception:
            return places.CPUPlace()
        if dev.platform == "tpu":
            return places.TPUPlace(dev.id)
        if dev.platform == "cpu":
            return places.CPUPlace()
        return places.CustomPlace(dev.platform, dev.id)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad is None:
            return None
        g = Tensor.__new__(Tensor)
        _init_raw(g, self._grad, stop_gradient=True)
        g.name = self.name + "@GRAD"
        return g

    @grad.setter
    def grad(self, value) -> None:
        if value is None:
            self._grad = None
        else:
            self._grad = value._data if isinstance(value, Tensor) \
                else jnp.asarray(value)

    # jax interop: lets jnp.* consume a Tensor directly (no grad tracking).
    def __jax_array__(self):
        return self._data

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor: Optional["Tensor"] = None,
                 retain_graph: bool = False) -> None:
        """Reference: tensor_patch_methods.py:252 → run_backward."""
        tape.run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self) -> None:
        self._grad = None

    clear_gradient = clear_grad

    def _accumulate_grad(self, ct) -> None:
        if ct.dtype != self._data.dtype and jnp.issubdtype(
                self._data.dtype, jnp.floating):
            ct = ct.astype(self._data.dtype)
        self._grad = ct if self._grad is None else self._grad + ct

    def register_hook(self, hook: Callable):
        """Grad hook (reference: GradNodeBase hooks)."""
        self._grad_hooks.append(hook)

        class _Handle:
            def remove(handle_self):
                try:
                    self._grad_hooks.remove(hook)
                except ValueError:
                    pass

        return _Handle()

    def detach(self) -> "Tensor":
        t = Tensor.__new__(Tensor)
        _init_raw(t, self._data, stop_gradient=True)
        t.name = self.name + ".detach"
        return t

    def detach_(self) -> "Tensor":
        self._grad_node = None
        self._out_idx = 0
        self.stop_gradient = True
        return self

    def _wrap_like(self, arr) -> "Tensor":
        t = Tensor.__new__(Tensor)
        _init_raw(t, arr, stop_gradient=True)
        return t

    # -- value access -------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self, *args) -> Any:
        if args:
            return self.numpy().item(*args)
        return self._data.item()

    def tolist(self):
        return self.numpy().tolist()

    def __len__(self) -> int:
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __iter__(self):
        # MUST exist: jax CLAMPS out-of-bounds integer indexing, so
        # Python's legacy iteration protocol (__getitem__(0), (1), ...
        # until IndexError) never terminates on a Tensor — `for row in
        # t` spun forever (the round-4 `multiplex` hang's root cause)
        for i in range(len(self)):
            yield self[i]

    def __array__(self, dtype=None, copy=None):
        # without this, np.asarray(t) walks the sequence protocol and
        # builds an OBJECT array of row Tensors
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __int__(self):
        return int(self.item())

    def __float__(self):
        return float(self.item())

    def __bool__(self):
        if self._data.size != 1:
            raise ValueError(
                "The truth value of a Tensor with more than one element is "
                "ambiguous.")
        return bool(self.item())

    def __index__(self):
        return int(self.item())

    def __format__(self, spec):
        if self._data.size == 1:
            return format(self.item(), spec)
        return format(self.numpy(), spec)

    def __repr__(self) -> str:
        arr = np.asarray(self._data)
        body = np.array2string(arr, precision=8, separator=", ")
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}, stop_gradient={self.stop_gradient},\n"
                f"       {body})")

    # -- in-place machinery -------------------------------------------------
    def _inplace_assign(self, new_tensor: "Tensor") -> "Tensor":
        """Rebind this handle to the result of an (autograd-tracked) op.

        The tape captured edges by value, so older consumers are unaffected
        (reference keeps a version counter; we keep snapshots instead).
        """
        self._data = new_tensor._data
        self._grad_node = new_tensor._grad_node
        self._out_idx = new_tensor._out_idx
        if not new_tensor.stop_gradient:
            self.stop_gradient = False
        return self

    def copy_(self, other: "Tensor") -> "Tensor":
        src = other._data if isinstance(other, Tensor) else jnp.asarray(other)
        self._data = src.astype(self._data.dtype) \
            if src.dtype != self._data.dtype else src
        return self

    def set_value(self, value) -> None:
        src = value._data if isinstance(value, Tensor) else jnp.asarray(value)
        if tuple(src.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {src.shape} vs "
                f"{self._data.shape}")
        src = src.astype(self._data.dtype)
        # keep the destination's placement: a TP/ZeRO-sharded parameter
        # must stay sharded after loading new values
        old_sharding = getattr(self._data, "sharding", None)
        new_sharding = getattr(src, "sharding", None)
        if (old_sharding is not None
                and getattr(old_sharding, "mesh", None) is not None
                and old_sharding != new_sharding):
            from ..distributed.auto_parallel import _device_put_robust
            src = _device_put_robust(src, old_sharding)
        self._data = src

    def get_tensor(self):  # LoDTensor-compat shim
        return self

    # -- device movement ----------------------------------------------------
    def to(self, *args, **kwargs) -> "Tensor":
        device = kwargs.get("device")
        dtype_arg = kwargs.get("dtype")
        blocking = kwargs.get("blocking")  # noqa: F841 (parity)
        for a in args:
            if isinstance(a, (dtypes.DType,)) or (
                    isinstance(a, str) and a.replace("paddle.", "")
                    in dtypes._BY_NAME):
                dtype_arg = a
            elif isinstance(a, (str, places.Place)):
                device = a
        out = self
        if dtype_arg is not None:
            out = out.astype(dtype_arg)
        if device is not None:
            place = places._parse_device(device) if not isinstance(
                device, places.Place) else device
            new = Tensor.__new__(Tensor)
            _init_raw(new, jax.device_put(out._data, place.jax_device()),
                      stop_gradient=out.stop_gradient)
            new._grad_node = out._grad_node
            new._out_idx = out._out_idx
            out = new
        return out

    def cpu(self) -> "Tensor":
        return self.to(device="cpu")

    def cuda(self, device_id=0, blocking=True) -> "Tensor":
        return self.to(device=f"gpu:{device_id}")

    def tpu(self, device_id=0) -> "Tensor":
        return self.to(device=f"tpu:{device_id}")

    def pin_memory(self) -> "Tensor":
        return self

    def contiguous(self) -> "Tensor":
        return self

    def is_contiguous(self) -> bool:
        return True

    # astype / cast / clone / reshape etc. are patched in by op modules.

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self._data,), (self.stop_gradient,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        t = cls.__new__(cls)
        _init_raw(t, children[0], stop_gradient=aux[0])
        return t


def _init_raw(t: Tensor, data, stop_gradient: bool = True) -> None:
    t._data = data
    t.stop_gradient = stop_gradient
    t._grad = None
    t._grad_node = None
    t._out_idx = 0
    t._grad_hooks = []
    Tensor._name_counter[0] += 1
    t.name = f"generated_tensor_{Tensor._name_counter[0]}"
    t.persistable = False
    t._is_param = False


def _to_jax_array(data, dtype=None, place=None):
    jdt = dtypes.to_jax_dtype(dtype) if dtype is not None else None
    if isinstance(data, Tensor):
        arr = data._data
    elif isinstance(data, jax.Array):
        arr = data
    elif isinstance(data, np.ndarray):
        arr = jnp.asarray(data)
    elif isinstance(data, (bool, int, float, complex)):
        if jdt is None:
            if isinstance(data, bool):
                jdt = np.bool_
            elif isinstance(data, int):
                jdt = np.int64
            elif isinstance(data, float):
                jdt = dtypes.to_jax_dtype(dtypes.default_float_dtype())
            else:
                jdt = np.complex64
        arr = jnp.asarray(data, dtype=jdt)
        jdt = None
    else:
        np_arr = np.asarray(data)
        if jdt is None and np_arr.dtype == np.float64:
            jdt = dtypes.to_jax_dtype(dtypes.default_float_dtype())
        arr = jnp.asarray(np_arr)
    if jdt is not None and arr.dtype != jdt:
        arr = arr.astype(jdt)
    if isinstance(place, places.Place):
        arr = jax.device_put(arr, place.jax_device())
    return arr


def wrap_array(arr, stop_gradient: bool = True) -> Tensor:
    """Fast internal constructor from a raw jax array."""
    t = Tensor.__new__(Tensor)
    _init_raw(t, arr, stop_gradient=stop_gradient)
    return t


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """Mirror of ``paddle.to_tensor``."""
    if isinstance(data, Tensor) and dtype is None and place is None:
        t = wrap_array(data._data, stop_gradient=stop_gradient)
        return t
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


def is_tensor(x) -> bool:
    return isinstance(x, Tensor)


# Register Tensor as a jax pytree so functional transforms can carry them.
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: t.tree_flatten(),
    Tensor.tree_unflatten,
)
