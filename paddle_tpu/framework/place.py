"""Device / place model.

TPU-native equivalent of the reference's ``Place`` hierarchy
(/root/reference/paddle/phi/common/place.h — CPUPlace/GPUPlace/XPUPlace/
CustomPlace) and ``paddle.device.set_device``
(/root/reference/python/paddle/device/__init__.py:265).

A ``Place`` names a jax device.  ``TPUPlace(i)`` is first-class (the
north-star backend, jax platform ``"tpu"``); ``CPUPlace`` maps to jax CPU
devices; ``CustomPlace`` covers any other jax platform.  A place that
names a device this process does not have RAISES when it is resolved
(as the reference's ``set_device`` does for a device the build lacks) —
it is never clamped or quietly left on another device.
"""

from __future__ import annotations

import threading
from typing import Optional, Union

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "CustomPlace",
    "CUDAPinnedPlace", "set_device", "get_device", "get_all_devices",
    "device_count", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_tpu", "is_compiled_with_rocm",
    "is_compiled_with_cinn", "is_compiled_with_distribute",
]


class Place:
    """Base place: (device_type, device_id)."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0) -> None:
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self) -> str:
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other) -> bool:
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self._device_id == other._device_id)

    def __hash__(self) -> int:
        return hash((self.device_type, self._device_id))

    # -- jax mapping --------------------------------------------------------
    def _devices(self):
        try:
            return jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: this process has no {self.device_type!r} "
                f"devices (default backend: "
                f"{jax.default_backend()!r})") from e

    def jax_device(self) -> jax.Device:
        """The jax device this place names; raises ``RuntimeError``
        when the platform has no devices here or the id is out of
        range."""
        devs = self._devices()
        if not 0 <= self._device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: device id out of range — {len(devs)} "
                f"{devs[0].platform!r} device(s) present")
        return devs[self._device_id]


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self) -> None:
        super().__init__(0)

    def __repr__(self) -> str:
        return "Place(cpu)"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):
    """Accepted for API parity; resolves to the default accelerator
    (whatever backend jax initialised — ``tpu`` on a chip)."""
    device_type = "gpu"

    def _devices(self):
        return jax.devices()


class XPUPlace(CUDAPlace):
    device_type = "xpu"


class CUDAPinnedPlace(CPUPlace):
    pass


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0) -> None:
        super().__init__(device_id)
        self.device_type = device_type


_lock = threading.Lock()
_current_place: Optional[Place] = None


def _default_place() -> Place:
    d = jax.devices()[0]
    if d.platform == "tpu":
        return TPUPlace(0)
    if d.platform == "cpu":
        return CPUPlace()
    return CustomPlace(d.platform, 0)


def _parse_device(device: Union[str, Place]) -> Place:
    if isinstance(device, Place):
        return device
    s = str(device).lower()
    idx = 0
    if ":" in s:
        s, i = s.split(":", 1)
        idx = int(i)
    if s == "cpu":
        return CPUPlace()
    if s == "tpu":
        return TPUPlace(idx)
    if s in ("gpu", "cuda"):
        return CUDAPlace(idx)
    if s == "xpu":
        return XPUPlace(idx)
    return CustomPlace(s, idx)


def set_device(device: Union[str, Place]) -> Place:
    """Mirror of ``paddle.device.set_device``; raises when the
    process has no such device."""
    global _current_place
    place = _parse_device(device)
    place.jax_device()
    with _lock:
        _current_place = place
    return place


def get_device() -> str:
    p = _get_current_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p.device_type}:{p.get_device_id()}"


def _get_current_place() -> Place:
    global _current_place
    with _lock:
        if _current_place is None:
            _current_place = _default_place()
        return _current_place


def current_jax_device() -> jax.Device:
    return _get_current_place().jax_device()


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role and is always present.
    return True


def is_compiled_with_distribute() -> bool:
    return True
