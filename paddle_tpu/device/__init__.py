"""paddle.device namespace (reference: python/paddle/device.py —
set_device:137, get_device:193, is_compiled_with_* queries, Place classes
from fluid/core).

TPU translation: a "place" is a jax.Device; device strings are
``"tpu"``/``"tpu:0"``/``"cpu"`` instead of ``"gpu:0"``. The reference's
per-device streams/contexts (platform/device_context.h) dissolve — XLA owns
scheduling.
"""
from __future__ import annotations

import jax

from ..framework import (  # noqa: F401
    get_device, is_compiled_with_cuda, is_compiled_with_npu,
    is_compiled_with_rocm, is_compiled_with_tpu, is_compiled_with_xpu,
    set_device)


class Place:
    """Device handle wrapping a jax.Device (reference platform/place.h)."""

    _platform = None

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def jax_device(self) -> jax.Device:
        devs = jax.devices(self._platform) if self._platform else jax.devices()
        return devs[self._device_id]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._device_id == other._device_id)

    def __repr__(self):
        plat = self._platform or "any"
        return f"Place({plat}:{self._device_id})"


class CPUPlace(Place):
    _platform = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    _platform = None  # default backend under jax; tpu when available


class CUDAPlace(TPUPlace):
    """Accepted for source compat; maps to the default accelerator."""


class CUDAPinnedPlace(CPUPlace):
    """Pinned host memory is implicit in jax host buffers."""


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def device_count() -> int:
    return jax.device_count()


def get_cudnn_version():
    return None


def synchronize(device=None):
    """Block until all queued work on the device is done.

    Reference: paddle.device.cuda.synchronize. XLA equivalent: a tiny
    computation queued behind the outstanding work, waited for.
    """
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class cuda:  # namespace shim: paddle.device.cuda.*
    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def empty_cache():
        pass


class XPUPlace(TPUPlace):
    """Accepted for source compat; maps to the default accelerator."""


class NPUPlace(TPUPlace):
    """Accepted for source compat; maps to the default accelerator."""
