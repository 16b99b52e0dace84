"""Device management: Place, set_device/get_device.

Reference parity: paddle's `Place`/`CPUPlace`/`CUDAPlace` and
`paddle.set_device('gpu:0')` (ref: paddle/phi/common/place.h,
python/paddle/device/ — SURVEY.md §2.2 "Device mgmt"). TPU is first-class
here: `set_device('tpu')` selects the jax TPU backend and raises when the
process has none; 'cpu' selects the host backend (used by CI). Devices are
jax devices; there are no streams — XLA schedules asynchronously per
device.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

_lock = threading.Lock()
_current_place: Optional["Place"] = None


class Place:
    """A device place: backend name + device index (e.g. tpu:0, cpu:0)."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        if isinstance(other, Place):
            return (
                self.device_type == other.device_type
                and self.device_id == other.device_id
            )
        if isinstance(other, str):
            return str(self) == f"Place({other if ':' in other else other + ':0'})"
        return NotImplemented

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    # GPU never exists in this framework; kept for API-shape compatibility.
    def is_gpu_place(self):
        return False

    def jax_device(self):
        """Resolve to the concrete jax device. A place names a real
        device: a 'tpu' place in a process without one raises
        (RuntimeError from jax) instead of resolving to a CPU device
        under the accelerator's name."""
        devs = jax.devices(self.device_type)
        if self.device_id >= len(devs):
            raise ValueError(
                f"device index {self.device_id} out of range for "
                f"{self.device_type} ({len(devs)} devices)"
            )
        return devs[self.device_id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


# Alias so code written against the reference's CUDAPlace keeps working on TPU.
CUDAPlace = TPUPlace


def _parse_device(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("gpu", "cuda", "xpu", "npu"):
        # Map legacy accelerator names onto the TPU backend so reference-era
        # scripts run unmodified.
        kind = "tpu"
    if kind not in ("cpu", "tpu"):
        raise ValueError(f"unsupported device '{device}' (use 'cpu' or 'tpu')")
    return Place(kind, idx)


def set_device(device) -> Place:
    global _current_place
    place = device if isinstance(device, Place) else _parse_device(device)
    place.jax_device()  # validate now
    with _lock:
        _current_place = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        with _lock:
            if _current_place is None:
                # Default: what jax's default backend is, by its own name
                _current_place = Place(jax.default_backend(), 0)
    return _current_place


def current_jax_device():
    return current_place().jax_device()


def device_count(device_type: Optional[str] = None) -> int:
    """Devices of `device_type` (raises when the process has none of
    that platform); with no argument, of the default backend."""
    return len(jax.devices(device_type))


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    """TPU is the first-class 'custom device' of this build (reference:
    plugin device registry); everything else is absent."""
    return str(device_type).lower() == "tpu"


def get_cudnn_version():
    """No CUDA backend: the reference returns None when not compiled
    with cuDNN."""
    return None


def is_compiled_with_tpu() -> bool:
    try:
        return len(jax.devices("tpu")) > 0
    except RuntimeError:
        return False


def is_compiled_with_distribute() -> bool:
    return True


def synchronize():
    """Block until all pending device work completes (paddle.device.synchronize)."""
    (jax.device_put(0) + 0).block_until_ready()
