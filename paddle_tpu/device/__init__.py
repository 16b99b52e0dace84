"""paddle.device namespace (python/paddle/device parity — SURVEY.md §2.2).

Streams/events are no-ops under XLA's async dispatch; kept API-shaped so
reference-era code runs.
"""
from ..framework.device import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    current_place,
    device_count,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    set_device,
    synchronize,
)


def get_all_device_type():
    return ["cpu", "tpu"]


def get_all_custom_device_type():
    return []


def get_available_device():
    import jax

    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def get_available_custom_device():
    return []


class Stream:
    """API-shape stub: XLA orders work per device automatically."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def guard():
        yield

    return guard()


class cuda:
    """paddle.device.cuda compatibility shim (maps to the TPU backend)."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count("tpu") if is_compiled_with_tpu() else 0

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        return 0

    @staticmethod
    def memory_allocated(device=None):
        return 0
