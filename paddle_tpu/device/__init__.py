"""Device API (ref: python/paddle/device/__init__.py).

On TPU the device model is trivial compared to the reference's
DeviceManager/DeviceContextPool (ref paddle/phi/backends/device_manager.h):
XLA owns placement; this module surfaces enumeration + the stream/event API
as no-op-compatible shims (XLA streams are compiler-managed).
"""
from __future__ import annotations

import jax

_current_device = None


def _platform() -> str:
    # a backend that fails to initialise raises: answering "cpu" here
    # would send a chip machine's work to the host without a word
    return jax.devices()[0].platform


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    p = _platform()
    if p == "tpu":
        return "tpu:0"
    if p == "gpu":
        return "gpu:0"
    return "cpu"


def set_device(device: str) -> str:
    global _current_device
    _current_device = device
    return device


def get_all_custom_device_type():
    return ["tpu"] if _platform() == "tpu" else []


def device_count() -> int:
    try:
        return jax.device_count()
    except RuntimeError:
        return 0


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    return device_type == "tpu" and _platform() == "tpu"


class Stream:
    """Compat shim: XLA schedules its own streams on TPU (ref
    paddle/phi/backends/stream.h). Exists so stream-annotated user code runs."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        for d in jax.live_arrays():
            pass

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        pass


def current_stream(device=None):
    return Stream(device)


def synchronize(device=None):
    (jax.device_put(0) + 0).block_until_ready()


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        yield

    return _guard()


def _resolve_jax_device(device=None):
    """None | int | 'tpu:3'/'gpu:1'/'xpu:0' | jax.Device → a jax.Device."""
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        plat, _, idx = device.partition(":")
        if plat == "cpu":
            try:
                pool = jax.devices("cpu")
            except RuntimeError:
                pool = jax.devices()
        else:
            # shim convention: 'gpu'/'xpu'/'tpu' all mean "the accelerator"
            # (Tensor.cuda() is likewise a no-op on the TPU array)
            pool = jax.devices()
        return pool[int(idx) if idx else 0]
    return device  # already a jax.Device


def memory_stats(device=None) -> dict:
    """Per-device memory statistics (ref memory/stats.h) via PJRT."""
    try:
        d = _resolve_jax_device(device)
        return dict(d.memory_stats() or {})
    except (RuntimeError, AttributeError, IndexError, ValueError):
        return {}


def max_memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_allocated(device=None) -> int:
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_reserved(device=None) -> int:
    # PJRT has no allocator-reservation counter distinct from usage; the peak
    # in-use high-water mark is the closest honest analogue (NOT bytes_limit,
    # which is the constant device capacity).
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_reserved", stats.get("peak_bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    stats = memory_stats(device)
    return int(stats.get("bytes_reserved", stats.get("bytes_in_use", 0)))


def empty_cache():
    pass


class cuda:
    """paddle.device.cuda shim — reports no CUDA (we are a TPU build); the
    memory-stat APIs report the TPU's PJRT stats so monitoring code ports."""

    @staticmethod
    def device_count():
        return 0

    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_reserved = staticmethod(memory_reserved)
    empty_cache = staticmethod(empty_cache)
    Stream = Stream
    Event = Event
