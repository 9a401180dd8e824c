"""Device management (parity: python/paddle/device/).

TPU-native: one logical backend (XLA). set_device accepts 'tpu'/'cpu'/'gpu'
spellings; device queries map to jax.devices()."""

from __future__ import annotations

import jax

_current = None


def set_device(device: str):
    global _current
    _current = device
    return device


def get_device() -> str:
    if _current is not None:
        return _current
    backend = jax.default_backend()
    return f"{backend}:0"


def get_all_custom_device_type():
    return ["tpu"] if jax.default_backend() == "tpu" else []


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return jax.default_backend() == "tpu"


class Stream:
    """Parity shim: XLA owns stream scheduling on TPU; we expose the API shape
    (reference: python/paddle/device/cuda/streams.py) as ordered no-ops."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        for d in jax.devices():
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


def synchronize(device=None):
    """Block until all queued work on the device is complete."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


def current_stream(device=None):
    return Stream(device)


# -- memory statistics (reference: paddle.device.cuda.memory_allocated /
# platform/monitor.cc + memory/stats.cc counters).  TPU-native: XLA/PJRT
# owns allocation; per-device stats surface through Device.memory_stats().

def memory_stats(device=None) -> dict:
    """Raw PJRT allocator counters for one device ({} when the backend
    does not expose them, e.g. the CPU)."""
    devs = jax.devices()
    idx = 0
    if isinstance(device, int):
        idx = device
    elif isinstance(device, str) and ":" in device:
        idx = int(device.rsplit(":", 1)[1])
    stats = devs[idx].memory_stats()
    return dict(stats) if stats else {}


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-water mark of live-buffer bytes."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes the allocator has reserved from the device (pool size)."""
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved", s.get("peak_bytes_in_use", 0)))


def device_memory_limit(device=None) -> int:
    """Total memory the allocator may use (HBM capacity budget)."""
    return int(memory_stats(device).get("bytes_limit", 0))


class _CudaNamespace:
    """paddle.device.cuda parity veneer over the XLA stats — the reference
    API names kept so monitoring code ports unchanged (no CUDA exists in
    this build; numbers are the accelerator's)."""
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)

    @staticmethod
    def empty_cache():
        pass  # XLA manages its pools; nothing to drop

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize(device)


cuda = _CudaNamespace()
