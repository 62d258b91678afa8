"""Device management (reference: paddle/phi/backends device layer).

XLA/PJRT owns device enumeration, streams and memory; this module provides
the user-facing Place/set_device API surface (paddle.set_device,
paddle.device.*) mapped onto jax devices, plus memory stats
(reference: paddle/phi/core/memory/stats.h).
"""

from __future__ import annotations

import jax

__all__ = [
    "set_device",
    "get_device",
    "device_count",
    "is_compiled_with_cuda",
    "is_compiled_with_xpu",
    "is_compiled_with_tpu",
    "get_all_devices",
    "max_memory_allocated",
    "memory_allocated",
    "memory_reserved",
    "reset_max_memory_allocated",
    "synchronize",
]

_current_device = None


def get_all_devices():
    return jax.devices()


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def _device_from_string(device: str):
    """'tpu', 'tpu:0', 'cpu', 'gpu:0' style strings -> a jax Device.
    Asking for a platform this process does not have is an error: the
    caller named a device and must not be handed another one."""
    platform, _, idx = device.partition(":")
    platform = {"gpu": "cuda", "xpu": "tpu"}.get(platform, platform)
    devs = [d for d in jax.devices() if d.platform == platform]
    if not devs:
        have = sorted({d.platform for d in jax.devices()})
        raise ValueError(
            f"device '{device}': no '{platform}' device in this process "
            f"(jax.devices() has {have})")
    idx = int(idx or 0)
    if idx >= len(devs):
        raise ValueError(
            f"device '{device}': only {len(devs)} '{platform}' device(s)")
    return devs[idx]


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' style strings."""
    global _current_device
    _current_device = _device_from_string(device)
    jax.config.update("jax_default_device", _current_device)
    return _current_device


def get_device() -> str:
    d = _current_device or jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize():
    """Block until all queued work on the default device is complete."""
    (jax.device_put(0) + 0).block_until_ready()


def _mem_stats(device=None):
    d = device or _current_device or jax.devices()[0]
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


_peak_live_bytes: dict = {}       # per-device high-water mark (fallback)
_peak_reserved: dict = {}


def _resolve_device(device=None):
    """Accept a jax Device, an int index, or a 'platform:N' string
    (same parsing rules as set_device)."""
    if device is None:
        return _current_device or jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        return _device_from_string(device)
    return device


def _live_bytes(device=None) -> int:
    """Bytes of live jax Arrays on the device — the fallback accounting
    when the PJRT client exposes no memory_stats (the CPU client).
    Counts framework-visible buffers, not XLA temporaries."""
    d = _resolve_device(device)
    total = 0
    for a in jax.live_arrays():
        try:
            if d in a.devices():
                total += a.nbytes
        except Exception:
            continue
    _peak_live_bytes[d] = max(_peak_live_bytes.get(d, 0), total)
    return total


_peak_reset: set = set()          # devices whose peak was user-reset


def memory_allocated(device=None) -> int:
    d = _resolve_device(device)
    stats = _mem_stats(d)
    if "bytes_in_use" in stats:
        cur = int(stats["bytes_in_use"])
        # keep the resettable sampled peak current (PJRT's own peak
        # counter cannot be reset; see max_memory_allocated)
        _peak_live_bytes[d] = max(_peak_live_bytes.get(d, 0), cur)
        return cur
    return _live_bytes(d)


def max_memory_allocated(device=None) -> int:
    d = _resolve_device(device)
    stats = _mem_stats(d)
    if "peak_bytes_in_use" in stats:
        cur = int(stats["bytes_in_use"]) if "bytes_in_use" in stats else 0
        _peak_live_bytes[d] = max(_peak_live_bytes.get(d, 0), cur)
        if d in _peak_reset:
            # after a reset the client's lifetime peak is stale: report
            # the peak SAMPLED at our API calls since the reset
            return _peak_live_bytes[d]
        return max(int(stats["peak_bytes_in_use"]), _peak_live_bytes[d])
    _live_bytes(d)
    return _peak_live_bytes.get(d, 0)


def reset_max_memory_allocated(device=None) -> None:
    d = _resolve_device(device)
    _peak_live_bytes[d] = 0
    _peak_reserved[d] = 0
    _peak_reset.add(d)


def memory_reserved(device=None) -> int:
    d = _resolve_device(device)
    stats = _mem_stats(d)
    if "bytes_reserved" in stats:
        return int(stats["bytes_reserved"])
    return memory_allocated(d)


def max_memory_reserved(device=None) -> int:
    d = _resolve_device(device)
    cur = memory_reserved(d)
    _peak_reserved[d] = max(_peak_reserved.get(d, 0), cur)
    return _peak_reserved[d]
