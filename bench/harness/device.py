"""The chip a run measures: its checks, its peaks, its memory.

A run on anything but a TPU listed in ``bench/peaks.json`` is refused:
there is no CPU fallback, and an unknown device has no peaks to divide by.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS_FILE = os.path.join(BENCH, "peaks.json")


class DeviceError(RuntimeError):
    """The devices JAX found cannot run this cell."""


def load_peaks() -> dict:
    with open(PEAKS_FILE) as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    table = load_peaks()
    if kind not in table or kind == "source":
        raise DeviceError(f"device kind {kind!r} is not in {PEAKS_FILE}; "
                          f"known: {sorted(k for k in table if k != 'source')}")
    return table[kind]


def describe(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def require(devices, chips: int) -> dict:
    """The cell's device record, or :class:`DeviceError`."""
    info = describe(devices)
    if info["platform"] != "tpu":
        raise DeviceError(f"no TPU: JAX found platform {info['platform']!r}")
    if info["count"] < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{info['count']}")
    peaks_for(info["kind"])
    return info


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout.

    Every program is cached, however fast it compiled: the default floor of
    one second left dozens of small programs to compile in every process.
    """
    import jax

    path = os.path.join(root, ".bench_cache", "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
