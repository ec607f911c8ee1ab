"""The device a run measures, its published peaks, and the compile cache.

A run names the device it ran on in every result line.  A measuring run
that finds no TPU, or fewer chips than its cell asks for, stops before it
reports anything: a number from the CPU is never written under a device
metric's name.
"""
from __future__ import annotations

import json
import os
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent
# Fixed path inside the checkout: the path is part of the cache's key, so
# a directory that moves never hits.
CACHE_DIR = CHECKOUT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def use_checkout_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout, before JAX
    is imported.  Only programs that take a second or more to compile are
    kept (JAX's own default, set here so that no outside setting moves it):
    the join kernel compiles once per (events, triggers) shape in about
    0.1 s, a hit costs as much, and a cache that gathers every run's new
    shapes made each later run in one checkout slower than the last."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "1"
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return str(CACHE_DIR)


def require_tpu(chips: int):
    """The cell's devices, or :class:`NoAccelerator`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX's default device is {devs[0].platform!r}, "
                            "not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def device_info(devices) -> dict:
    """``device`` of the result line: as JAX reports it, with the peak
    memory of the fullest chip (``None`` where the backend keeps none)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def peaks_for(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an error."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       "peaks.json")
    return table[kind]
