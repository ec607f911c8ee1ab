"""Backend dispatch for the event-join segmented sum.

The worker's batch plane reduces a routed event batch to per-trigger
activation counts (``new_counts``) and threshold-crossing flags (``fired``).
Three interchangeable backends compute the same one-hot segmented sum:

* ``pallas`` — the TPU kernel (``event_join.event_join_counts``);
* ``jax``    — the jitted pure-jnp oracle (``ref.join_counts_ref``), the
  CPU/GPU XLA path;
* ``numpy``  — ``np.bincount``, the host path: for processes that own no
  device (``ProcessShardPool`` shard processes) and hosts without a TPU.

A backend takes int32 numpy arrays (``events`` holds trigger row ids, −1 =
padding) and returns numpy ``(new_counts, fired)``.  Selection:
``TRIGGERFLOW_JOIN_BACKEND`` env var (``auto`` | ``numpy`` | ``jax`` |
``pallas`` | ``off``), default ``auto`` = pallas when this process's default
JAX device is a TPU, numpy otherwise.  Resolving ``auto`` imports JAX and,
unless ``JAX_PLATFORMS`` already excludes the TPU, initializes its backend:
on a TPU host the resolving process takes the chip, so a process that must
stay off it asks for ``numpy`` by name.
"""
from __future__ import annotations

import os
from typing import Callable, Optional, Tuple

import numpy as np

JoinFn = Callable[[np.ndarray, np.ndarray, np.ndarray],
                  Tuple[np.ndarray, np.ndarray]]


def _numpy_join(events: np.ndarray, counts: np.ndarray,
                expected: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    valid = events[events >= 0]
    add = np.bincount(valid, minlength=counts.shape[0]).astype(np.int32)
    new_counts = counts + add
    return new_counts, (new_counts >= expected).astype(np.int32)


def _make_jax_join() -> JoinFn:
    import jax

    from .ref import join_counts_ref

    f = jax.jit(join_counts_ref)

    def run(events, counts, expected):
        nc, fired = f(events, counts, expected)
        return np.asarray(nc), np.asarray(fired)

    return run


def _make_pallas_join() -> JoinFn:
    from .ops import event_join

    def run(events, counts, expected):
        nc, fired = event_join(events, counts, expected)
        return np.asarray(nc), np.asarray(fired)

    return run


def _on_tpu() -> bool:
    """Whether this process's default JAX device is a TPU.  Without JAX
    installed there is no device to find, and a ``jax_platforms`` setting
    (``JAX_PLATFORMS``) without ``tpu`` rules it out before any runtime
    starts; any other error (a chip held by another process, a broken
    runtime) propagates."""
    try:
        import jax
    except ImportError:
        return False
    platforms = jax.config.jax_platforms
    if platforms and "tpu" not in platforms.split(","):
        return False
    return jax.devices()[0].platform == "tpu"


_resolved: dict = {}


def resolve_join_backend(name: Optional[str] = None) -> Tuple[str, Optional[JoinFn]]:
    """Resolve a backend name to ``(resolved_name, fn)``, cached per name.

    ``fn`` is ``None`` for ``off``.  Unavailable explicit choices raise so
    misconfiguration is loud; ``auto`` names what it resolved to in the
    first element."""
    name = (name or os.environ.get("TRIGGERFLOW_JOIN_BACKEND", "auto")).lower()
    cached = _resolved.get(name)
    if cached is not None:
        return cached
    if name == "off":
        resolved: Tuple[str, Optional[JoinFn]] = ("off", None)
    elif name == "numpy":
        resolved = ("numpy", _numpy_join)
    elif name == "jax":
        resolved = ("jax", _make_jax_join())
    elif name == "pallas":
        resolved = ("pallas", _make_pallas_join())
    elif name != "auto":
        raise ValueError(f"unknown join backend {name!r}")
    elif _on_tpu():
        resolved = ("pallas", _make_pallas_join())
    else:
        resolved = ("numpy", _numpy_join)
    _resolved[name] = resolved
    return resolved


def join_counts_segments(lens, counts: np.ndarray, expected: np.ndarray,
                         fn: Optional[JoinFn] = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Segmented-sum join over *contiguous runs*: ``lens[i]`` events belong
    to trigger row ``i``.  This is the shape the columnar ingest path
    produces (a batch bucketed by subject is runs of row ids, never a
    ragged scatter), so the row-id expansion lives here next to the kernel
    instead of in every caller."""
    if fn is None:
        _name, fn = resolve_join_backend()
        if fn is None:
            raise RuntimeError(
                "join backend disabled (TRIGGERFLOW_JOIN_BACKEND=off)")
    event_rows = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
    return fn(event_rows, counts, expected)
