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

Every call is timed here, around the backend call, and left in
``LAST_CALL`` (one record per thread) for the caller to book: its host
seconds, and whether it compiled.  A ``jax`` or ``pallas`` backend
registers one listener on JAX's compile-duration events; the events fire
on the compiling thread, so a call compiled iff its own thread saw one
during it, however many shard threads share the process.  Armed, the call
is marked on the profiler's clock as ``tf.join.compile`` (an (N, T) shape
this process has not dispatched before) or ``tf.join.call``.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Tuple

import numpy as np

from ...obs.trace import span

JoinFn = Callable[[np.ndarray, np.ndarray, np.ndarray],
                  Tuple[np.ndarray, np.ndarray]]


def _numpy_join(events: np.ndarray, counts: np.ndarray,
                expected: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    valid = events[events >= 0]
    add = np.bincount(valid, minlength=counts.shape[0]).astype(np.int32)
    new_counts = counts + add
    return new_counts, (new_counts >= expected).astype(np.int32)


#: JAX's own compile phases (``jax/_src/dispatch.py``): trace, lowering,
#: backend compile (a persistent-cache hit reports under the last).
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))


class _CallRecord(threading.local):
    """Per thread: the last join call's host seconds and whether it
    compiled, and the compile events this thread has seen."""

    seconds = 0.0
    compiled = False
    compile_events = 0


LAST_CALL = _CallRecord()
_listening = False
_dispatched: set = set()  # (fn, N, T) shapes dispatched, to name the span


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event in _COMPILE_EVENTS:
        LAST_CALL.compile_events += 1


def _listen_for_compiles() -> None:
    """Register the compile listener, once per process (a second one, from
    a race, would count each event twice: a call still compiled or not)."""
    global _listening
    if not _listening:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def _compiling(run: JoinFn) -> JoinFn:
    run.compiles = True  # marks a backend whose new shapes compile
    _listen_for_compiles()
    return run


def _make_jax_join() -> JoinFn:
    import jax

    from .ref import join_counts_ref

    f = jax.jit(join_counts_ref)

    def run(events, counts, expected):
        nc, fired = f(events, counts, expected)
        return np.asarray(nc), np.asarray(fired)

    return _compiling(run)


def _make_pallas_join() -> JoinFn:
    from .ops import event_join

    def run(events, counts, expected):
        nc, fired = event_join(events, counts, expected)
        return np.asarray(nc), np.asarray(fired)

    return _compiling(run)


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
    return _timed(fn, event_rows, counts, expected)


def _timed(fn: JoinFn, events: np.ndarray, counts: np.ndarray,
           expected: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``fn``'s result; its host seconds and whether it compiled are left
    in ``LAST_CALL``."""
    rec = LAST_CALL
    seen = rec.compile_events
    name = "tf.join.call"
    if getattr(fn, "compiles", False):
        shape = (fn, events.shape[0], counts.shape[0])
        if shape not in _dispatched:
            _dispatched.add(shape)
            name = "tf.join.compile"
    t0 = time.perf_counter()
    with span(name):
        out = fn(events, counts, expected)
    rec.seconds = time.perf_counter() - t0
    rec.compiled = rec.compile_events != seen
    return out
