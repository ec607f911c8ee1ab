"""Capture the profiler's trace of a window and reduce it to numbers.

The reduction works on plain rows so that a test can check it on a small
recorded trace: ``{"device": {<plane>: [[op, start_ns, dur_ns, program],
...]}, "host": [[name, start_ns, dur_ns], ...]}``.  Device rows are the
operations a TPU ran (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane), each with the program (``XLA Modules`` line) it ran in; host rows
are the spans the benchmark opens with
``jax.profiler.TraceAnnotation`` (names starting ``chipbench.``), which the
profiler writes on the same clock.

- busy time: the union of the device rows' intervals inside the window,
  averaged over the devices; idle share is 1 minus busy over the window;
- kernel time: the summed durations of the operations whose name holds
  one of the kernel's names;
- breakdown: the ten device operations that took most time, and the ten
  longest idle gaps, each named by the host span that covers most of it.
"""
from __future__ import annotations

import glob
import os
from bisect import bisect_right
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"

Interval = Tuple[int, int]


def _module_name(name: str) -> str:
    """``jit_event_join(1233...)`` -> ``jit_event_join``."""
    return name.split("(", 1)[0]


def _op_name(name: str) -> str:
    """``%event_join.1 = (s32[13]...) custom-call(...)`` -> ``event_join.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_rows(trace_dir: str) -> dict:
    """Rows of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    pd = ProfileData.from_file(paths[0])
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                              _module_name(ev.name))
                             for ev in (lines["XLA Modules"].events
                                        if "XLA Modules" in lines else ()))
            starts = [m[0] for m in modules]
            rows = device.setdefault(plane.name, [])
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                t0, d = int(ev.start_ns), int(ev.duration_ns)
                i = bisect_right(starts, t0) - 1
                module = modules[i][2] if i >= 0 and t0 < modules[i][1] else ""
                rows.append([_op_name(ev.name), t0, d, module])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"device": device, "host": host}


def window_of(rows: dict) -> Interval:
    """The traced window: the benchmark's ``chipbench.window`` span."""
    spans = [(s, s + d) for n, s, d in rows["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(spans)}")
    return spans[0]


def merge(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Union of intervals clipped to ``[lo, hi)``, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(rows: dict, window: Interval) -> float:
    """Device busy time in the window, averaged over the device planes."""
    planes = list(rows["device"].values())
    if not planes:
        return 0.0
    total = 0
    for ops in planes:
        total += sum(e - s for s, e in merge(
            ((r[1], r[1] + r[2]) for r in ops), *window))
    return total / len(planes)


def kernel_ns(rows: dict, window: Interval, names: Sequence[str]) -> float:
    """Summed device time of the operations whose name holds one of
    ``names``, inside the window, over all devices."""
    lo, hi = window
    total = 0
    for ops in rows["device"].values():
        for name, s, d, _ in ops:
            if any(k in name for k in names):
                total += max(0, min(s + d, hi) - max(s, lo))
    return float(total)


def top_ops(rows: dict, window: Interval, k: int = 10) -> List[list]:
    """The ``k`` device operations (``program/operation``) with most time
    in the window, in seconds, summed over devices."""
    lo, hi = window
    by: Dict[str, int] = {}
    for ops in rows["device"].values():
        for name, s, d, module in ops:
            t = min(s + d, hi) - max(s, lo)
            if t > 0:
                key = f"{module}/{name}"
                by[key] = by.get(key, 0) + t
    return [[n, t / 1e9] for n, t in sorted(by.items(), key=lambda x: -x[1])[:k]]


def _label(gap: Interval, host: List[list]) -> str:
    best, cover = "no benchmark span", 0
    for name, s, d in host:
        if name == WINDOW_SPAN:
            continue
        c = min(s + d, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = name[len(HOST_PREFIX):], c
    return best


def idle_gaps(rows: dict, window: Interval, k: int = 10) -> List[list]:
    """The ``k`` longest stretches of the window in which no device ran an
    operation, in seconds, each named by the host span covering most of it
    (on the first device plane)."""
    planes = list(rows["device"].values())
    if not planes:
        return []
    busy = merge(((r[1], r[1] + r[2]) for r in planes[0]), *window)
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < window[1]:
        gaps.append((t, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(g, rows["host"]), (g[1] - g[0]) / 1e9] for g in gaps[:k]]


def summarize(rows: dict) -> dict:
    """What a run reports from its trace: busy and window seconds, and the
    breakdown."""
    window = window_of(rows)
    return {"busy_s": busy_ns(rows, window) / 1e9,
            "window_s": (window[1] - window[0]) / 1e9,
            "breakdown": {"device_ops": top_ops(rows, window),
                          "idle_gaps": idle_gaps(rows, window)}}


class Capture:
    """``with Capture(dir) as cap:`` traces the block when ``dir`` is set."""

    def __init__(self, trace_dir: Optional[str]):
        self.trace_dir = trace_dir

    def __enter__(self):
        if self.trace_dir:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-Python-call events
            opts.host_tracer_level = 1       # TraceAnnotation spans kept
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.trace_dir:
            import jax

            jax.profiler.stop_trace()
        return False
