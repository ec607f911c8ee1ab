"""What the per-layer readers share: window deltas of the program's
histograms and counters, and the trace's shares.  A reader that finds
nothing to read returns ``None``."""
from __future__ import annotations

from typing import Optional


def hist_delta(run: dict, name: str) -> Optional[tuple]:
    """``(sum, count)`` a histogram gained across the window."""
    if "snap0" not in run:
        return None
    h0 = run["snap0"]["histograms"].get(name, {"sum": 0.0, "count": 0})
    h1 = run["snap1"]["histograms"].get(name)
    if h1 is None or h1["count"] - h0["count"] <= 0:
        return None
    return h1["sum"] - h0["sum"], h1["count"] - h0["count"]


def hist_mean(run: dict, name: str, scale: float) -> Optional[float]:
    d = hist_delta(run, name)
    return None if d is None else d[0] / d[1] * scale


def counter_delta(run: dict, name: str) -> Optional[int]:
    if "snap0" not in run:
        return None
    return (run["snap1"]["counters"].get(name, 0)
            - run["snap0"]["counters"].get(name, 0))


def idle_share_pct(run: dict) -> Optional[float]:
    """100 x (1 - device busy / window), from the trace."""
    t = run.get("trace")
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
