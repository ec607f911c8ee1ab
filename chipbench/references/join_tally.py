"""Plain tally of a counter join: what every trigger must have done after
a stream of events, under the configuration's guarantee that each trigger
fires exactly once per ``expected`` of its events (resetting after each
fire) and that every published event is counted exactly once.

It imports nothing of the program under test.
"""
from __future__ import annotations

from typing import List

import numpy as np


def tally(subjects: np.ndarray, results: np.ndarray, n_triggers: int,
          expected: int) -> List[dict]:
    """Per trigger: its fires, the count and results of its open round, and
    the results of its last full round (``None`` before the first fire)."""
    out = []
    for t in range(n_triggers):
        mine = results[subjects == t].tolist()
        fires, count = divmod(len(mine), expected)
        out.append({
            "fires": fires,
            "count": count,
            "results": mine[len(mine) - count:],
            "fired_results": (mine[(fires - 1) * expected: fires * expected]
                              if fires else None),
        })
    return out


def same_context(ctx: dict, want: dict) -> bool:
    """Whether a trigger's context holds what the tally says it must."""
    return (ctx.get("count", 0) == want["count"]
            and list(ctx.get("results") or []) == want["results"]
            and (want["fired_results"] is None
                 or list(ctx.get("fired_results") or []) == want["fired_results"]))
