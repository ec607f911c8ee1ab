"""Open-loop pacing: send each item at its due time, whatever the system
does, and record how far behind schedule each one went out."""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import numpy as np


def drive(due: np.ndarray, send: Callable[[int, int], None], t_base: float,
          marks: Dict[float, Callable[[], None]], until: float,
          done: Optional[Callable[[], bool]] = None,
          tick: float = 0.001) -> np.ndarray:
    """Send items ``[i, j)`` as their due times (seconds after ``t_base`` on
    ``time.perf_counter``) pass, and run each mark once as its time passes.

    Stops when every item is sent, or at ``until``, or once ``done()`` says
    so after the last mark.  Returns each item's lateness in seconds (NaN
    for items never sent)."""
    n = due.size
    late = np.full(n, np.nan)
    pending = sorted(marks.items())
    due_l = due.tolist()
    i = 0
    while True:
        now = time.perf_counter() - t_base
        while pending and pending[0][0] <= now:
            pending.pop(0)[1]()
            now = time.perf_counter() - t_base
        if now >= until or (not pending and done is not None and done()):
            break
        j = int(np.searchsorted(due, now, side="right"))
        if j > i:
            send(i, j)
            late[i:j] = (time.perf_counter() - t_base) - due[i:j]
            i = j
            continue
        if i >= n and not pending and done is None:
            break
        nxt = min(due_l[i] if i < n else until,
                  pending[0][0] if pending else until, until)
        time.sleep(min(max(nxt - now, 0.0), tick))
    return late
