"""The Table-1 join deployment under open-loop event traffic.

Set-up builds the deployment that the configuration file states (the
``Triggerflow`` facade over the in-memory partitioned bus, threaded shards,
the batch plane with the join backend that ``auto`` resolves), registers
one ``counter`` trigger per map stage whose action, the benchmark's own,
stamps each fire with its trigger and the time, and then runs the cell's
own traffic for ``warmup_s`` seconds.  The window publishes the schedule
through ``event_store.publish_batch`` at each event's due time.

Each fire is timed from the due time of the event that completes its
round.  After the window the run waits, a minute at most, for the bus to
drain, and compares every trigger's fires and final context with the
plain tally of the events published.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List

import numpy as np

from . import openloop, traffic as tr
from .trace import WINDOW_SPAN

WORKFLOW = "join"
DRAIN_S = 60.0


class FireLog:
    """Fires in the order they ran: (trigger index, ``perf_counter``)."""

    def __init__(self):
        self.fires: List[tuple] = []
        self._lock = threading.Lock()

    def action(self, ctx, event, params) -> None:
        t = time.perf_counter()
        with self._lock:
            self.fires.append((params["index"], t))

    def batched(self, ctx, events, params) -> None:
        t = time.perf_counter()
        with self._lock:
            self.fires.extend((params["index"], t) for _ in events)


def build(cfg: dict, log: FireLog):
    """The deployment of ``cfg`` with one stamped trigger per map stage."""
    from repro.core import Triggerflow, make_trigger, register_action

    action = f"chipbench.stamp.{id(log):x}"
    register_action(action, log.action, batched=log.batched)
    dep = cfg["deployment"]
    tf = Triggerflow(num_shards=dep["num_shards"],
                     num_partitions=dep["num_partitions"],
                     inline_functions=True,
                     commit_policy=dep["commit_policy"])
    tf.pool.batch_plane = dep["batch_plane"]
    tf.pool.keep_event_log = dep["keep_event_log"]
    tf.create_workflow(WORKFLOW)
    n, expected = cfg["triggers"], cfg["expected"]
    for t in range(n):
        tf.add_trigger(WORKFLOW, make_trigger(
            f"j{t}",
            condition={"name": "counter", "expected": expected,
                       "reset_on_fire": cfg["reset_on_fire"]},
            action={"name": action, "index": t},
            trigger_id=f"jt{t}", transient=False))
    return tf


def record_join_calls(calls: list):
    """Note ``(time, events, trigger rows)`` of every call the vector join
    plane makes into the join kernel; returns the undo.  Planes built after
    this call pick it up."""
    import jax

    from repro.kernels.event_join import dispatch

    inner = dispatch.join_counts_segments

    def noted(lens, counts, expected, fn=None):
        calls.append((time.perf_counter(), int(np.sum(lens)), len(lens)))
        with jax.profiler.TraceAnnotation("chipbench.join_call"):
            return inner(lens, counts, expected, fn)

    dispatch.join_counts_segments = noted
    return lambda: setattr(dispatch, "join_counts_segments", inner)


def _committed(tf, published: int) -> int:
    return published - tf.event_store.lag(WORKFLOW)


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir, on_window_open, read_device, control: bool = False) -> dict:
    """One run of the cell; ``on_window_open()`` returns the set-up time and
    ``read_device()`` the device line.  ``control`` also reads the control:
    the tally put in the program's place with the exactly-once guarantee
    broken, one event of the window delivered twice."""
    import jax

    from repro.core import termination_event
    from repro.kernels.event_join.ops import event_join

    from . import trace as trace_mod
    from ..references import join_tally

    sched = tr.schedule(traffic, seed, seconds)
    due = sched.due
    subj = sched.attrs["subject"]
    res = tr.rng_for(seed, "results").integers(0, 1 << 30, due.size,
                                               dtype=np.int64)
    names = [f"j{t}" for t in range(cfg["triggers"])]
    subj_l, res_l = subj.tolist(), res.tolist()

    log = FireLog()
    calls: list = []
    undo = record_join_calls(calls) if trace_dir else None
    tf = build(cfg, log)
    tf.start_shards(WORKFLOW)
    out: Dict = {"cell_kind": "join"}
    state = {"published": 0}

    def send(i: int, j: int) -> None:
        with jax.profiler.TraceAnnotation("chipbench.publish"):
            tf.event_store.publish_batch(WORKFLOW, [
                termination_event(names[s], r)
                for s, r in zip(subj_l[i:j], res_l[i:j])])
        state["published"] = j

    window_span = []    # made as the window opens: only then is the trace on
    capture = trace_mod.Capture(trace_dir)

    def start_trace():
        capture.__enter__()

    def open_window():
        out["setup_s"] = on_window_open()
        out["snap0"] = tf.metrics_snapshot(WORKFLOW)
        out["cache0"] = event_join._cache_size()
        out["committed0"] = _committed(tf, state["published"])
        out["t_open"] = time.perf_counter()
        window_span.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        window_span[0].__enter__()

    def close_window():
        window_span[0].__exit__(None, None, None)
        out["t_close"] = time.perf_counter()
        out["committed1"] = _committed(tf, state["published"])
        out["backlog_at_close"] = tf.event_store.lag(WORKFLOW)
        out["snap1"] = tf.metrics_snapshot(WORKFLOW)
        out["cache1"] = event_join._cache_size()

    t_base = time.perf_counter() + float(traffic.get("warmup_s", 0.0))
    marks = {0.0: open_window, float(seconds): close_window}
    if trace_dir:
        marks[-0.5] = start_trace
    try:
        late = openloop.drive(due, send, t_base, marks,
                              until=float(seconds) + 1e-9)
        if trace_dir:
            capture.__exit__(None, None, None)
        deadline = time.monotonic() + DRAIN_S
        while tf.event_store.lag(WORKFLOW) > 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        undrained = tf.event_store.lag(WORKFLOW)
        snap_end = tf.metrics_snapshot(WORKFLOW)
        contexts = [tf.get_trigger_context(WORKFLOW, f"jt{t}")
                    for t in range(cfg["triggers"])]
        out["device"] = read_device()
    finally:
        tf.shutdown()
        if undo is not None:
            undo()

    published = state["published"]
    window = (due >= 0) & (due < seconds) & np.isfinite(late)
    out["late_s"] = late[window]
    out["published"] = published
    out["backends"] = sorted(g[len("tf_join_backend_"):]
                             for g in snap_end["gauges"]
                             if g.startswith("tf_join_backend_"))

    # -- fires: the k-th fire of a trigger closes its k-th round ------------
    expected = cfg["expected"]
    fired: Dict[int, List[float]] = {}
    for t, when in log.fires:
        fired.setdefault(t, []).append(when)
    lat, attempted, missing = [], 0, 0
    for t in range(cfg["triggers"]):
        idx = np.flatnonzero(subj[:published] == t)
        closing = idx[expected - 1::expected]
        times = fired.get(t, [])
        for k, e in enumerate(closing):
            if not 0.0 <= due[e] < seconds:
                continue
            attempted += 1
            if k < len(times):
                lat.append(times[k] - (t_base + due[e]))
            else:
                missing += 1
    out["fire_latency_s"] = np.asarray(lat)
    out["attempted"] = attempted
    out["failed"] = missing

    # -- the comparison with the plain tally ---------------------------------
    want = join_tally.tally(subj[:published], res[:published],
                            cfg["triggers"], expected)
    fire_diff = sum(abs(len(fired.get(t, [])) - w["fires"])
                    for t, w in enumerate(want))
    ctx_diff = sum(not join_tally.same_context(c, w)
                   for c, w in zip(contexts, want))
    out["checks"] = [
        {"name": "undrained_events", "value": int(undrained), "limit": 0},
        {"name": "fire_count_diff", "value": int(fire_diff), "limit": 0},
        {"name": "context_diff", "value": int(ctx_diff), "limit": 0},
    ]
    if control:
        win = np.flatnonzero(window)
        order = np.r_[np.arange(published), win[len(win) // 3]]
        got = join_tally.tally(subj[order], res[order], cfg["triggers"],
                               expected)
        out["control_checks"] = [
            {"name": "fire_count_diff", "limit": 0, "value": sum(
                abs(g["fires"] - w["fires"]) for g, w in zip(got, want))},
            {"name": "context_diff", "limit": 0, "value": sum(
                not join_tally.same_context(g, w) for g, w in zip(got, want))}]
    window_s = out["t_close"] - out["t_open"]
    out["window_s"] = window_s
    out["join_calls"] = [(n, t) for when, n, t in calls
                         if out["t_open"] <= when < out["t_close"]]
    out["e2e"] = {
        "fire_latency_p95_ms": (float(np.percentile(out["fire_latency_s"], 95))
                                * 1e3 if lat else None),
        "events_per_s": (out["committed1"] - out["committed0"]) / window_s,
    }
    print(f"join: backend {','.join(out['backends'])}; {published} events "
          f"published, {len(log.fires)} fires, {attempted} fires due in the "
          f"window ({missing} missing); kernel shapes {out['cache0']} -> "
          f"{out['cache1']} across the window", file=sys.stderr)
    if lat:
        print("join: fire latency ms p50 {} p95 {} p99 {} max {}; {} events "
              "on the bus as the window closed".format(
                  *np.percentile(np.asarray(lat) * 1e3, [50, 95, 99, 100]),
                  out["backlog_at_close"]), file=sys.stderr)
    return out
