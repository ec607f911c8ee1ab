"""Fig 8 on the sharded runtimes: pool-agnostic scale 0→N→0.

The original ``benchmarks/autoscaling.py`` reproduces Fig 8 in *classic*
mode (one TF-Worker per workflow over the unpartitioned in-memory store).
This module reproduces it on the **sharded** runtimes through the
``ScalablePool`` protocol, with the identical driver for both substrates:

* ``--mode=thread`` — ``ShardedWorkerPool`` shards (threads, in-memory bus),
* ``--mode=process`` — ``ProcessShardPool`` shard *processes* over the
  durable file bus: the paper's KEDA/Knative container-per-worker shape.
  Scale-to-zero here means zero OS processes, and scale-up re-forks them.

Workload: a burst is published into a drained, zero-shard deployment; the
``KedaAutoscaler`` scales 0→N (lag-proportional), the shards drain the
stream, idle out within the grace period, and are reaped back to 0; a second
burst then re-scales from zero.  The recorded ``timeline`` of
``(t, active_shards, total_lag)`` samples is the figure's data; the derived
fields pin the headline numbers (peak shards, seconds from drain to zero).

``idle_stats`` measures what an *idle* autoscaler tick costs on the file
bus: stat calls per ``lag()`` poll at two partition widths — the
publish-notify gate keeps it at exactly one, independent of partitions.
"""
from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

from repro.bus import FilePartitionedEventStore, PartitionedEventStore, ProcessShardPool
from repro.core import KedaAutoscaler, Triggerflow, make_trigger, termination_event


def _deployment(mode: str, root: Optional[str], subjects: int,
                partitions: int, batch_size: int) -> Triggerflow:
    if mode == "thread":
        tf = Triggerflow(event_store=PartitionedEventStore(partitions),
                         inline_functions=True, commit_policy="every_batch")
        tf.pool.batch_size = batch_size
        tf.pool.keep_event_log = False
        tf.create_workflow("load")
    else:
        pool = ProcessShardPool(root, num_partitions=partitions,
                                batch_size=batch_size, fsync=False)
        pool.create_workflow("load")
        tf = Triggerflow(pool=pool)
    for s in range(subjects):
        tf.add_trigger("load", make_trigger(
            f"e{s}", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"noop{s}", transient=False))
    return tf


def _wait(cond, timeout: float, msg: str, poll: float = 0.01) -> float:
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(msg)
        time.sleep(poll)
    return time.monotonic()


def bench_fig8(
    mode: str = "thread",
    n_events: int = 60_000,
    subjects: int = 32,
    partitions: int = 8,
    batch_size: int = 2048,
    events_per_shard: int = 2_000,
    max_shards: int = 4,
    grace: float = 0.25,
    poll: float = 0.02,
    root: Optional[str] = None,
) -> Dict:
    """One full Fig-8 cycle pair: burst → 0→N→0, second burst → re-scale.

    Returns the benchmark row, including the sampled timeline.  Asserts the
    two headline claims: lag-proportional scale-up reached ≥ 2 shards, and
    live shards decayed to zero within ~one grace period of the drain."""
    own_root = mode == "process" and root is None
    if own_root:
        root = tempfile.mkdtemp(prefix="tf-autoscale-")
    tf = _deployment(mode, root, subjects, partitions, batch_size)
    store = tf.event_store
    scaler = KedaAutoscaler(tf, poll_interval=poll, grace_period=grace,
                            events_per_shard=events_per_shard,
                            max_shards_per_workflow=max_shards)
    second = n_events // 2
    t0 = time.monotonic()
    scaler.start()
    try:
        zero_after: List[float] = []
        for phase, count, base in (("first", n_events, 0),
                                   ("second", second, n_events)):
            store.publish_batch("load", [
                termination_event(f"e{i % subjects}", base + i)
                for i in range(count)])
            t_drain = _wait(lambda: store.lag("load") == 0, 120,
                            f"{phase} burst did not drain")
            t_zero = _wait(lambda: scaler.active_workers == 0, 60,
                           f"no scale-to-zero after the {phase} burst")
            zero_after.append(t_zero - t_drain)
        # let the scaler's own ticks retire every departed shard before the
        # run closes — calling reap() from here would steal the departures
        # from the scaler's scale_downs accounting.  (scale_ups can still
        # legitimately exceed scale_downs by a hair: an idle thread-shard
        # *task* rescheduled by a later tick before reap() saw it counts as
        # a fresh scale-up but departs only once.)
        pool = tf.pool
        _wait(lambda: pool.shard_count("load") == 0, 10,
              "not every departed shard was reaped")
        wall = time.monotonic() - t0
    finally:
        scaler.stop()
        tf.shutdown()
        if own_root:
            shutil.rmtree(root, ignore_errors=True)
    peak = max(w for _, w, _ in scaler.timeline)
    zeros = sum(1 for _, w, _ in scaler.timeline if w == 0)
    total = n_events + second
    assert peak >= 2, f"lag-proportional scale-up never reached 2 (peak={peak})"
    # "within one grace period of drain", plus control-loop ticks and a
    # constant for process teardown under CPU steal (the derived row carries
    # the exact measurement; this assert only bounds gross regressions)
    slack = grace + 6 * poll + 0.6
    assert max(zero_after) <= slack, \
        f"scale-to-zero took {max(zero_after):.2f}s (grace={grace}s)"
    unit = "shard processes" if mode == "process" else "thread shards"
    return {
        "name": f"autoscale.fig8_{mode}",
        "us_per_call": wall / total * 1e6,
        "events_per_s": total / wall,
        "derived": (
            f"0->{peak}->0 {unit} twice over {total} events "
            f"(lag-proportional, cap {max_shards}); drain->zero in "
            f"{zero_after[0]:.2f}s/{zero_after[1]:.2f}s (grace {grace}s); "
            f"scale_ups={scaler.scale_ups} scale_downs={scaler.scale_downs} "
            f"restarts={scaler.restarts} zero_samples={zeros} "
            f"wall={wall:.1f}s"),
        "timeline": [(round(t, 3), w, lag) for t, w, lag in scaler.timeline],
    }


def bench_idle_tick_stats(polls: int = 200,
                          widths: tuple = (8, 64)) -> Dict:
    """Stat calls per idle autoscaler lag poll on the file bus, at two
    partition widths.  The publish-notify gate makes the answer 1 regardless
    of width — without it every poll pays O(partitions) probes."""
    per_width: Dict[int, float] = {}
    real_getsize = os.path.getsize
    for partitions in widths:
        root = tempfile.mkdtemp(prefix="tf-idlestat-")
        try:
            store = FilePartitionedEventStore(root, partitions, fsync=False)
            store.create_stream("load")
            evs = [termination_event(f"e{i}", i) for i in range(64)]
            store.publish_batch("load", evs)
            store.commit("load", [e.id for e in evs])
            assert store.lag("load") == 0  # observe + cache the drained state
            calls = [0]

            def counting(path, _c=calls, _r=real_getsize):
                _c[0] += 1
                return _r(path)

            os.path.getsize = counting
            try:
                for _ in range(polls):
                    assert store.lag("load") == 0
            finally:
                os.path.getsize = real_getsize
            per_width[partitions] = calls[0] / polls
        finally:
            shutil.rmtree(root, ignore_errors=True)
    flat = max(per_width.values())
    assert flat <= 1.5, f"idle lag poll is not O(1): {per_width}"
    detail = ", ".join(f"{v:.2f} @ {k} partitions"
                       for k, v in sorted(per_width.items()))
    return {
        "name": "autoscale.idle_tick_stats",
        "us_per_call": 0.0,
        "derived": (f"stat calls per idle lag() poll: {detail} — "
                    f"publish-notify-gated, flat in partition count"),
    }


def run(mode: str = "all") -> List[Dict]:
    # The process cycle runs first: it forks shard processes from this one,
    # and the thread cycle's batch-plane workers initialize JAX here (on a
    # TPU host, taking the chip) — no child may inherit that runtime.
    rows: List[Dict] = []
    if mode in ("all", "process"):
        rows.append(bench_fig8(
            "process", n_events=20_000, subjects=16, partitions=4,
            batch_size=1024, events_per_shard=2_000, max_shards=2,
            grace=0.5, poll=0.05))
    if mode in ("all", "thread"):
        rows.append(bench_fig8("thread"))
    rows.append(bench_idle_tick_stats())
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("thread", "process", "all"),
                    default="all")
    args = ap.parse_args()
    for row in run(mode=args.mode):
        print(f"{row['name']},{row['us_per_call']:.2f},\"{row['derived']}\"")
