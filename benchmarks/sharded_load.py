"""repro.bus reproduction: events/s vs worker-shard count.

The Table 1 scenario (noop triggers, §6.1) run on the sharded dataplane:
events are keyed over ``subjects`` distinct trigger subjects, routed onto a
partitioned event bus, and drained by {1, 2, 4, 8} ShardWorker shards running
on their own threads.  The single-worker ``load_test.bench_noop`` figure on
the same machine (action plane on, like the shards) is reported as the
baseline; multi-shard rows also report scaling vs the 1-shard row — the
apples-to-apples number (same subjects/partitions/store), and the one the
store's lock granularity governs.

Shard throughput comes from the consumer-group fast path (exclusive
partition ownership ⇒ no per-event committed checks, O(batch) prefix commits
against short per-partition logs) plus overlapping shard batches; on
GIL-bound boxes with few cores, thread shards cannot beat the interpreter's
serial ceiling, which is what the striped-vs-global-lock contention rows
(4 shards, batch 256) isolate: same workload, only the lock granularity
changes.

``--mode=process`` runs the same workload on the multiprocess runtime
(``ProcessShardPool`` over the durable ``FilePartitionedEventStore``):
each shard is an OS process with its own interpreter, consuming and
committing through per-partition file-locked segment logs.  Unlike thread
shards (which share the publisher's in-memory mirror and never touch a
codec), every shard process pays real event deserialization — the same cost
the paper's TF-Workers pay consuming from Kafka — so the process rows
measure *scaling past the GIL net of serialization*.  The derived fields
report per-shard CPU seconds alongside wall throughput: on kernels where
multiprocess Python scales (any normal box with ≥2 cores), process shards
pass thread shards as soon as cores × per-core file throughput exceeds the
GIL ceiling; sandboxed kernels that serialize allocation-heavy processes
(gVisor-style) cap the wall-clock win regardless of core count, which the
cpu/wall split makes visible instead of hiding.
"""
from __future__ import annotations

import argparse
import shutil
import tempfile
import time
from typing import Dict, List

from repro.bus import PartitionedEventStore, ProcessShardPool
from repro.core import Triggerflow, make_trigger, termination_event

from benchmarks.load_test import bench_join, bench_noop

SHARD_COUNTS = (1, 2, 4, 8)
PROC_SHARD_COUNTS = (1, 2, 4)


def bench_sharded_noop(
    n_events: int = 100_000,
    shards: int = 4,
    partitions: int = 16,
    subjects: int = 64,
    batch_size: int = 4096,
    striped: bool = True,
) -> Dict:
    """``striped=False`` serializes every partition behind one lock — the
    pre-striping store, kept as the contention baseline.  Small
    ``batch_size`` values raise the store-call rate and make the lock
    granularity visible."""
    store = PartitionedEventStore(partitions, striped=striped)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.pool.batch_size = batch_size
    tf.pool.keep_event_log = False
    tf.create_workflow("load")
    for s in range(subjects):
        tf.add_trigger("load", make_trigger(
            f"e{s}", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"noop{s}", transient=False))
    events = [termination_event(f"e{i % subjects}", i) for i in range(n_events)]
    store.publish_batch("load", events)

    t0 = time.perf_counter()
    tf.pool.start_shards("load", shards)
    while store.lag("load") > 0:
        time.sleep(0.0005)
    dt = time.perf_counter() - t0
    tf.shutdown()
    processed = tf.pool.total_events_processed("load")
    assert processed >= n_events, (processed, n_events)
    return {"events": n_events, "seconds": dt, "events_per_s": n_events / dt,
            "shards": shards, "partitions": partitions}


def bench_proc_noop(
    n_events: int = 100_000,
    shards: int = 4,
    partitions: int = 16,
    subjects: int = 64,
    batch_size: int = 4096,
    fsync: bool = False,
    root: str = None,
) -> Dict:
    """The Table-1 noop workload on the multiprocess runtime: ``shards`` OS
    processes over the durable file-backed bus.  ``fsync=False`` is the
    Kafka-default-flush analogy (the page cache survives the SIGKILL crash
    mode; power-loss durability costs the extra fsyncs)."""
    own_root = root is None
    root = root or tempfile.mkdtemp(prefix="tf-procbench-")
    pool = ProcessShardPool(root, num_partitions=partitions,
                            batch_size=batch_size, fsync=fsync)
    pool.create_workflow("load")
    for s in range(subjects):
        pool.add_trigger("load", make_trigger(
            f"e{s}", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"noop{s}", transient=False))
    events = [termination_event(f"e{i % subjects}", i) for i in range(n_events)]
    pool.publish_batch("load", events)

    t0 = time.perf_counter()
    pool.start_shards("load", shards)
    pool.wait_drained("load", timeout=600, poll=0.02)
    dt = time.perf_counter() - t0
    stats = pool._stats("load")
    processed = sum(s.get("events_processed", 0) for s in stats.values())
    cpu = sum(s.get("cpu_seconds", 0.0) for s in stats.values())
    pool.stop_all()
    if own_root:
        shutil.rmtree(root, ignore_errors=True)
    assert processed >= n_events, (processed, n_events)
    return {"events": n_events, "seconds": dt, "events_per_s": n_events / dt,
            "shards": shards, "partitions": partitions,
            "shard_cpu_seconds": cpu}


def bench_sharded_join(
    n_triggers: int = 100,
    events_each: int = 1000,
    shards: int = 4,
    partitions: int = 16,
    batch_size: int = 4096,
    batch_plane: bool = True,
) -> Dict:
    """The Table-1 join workload on the sharded dataplane: proves the batch
    plane (grouped slices + vectorized triage) composes with partitioned
    shards — each shard triages its own partitions' share of the batch.
    ``batch_plane=False`` is the interpreter-on-shards control."""
    store = PartitionedEventStore(partitions)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.pool.batch_size = batch_size
    tf.pool.keep_event_log = False
    tf.pool.batch_plane = batch_plane
    tf.create_workflow("join")
    for t in range(n_triggers):
        tf.add_trigger("join", make_trigger(
            f"j{t}",
            condition={"name": "counter", "expected": events_each,
                       "aggregate": False},
            action={"name": "noop"}, trigger_id=f"jt{t}", transient=False))
    n_events = n_triggers * events_each
    events = [termination_event(f"j{i % n_triggers}", i) for i in range(n_events)]
    store.publish_batch("join", events)

    t0 = time.perf_counter()
    tf.pool.start_shards("join", shards)
    while store.lag("join") > 0:
        time.sleep(0.0005)
    dt = time.perf_counter() - t0
    tf.shutdown()
    fired = tf.pool.total_fires("join")
    assert fired == n_triggers, (fired, n_triggers)
    return {"events": n_events, "seconds": dt, "events_per_s": n_events / dt,
            "shards": shards, "partitions": partitions, "fired": fired}


def run(reps: int = 3, n_events: int = 100_000,
        mode: str = "all") -> List[Dict]:
    # Interleave scenarios across repetitions and keep the best events/s per
    # scenario: single-run numbers on small shared machines swing ±25% from
    # CPU steal, which would drown the architectural deltas being measured.
    #
    # Process rows are measured first: their shard processes fork from this
    # one, and the thread rows' batch-plane workers initialize JAX here (on
    # a TPU host, taking the chip) — no child may inherit that runtime.
    proc_best = (_measure_process(reps, n_events)
                 if mode in ("all", "process") else None)
    rows: List[Dict] = []
    if mode in ("all", "thread"):
        rows.extend(_run_thread(reps, n_events))
    if proc_best is not None:
        thread4 = next((r["events_per_s"] for r in rows
                        if r["name"] == "sharded_load.noop_4shard"), None)
        rows.extend(_process_rows(proc_best, thread4_noop=thread4))
    return rows


def _run_thread(reps: int, n_events: int) -> List[Dict]:
    best: Dict = {"baseline": 0.0, "contention_striped": 0.0,
                  "contention_coarse": 0.0}
    best.update({s: 0.0 for s in SHARD_COUNTS})
    for _ in range(reps):
        # baseline runs the same plane configuration as the shards (action
        # plane on), so shard-count rows measure scaling, not plane deltas
        best["baseline"] = max(
            best["baseline"],
            bench_noop(n_events, action_plane=True)["events_per_s"])
        for shards in SHARD_COUNTS:
            r = bench_sharded_noop(n_events=n_events, shards=shards)
            best[shards] = max(best[shards], r["events_per_s"])
        # store-lock contention A/B: 4 shards, small batches (high store-call
        # rate), striped per-partition locks vs the old global lock
        for key, striped in (("contention_striped", True),
                             ("contention_coarse", False)):
            r = bench_sharded_noop(n_events=n_events, shards=4,
                                   batch_size=256, striped=striped)
            best[key] = max(best[key], r["events_per_s"])

    rows = [{
        "name": "sharded_load.baseline_single_worker",
        "us_per_call": 1e6 / best["baseline"],
        "events_per_s": best["baseline"],
        "derived": f"{best['baseline']:.0f} events/s (bench_noop, best of {reps})",
    }]
    for shards in SHARD_COUNTS:
        speedup = best[shards] / best["baseline"]
        scaling = best[shards] / best[1]
        rows.append({
            "name": f"sharded_load.noop_{shards}shard",
            "us_per_call": 1e6 / best[shards],
            "events_per_s": best[shards],
            "derived": f"{best[shards]:.0f} events/s "
                       f"({speedup:.2f}x vs single worker, "
                       f"{scaling:.2f}x vs 1 shard)",
        })
    coarse = best["contention_coarse"]
    striped = best["contention_striped"]
    rows.append({
        "name": "sharded_load.noop_4shard_contention_coarse",
        "us_per_call": 1e6 / coarse,
        "events_per_s": coarse,
        "derived": f"{coarse:.0f} events/s (4 shards, batch 256, one global "
                   f"store lock)",
    })
    rows.append({
        "name": "sharded_load.noop_4shard_contention",
        "us_per_call": 1e6 / striped,
        "events_per_s": striped,
        "derived": f"{striped:.0f} events/s "
                   f"({striped / coarse:.2f}x vs global lock; 4 shards, "
                   f"batch 256, striped per-partition locks)",
    })
    # Batch plane × sharding composition: the same 4-shard deployment with
    # the interpreter vs the batch plane (the latter must not regress).
    join_interp = join_batch = 0.0
    for _ in range(reps):
        join_interp = max(join_interp,
                          bench_sharded_join(batch_plane=False)["events_per_s"])
        join_batch = max(join_batch,
                         bench_sharded_join(batch_plane=True)["events_per_s"])
    join_single = bench_join()["events_per_s"]
    rows.append({
        "name": "sharded_load.join_4shard_interpreter",
        "us_per_call": 1e6 / join_interp,
        "events_per_s": join_interp,
        "derived": f"{join_interp:.0f} events/s (per-event interpreter on "
                   f"4 shards)",
    })
    rows.append({
        "name": "sharded_load.join_4shard",
        "us_per_call": 1e6 / join_batch,
        "events_per_s": join_batch,
        "derived": f"{join_batch:.0f} events/s "
                   f"({join_batch / join_interp:.2f}x vs interpreter shards, "
                   f"{join_batch / join_single:.2f}x vs 1 batch-plane worker)",
    })
    return rows


def _measure_process(reps: int, n_events: int) -> Dict[int, Dict]:
    """Best-of-``reps`` process-mode run per shard count: the same noop
    workload on ``ProcessShardPool`` over the durable file bus."""
    best: Dict[int, Dict] = {}
    for _ in range(reps):
        for shards in PROC_SHARD_COUNTS:
            r = bench_proc_noop(n_events=n_events, shards=shards)
            if shards not in best or r["events_per_s"] > best[shards]["events_per_s"]:
                best[shards] = r
    return best


def _process_rows(best: Dict[int, Dict],
                  thread4_noop: float = None) -> List[Dict]:
    """Process-mode rows.  Reports wall events/s, the ratio against the
    threaded 4-shard row (when available), per-count scaling vs 1 process,
    and the aggregate shard-CPU seconds (cpu ≈ wall·shards ⇒ the kernel ran
    the processes in parallel; cpu ≈ wall ⇒ it serialized them)."""
    rows: List[Dict] = []
    base = best[PROC_SHARD_COUNTS[0]]["events_per_s"]
    for shards in PROC_SHARD_COUNTS:
        r = best[shards]
        eps = r["events_per_s"]
        vs_thread = (f", {eps / thread4_noop:.2f}x vs threaded 4-shard"
                     if thread4_noop else "")
        rows.append({
            "name": f"sharded_load.noop_{shards}proc_file",
            "us_per_call": 1e6 / eps,
            "events_per_s": eps,
            "derived": f"{eps:.0f} events/s ({shards} shard processes over "
                       f"the durable file bus; {eps / base:.2f}x vs 1 process"
                       f"{vs_thread}; shard-cpu {r['shard_cpu_seconds']:.2f}s "
                       f"over {r['seconds']:.2f}s wall)",
        })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("thread", "process", "all"),
                    default="all",
                    help="thread: ShardedWorkerPool over the in-memory bus; "
                         "process: ProcessShardPool over the file bus")
    ap.add_argument("--events", type=int, default=100_000)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    for row in run(reps=args.reps, n_events=args.events, mode=args.mode):
        print(f"{row['name']},{row['us_per_call']:.2f},\"{row['derived']}\"")
