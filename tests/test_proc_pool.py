"""Multiprocess shard runtime (repro.bus.proc) + the pool/worker bugfix sweep.

Covers:
* thread/process parity on the noop and join workloads (same fires, same
  committed counts, same contexts — only the concurrency substrate differs),
* real SIGKILL crash recovery over the durable file-backed bus: exactly-once
  committed results after a mid-stream kill + replacement (§3.4 / Fig 13),
* torn segment-log tails (crash mid-append): readers stop before them,
  the next locked writer truncates them, nothing acknowledged is lost,
* DLQ quarantine + redrive across the process boundary,
* per-scope state-store delta logs (concurrent writer processes) + the
  compaction/ownership-boundary fold,
* regression tests for the pool/worker bugfixes: crash_shard discards the
  in-flight commit (redelivery at the crash point), reap() counts departures
  by recorded reason (idle ≠ crash), the scalar worker skips per-event
  is_committed on UNCOMMITTED_ONLY stores, and a shard whose batch raises
  releases its partitions immediately instead of stalling them.
"""
import os
import time

from repro.bus import (FilePartitionedEventStore, PartitionedEventStore,
                       ProcessShardPool)
from repro.core import Trigger, Triggerflow, make_trigger, termination_event
from repro.core.actions import ACTIONS, register_action
from repro.core.eventstore import MemoryEventStore, SegmentLog
from repro.core.functions import FunctionBackend
from repro.core.statestore import FileStateStore, MemoryStateStore
from repro.core.worker import TFWorker


def _noop_triggers(n):
    return [make_trigger(f"s{i}", condition={"name": "true"},
                         action={"name": "noop"}, trigger_id=f"t{i}",
                         transient=False) for i in range(n)]


def _proc_pool(tmp_path, **kw):
    kw.setdefault("num_partitions", 8)
    kw.setdefault("batch_size", 256)
    return ProcessShardPool(str(tmp_path / "pool"), **kw)


# -- thread/process parity -------------------------------------------------------

def test_thread_process_parity_noop(tmp_path):
    n_events, n_subj = 2000, 8
    events = [termination_event(f"s{i % n_subj}", i) for i in range(n_events)]

    pool = _proc_pool(tmp_path)
    pool.create_workflow("w")
    for trg in _noop_triggers(n_subj):
        pool.add_trigger("w", trg)
    pool.publish_batch("w", events)
    pool.start_shards("w", 2)
    pool.wait_drained("w", timeout=60)
    proc_fires = pool.total_fires("w")
    proc_committed = len(pool.event_store.committed_events("w"))
    offsets = pool.metrics("w")["commit_offsets"]
    pool.stop_all()

    store = PartitionedEventStore(8)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    for trg in _noop_triggers(n_subj):
        tf.add_trigger("w", trg)
    store.publish_batch("w", events)
    tf.pool.set_shard_count("w", 2)
    tf.pool.drive("w", timeout=30)
    thread_fires = tf.pool.total_fires("w")
    tf.shutdown()

    assert proc_fires == thread_fires == n_events
    assert proc_committed == n_events
    assert sum(offsets) == n_events


def test_thread_process_parity_join(tmp_path):
    n_subj, per_subj = 8, 50
    events = [termination_event(f"s{i % n_subj}", i)
              for i in range(n_subj * per_subj)]

    def join_triggers():
        return [make_trigger(
            f"s{i}", condition={"name": "counter", "expected": per_subj,
                                "aggregate": False},
            action={"name": "noop"}, trigger_id=f"t{i}", transient=False)
            for i in range(n_subj)]

    pool = _proc_pool(tmp_path)
    pool.create_workflow("w")
    for trg in join_triggers():
        pool.add_trigger("w", trg)
    pool.publish_batch("w", events)
    pool.start_shards("w", 2)
    pool.wait_drained("w", timeout=60)
    proc_fires = pool.total_fires("w")
    proc_ctx = {i: pool.trigger_context("w", f"t{i}") for i in range(n_subj)}
    pool.stop_all()

    store = PartitionedEventStore(8)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    for trg in join_triggers():
        tf.add_trigger("w", trg)
    store.publish_batch("w", events)
    tf.pool.set_shard_count("w", 2)
    tf.pool.drive("w", timeout=30)
    thread_fires = tf.pool.total_fires("w")
    thread_ctx = {i: tf.pool.trigger_context("w", f"t{i}")
                  for i in range(n_subj)}
    tf.shutdown()

    assert proc_fires == thread_fires == n_subj  # each join fired exactly once
    for i in range(n_subj):
        assert proc_ctx[i].get("count") == per_subj == thread_ctx[i].get("count")


def _register_jax_probe(backend):
    """Shard-process init: an action that records whether the process has
    imported JAX by the time its join fires."""
    import sys

    def probe(ctx, event, params):
        ctx["jax_imported"] = "jax" in sys.modules

    register_action("jax_probe", probe)


def test_shard_process_joins_on_host_path_without_jax(tmp_path):
    """A shard process owns no device: its vector join plane runs the numpy
    backend, says so in the scrape, and never imports JAX (a spawned child
    starts from a fresh interpreter, so nothing is inherited).  Each
    partition interleaves eight joins over several batches, so the plane
    claims the batches in which none can fire."""
    n_subj, per_subj = 64, 100
    pool = _proc_pool(tmp_path, start_method="spawn",
                      child_init=_register_jax_probe)
    pool.create_workflow("w")
    for i in range(n_subj):
        pool.add_trigger("w", make_trigger(
            f"s{i}", condition={"name": "counter", "expected": per_subj,
                                "aggregate": False},
            action={"name": "jax_probe"}, trigger_id=f"t{i}",
            transient=False))
    pool.publish_batch("w", [termination_event(f"s{i % n_subj}", i)
                             for i in range(n_subj * per_subj)])
    pool.start_shards("w", 2)
    pool.wait_drained("w", timeout=60)
    obs = pool.obs_snapshot("w")
    ctxs = [pool.trigger_context("w", f"t{i}") for i in range(n_subj)]
    pool.stop_all()

    assert obs["gauges"].get("tf_join_backend_numpy") == 2, obs["gauges"]
    assert obs["counters"]["tf_join_calls_total"] > 0
    assert [c.get("jax_imported") for c in ctxs] == [False] * n_subj


# -- crash recovery over the durable bus -----------------------------------------

def test_sigkill_crash_recovery_exactly_once(tmp_path):
    """SIGKILL a shard process mid-stream; a replacement recovers from disk.
    No committed event is lost or duplicated, and the exactly_once join
    counters end exact despite redelivery across the kill point."""
    n_subj, per_subj = 8, 300
    pool = _proc_pool(tmp_path, batch_size=64)
    pool.create_workflow("w")
    for i in range(n_subj):
        pool.add_trigger("w", make_trigger(
            f"s{i}", condition={"name": "counter", "expected": per_subj,
                                "aggregate": False, "exactly_once": True},
            action={"name": "noop"}, trigger_id=f"t{i}", transient=False))
    events = [termination_event(f"s{i % n_subj}", i)
              for i in range(n_subj * per_subj)]
    pool.publish_batch("w", events)
    members = pool.start_shards("w", 2)
    # kill one shard while the stream is actively draining (mid-batch from
    # the victim's point of view: whatever it had not committed is redone)
    deadline = time.monotonic() + 60
    total = n_subj * per_subj
    while pool.lag("w") > total * 0.6:
        assert time.monotonic() < deadline, "stream never started draining"
        time.sleep(0.002)
    pool.crash_shard("w", members[0])
    assert pool.shard_count("w") == 1
    assert pool.metrics("w")["crashes"] == 1
    pool.start_shards("w", 2)  # replacement recovers state from disk
    pool.wait_drained("w", timeout=60)
    committed = pool.event_store.committed_events("w")
    ids = [e.id for e in committed]
    assert len(ids) == len(set(ids)) == total  # no loss, no double commit
    for i in range(n_subj):
        assert pool.trigger_context("w", f"t{i}").get("count") == per_subj
    pool.stop_all()


def test_restarted_pool_recovers_from_disk(tmp_path):
    """A brand-new pool over an existing root resumes where the old one
    stopped: trigger defs, checkpoints and uncommitted events all on disk."""
    root = tmp_path / "pool"
    pool = ProcessShardPool(str(root), num_partitions=8, batch_size=64)
    pool.create_workflow("w")
    pool.add_trigger("w", make_trigger(
        "s0", condition={"name": "counter", "expected": 100,
                         "aggregate": False, "exactly_once": True},
        action={"name": "noop"}, trigger_id="t0", transient=False))
    pool.publish_batch("w", [termination_event("s0", i) for i in range(60)])
    pool.start_shards("w", 1)
    pool.wait_drained("w", timeout=60)
    pool.stop_all()  # graceful: everything checkpointed + committed

    pool2 = ProcessShardPool(str(root), num_partitions=8, batch_size=64)
    pool2.publish_batch("w", [termination_event("s0", 60 + i)
                              for i in range(40)])
    pool2.start_shards("w", 1)
    pool2.wait_drained("w", timeout=60)
    assert pool2.trigger_context("w", "t0").get("count") == 100
    assert pool2.total_fires("w") >= 1
    pool2.stop_all()
    # stop -> start on the SAME pool: stopped members must have left the
    # group, or the new shards would share partitions with dead members
    # and the workflow would stall (regression)
    pool2.publish_batch("w", [termination_event("s0", 100 + i)
                              for i in range(10)])
    members = pool2.start_shards("w", 2)
    assert len(members) == 2
    assert set(pool2._wfs["w"].group.members()) == set(members)
    pool2.wait_drained("w", timeout=60)
    pool2.stop_all()


# -- torn segment tails ----------------------------------------------------------

def test_torn_log_tail_repair(tmp_path):
    store = FilePartitionedEventStore(str(tmp_path / "bus"), 4)
    store.create_stream("w")
    evs = [termination_event(f"s{i}", i) for i in range(8)]
    store.publish_batch("w", evs)
    p = store.partition_for("s0")
    log_path = os.path.join(str(tmp_path / "bus"), "w", "p%04d.log" % p)
    with open(log_path, "ab") as f:
        # crash mid-append: a record whose length prefix promises more
        # bytes than ever hit the disk
        f.write(b"\x63torn-frame")
    # a fresh instance (reader) sees only the acknowledged events
    reader = FilePartitionedEventStore(str(tmp_path / "bus"), 4)
    assert {e.id for e in reader.consume("w", 100)} == {e.id for e in evs}
    # the next locked writer truncates the torn tail before appending
    extra = termination_event("s0", 99)
    reader.publish("w", extra)
    with open(log_path, "rb") as f:
        content = f.read()
    assert b"torn-frame" not in content
    got = {e.id for e in reader.consume("w", 100)}
    assert got == {e.id for e in evs} | {extra.id}
    # and the original instance also converges
    assert store.lag("w") == 9


def test_torn_committed_tail_means_uncommitted(tmp_path):
    """A torn committed-offset line was never acknowledged: after recovery
    the events stay pending and are redelivered (at-least-once, §3.4)."""
    root = str(tmp_path / "bus")
    store = FilePartitionedEventStore(root, 2)
    store.create_stream("w")
    evs = [termination_event("s0", i) for i in range(4)]
    store.publish_batch("w", evs)
    p = store.partition_for("s0")
    store.commit_partitions("w", [p], [evs[0].id])
    com_path = os.path.join(root, "w", "p%04d.committed" % p)
    with open(com_path, "a") as f:
        f.write(evs[1].id)  # torn: no newline — commit never acknowledged
    fresh = FilePartitionedEventStore(root, 2)
    pending = {e.id for e in fresh.consume("w", 100)}
    assert evs[0].id not in pending          # acknowledged commit holds
    assert {e.id for e in evs[1:]} <= pending  # torn commit is redelivered
    assert fresh.lag("w") == 3


def test_segmentlog_scan_and_repair(tmp_path):
    seg = SegmentLog(str(tmp_path / "seg.jsonl"))
    seg.append(['{"a": 1}', '{"b": 2}'])
    with open(seg.path, "a") as f:
        f.write('{"c": ')
    import json
    records, valid = seg.scan(json.loads)
    assert records == [{"a": 1}, {"b": 2}]
    assert valid < seg.size()
    records2, size2 = seg.repair(json.loads)
    assert records2 == records
    assert seg.size() == valid == size2
    seg.append(['{"c": 3}'])
    assert seg.scan(json.loads)[0] == [{"a": 1}, {"b": 2}, {"c": 3}]


def test_failed_batch_shard_process_is_reaped_and_rebalanced(tmp_path):
    """A shard process whose batch raises out of run_once dies with a
    non-zero exit; the drain loop reaps it and its partitions (including
    the poison event, now defused) rebalance to survivors — no silent
    stall."""
    marker = tmp_path / "died.once"

    def die_once(ctx, event, params):
        if not marker.exists():
            marker.write_text("x")
            raise SystemExit(3)  # BaseException: escapes the worker's guards

    register_action("die_once", die_once)
    try:
        pool = _proc_pool(tmp_path)
        pool.create_workflow("w")
        for trg in _noop_triggers(8):
            pool.add_trigger("w", trg)
        pool.add_trigger("w", make_trigger(
            "poison", condition={"name": "true"}, action={"name": "die_once"},
            trigger_id="tp", transient=False))
        pool.start_shards("w", 2)   # fork AFTER registration: children inherit
        events = [termination_event(f"s{i % 8}", i) for i in range(200)]
        events.append(termination_event("poison", -1))
        pool.publish_batch("w", events)
        pool.wait_drained("w", timeout=60)
        assert marker.exists()
        assert pool.metrics("w")["crashes"] >= 1
        committed = pool.event_store.committed_events("w")
        ids = [e.id for e in committed]
        assert len(ids) == len(set(ids)) == len(events)
        pool.stop_all()
    finally:
        ACTIONS.pop("die_once", None)


# -- DLQ across processes --------------------------------------------------------

def test_proc_dlq_redrive_after_reenable(tmp_path):
    pool = _proc_pool(tmp_path)
    pool.create_workflow("w")
    pool.add_trigger("w", make_trigger(
        "a", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="ta", transient=False))
    pool.add_trigger("w", Trigger(
        activation_events=["b"], condition={"name": "true"},
        action={"name": "noop"}, trigger_id="tb", transient=False,
        enabled=False))
    pool.start_shards("w", 2)
    pool.publish_batch("w", [termination_event("b", i) for i in range(3)])
    pb = pool.event_store.partition_for("b")
    deadline = time.monotonic() + 30
    while pool.event_store.dlq_size_partitions("w", [pb]) < 3:
        assert time.monotonic() < deadline, "events were not quarantined"
        time.sleep(0.01)
    assert pool.lag("w") == 0
    pool.set_trigger_enabled("w", "tb", True)   # redrives the partition DLQ
    pool.wait_drained("w", timeout=30)
    deadline = time.monotonic() + 30
    while pool.total_fires("w") < 3:
        assert time.monotonic() < deadline, "redriven events never fired"
        time.sleep(0.01)
    assert pool.event_store.dlq_size_partitions("w", [pb]) == 0
    pool.stop_all()


# -- scoped state-store delta logs ----------------------------------------------

def test_state_store_scoped_delta_logs_and_compaction(tmp_path):
    root = str(tmp_path / "state")
    a = FileStateStore(root, scope="shard-a")
    b = FileStateStore(root, scope="shard-b")
    reader = FileStateStore(root)
    a.put_contexts_delta("w", {"t1": {"replace": {"count": 1}}})
    b.put_contexts_delta("w", {"t2": {"replace": {"count": 10}}})
    a.put_contexts_delta("w", {"t1": {"set": {"count": 2}}})
    assert reader.get_contexts("w") == {"t1": {"count": 2},
                                        "t2": {"count": 10}}
    # ownership-boundary fold: all scopes into the base
    reader.compact("w")
    wf_dir = os.path.join(root, "w")
    assert not [fn for fn in os.listdir(wf_dir)
                if fn.startswith("contexts.delta")]
    assert reader.get_contexts("w")["t1"] == {"count": 2}
    # a scoped writer whose log was folded+removed under it must detect the
    # compaction (size mismatch) and not feed the unlinked inode
    a.put_contexts_delta("w", {"t1": {"set": {"count": 3}}})
    assert reader.get_contexts("w") == {"t1": {"count": 3},
                                        "t2": {"count": 10}}


# -- bugfix regressions: pool/worker ---------------------------------------------

def test_crash_shard_discards_inflight_commit():
    """crash_shard mid-batch must DISCARD the victim's checkpoint/commit —
    uncommitted events are redelivered to the new owner at the crash point
    (the old code fenced and let the batch finish + commit)."""
    store = PartitionedEventStore(4)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    crashed = []

    def boom(ctx, event, params):
        if not crashed:  # only the first owner crashes
            crashed.append(ctx._worker.member)
            # kill() (lock-free) crashes the victim *mid-batch*; the pool
            # membership change happens below, from the test thread.  An
            # action runs under its worker's batch lock, so calling
            # pool.crash_shard here would take pool._lock under worker.lock
            # — the reverse of the pool->worker order _rebalance uses
            # (tfcheck lock-order).
            ctx._worker.kill()

    register_action("boom", boom)
    try:
        tf.add_trigger("w", make_trigger(
            "s0", condition={"name": "true"}, action={"name": "boom"},
            trigger_id="tboom", transient=False))
        tf.add_trigger("w", make_trigger(
            "s0", condition={"name": "counter", "expected": 10,
                             "aggregate": False, "exactly_once": True},
            action={"name": "noop"}, trigger_id="tcount", transient=False))
        store.publish_batch("w", [termination_event("s0", i) for i in range(10)])
        members = tf.pool.set_shard_count("w", 2)
        p0 = store.partition_for("s0")
        owner = next(m for m in members
                     if p0 in tf.pool.metrics("w")["assignment"][m])
        processed = tf.pool.run_shard_once("w", owner)
        assert processed == 10          # the victim consumed the whole batch
        assert crashed == [owner]
        # THE regression assertion: nothing the victim did was committed —
        # every event is still pending for the new owner
        assert store.lag("w") == 10
        # complete the crash from outside the batch: membership + rebalance
        tf.pool.crash_shard("w", owner)
        tf.pool.drive("w", timeout=20)
        assert store.lag("w") == 0
        assert tf.pool.trigger_context("w", "tcount").get("count") == 10
    finally:
        ACTIONS.pop("boom", None)
        tf.shutdown()


def test_reap_idle_departure_is_not_a_crash():
    """Idle-timeout scale-down with events arriving AFTER the shard idled
    must be counted as a clean departure (the old code inferred 'crashed'
    from lag > 0 + _stop unset, skewing the autoscaler's restart stats)."""
    store = PartitionedEventStore(4)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    tf.add_trigger("w", make_trigger(
        "s0", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="t0", transient=False))
    store.publish_batch("w", [termination_event("s0", i) for i in range(20)])
    tf.pool.start_shards("w", 1, idle_timeout=0.05)
    deadline = time.monotonic() + 20
    while store.lag("w") > 0:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    while tf.pool.live_shard_count("w") > 0:   # wait for the idle exit
        assert time.monotonic() < deadline
        time.sleep(0.005)
    # events arrive after the shard went idle: lag > 0 at reap time
    store.publish_batch("w", [termination_event("s0", 100 + i) for i in range(5)])
    reaped = tf.pool.reap("w")
    assert reaped["reaped"] == 1 and reaped["crashed"] == 0
    assert reaped["reasons"] == {"idle": 1}  # classified, not inferred
    tf.shutdown()


def test_scalar_worker_skips_committed_check_on_uncommitted_only():
    """_run_once_scalar must use the batch plane's predicate: no per-event
    is_committed round-trips on an UNCOMMITTED_ONLY store, partitioned or
    not (the old code always checked for non-partitioned workers)."""

    class CountingStore(MemoryEventStore):
        def __init__(self):
            super().__init__()
            self.committed_checks = 0

        def is_committed(self, workflow, event_id):
            self.committed_checks += 1
            return super().is_committed(workflow, event_id)

    class LegacyStore(CountingStore):
        UNCOMMITTED_ONLY = False  # a store that may re-deliver committed events

    def run(store):
        worker = TFWorker("w", store, MemoryStateStore(),
                          FunctionBackend(store, inline=True),
                          batch_plane=False, commit_policy="every_batch")
        worker.add_trigger(make_trigger(
            "s0", condition={"name": "true"}, action={"name": "noop"},
            trigger_id="t0", transient=False))
        store.publish_batch("w", [termination_event("s0", i) for i in range(25)])
        while worker.run_once():
            pass
        return worker.stats.fires

    fast = CountingStore()
    assert run(fast) == 25
    assert fast.committed_checks == 0     # the provable no-op is skipped
    legacy = LegacyStore()
    assert run(legacy) == 25              # identical behavior...
    assert legacy.committed_checks > 0    # ...but the dedup check still runs


def test_failed_batch_shard_releases_partitions():
    """A shard whose batch raises must surrender its partitions immediately
    (group leave + rebalance from the runner's exit hook) — with no
    autoscaler loop calling reap(), the old code stalled them forever."""
    store = PartitionedEventStore(8)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    for i in range(16):
        tf.add_trigger("w", make_trigger(
            f"s{i}", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"t{i}", transient=False))
    members = tf.pool.set_shard_count("w", 2)
    victim = members[0]
    wp = tf.pool._wfs["w"]
    original = wp.shards[victim].run_once
    wp.shards[victim].run_once = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("broken shard"))
    store.publish_batch("w", [termination_event(f"s{i % 16}", i)
                              for i in range(400)])
    tf.pool.start_shards("w", 2)
    # the survivor must drain EVERYTHING — including the victim's partitions —
    # without anyone calling reap()
    deadline = time.monotonic() + 30
    while store.lag("w") > 0:
        assert time.monotonic() < deadline, (
            "partitions stalled after shard failure: lag=%d assignment=%s"
            % (store.lag("w"), tf.pool.metrics("w")["assignment"]))
        time.sleep(0.01)
    m = tf.pool.metrics("w")
    assert m["shard_failures"] == 1
    assert victim not in m["assignment"]
    # the failure is folded into the next reap() report exactly once
    assert tf.pool.reap("w")["crashed"] >= 1
    again = tf.pool.reap("w")
    assert again["reaped"] == 0 and again["crashed"] == 0
    tf.shutdown()
