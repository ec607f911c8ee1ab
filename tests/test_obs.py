"""Observability plane (repro.obs): metrics registry + causal trace plane.

Covers:
* histogram batch-granularity recording (``observe_batch``: one bisect per
  slice, all n observations credited), snapshot merge across shards, and
  the Prometheus text rendering,
* worker/pool scrape surfaces: ``TFWorker.metrics_snapshot`` folds the
  ``WorkerStats`` counters, both shard pools aggregate live + retired
  shards and their own membership counters,
* DLQ accounting parity: the batch plane and the scalar oracle count one
  ``dlq_events`` increment per quarantined event — across redeliveries and
  across a redrive cycle (the double-count regression),
* end-to-end trace propagation: a fan-out DAG driven through
  ``ctx.produce_batch`` yields ONE connected span tree per run on the
  thread pool, the process pool, and across a real SIGKILL crash/replay
  (open span records + span-id dedup at stitch time),
* the join call's books (compile, round trip, host against all of
  triage, on the ``jax`` backend standing in for ``pallas``), fire wait
  and delay, and the profiler spans' disarmed default.
"""
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.bus import PartitionedEventStore, ProcessShardPool
from repro.core import Triggerflow, make_trigger, termination_event
from repro.obs.metrics import (DEFAULT_BOUNDS, Histogram, MetricsRegistry,
                               dump_metrics, empty_snapshot, fold_counters,
                               merge_snapshot, render_prometheus)
from repro.obs.trace import (Tracer, arm_profiler_spans, context_of_span,
                             inject, span, span_trees, stitch_spans,
                             trace_context)


# -- metrics registry ------------------------------------------------------------

def test_histogram_observe_batch_is_batch_granular():
    h = Histogram("h", bounds=(0.01, 0.1, 1.0))
    # 100 observations totalling 5s -> mean 0.05 -> second bucket
    h.observe_batch(100, 5.0)
    assert h.count == 100
    assert h.sum == pytest.approx(5.0)
    assert h.counts == [0, 100, 0, 0]
    h.observe_batch(2, 4.0)  # mean 2.0 -> overflow bucket
    assert h.counts == [0, 100, 0, 2]
    assert h.count == 102


def test_registry_snapshot_merge_and_prometheus():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("tf_x_total").inc(3)
    b.counter("tf_x_total").inc(4)
    a.gauge("tf_g").set(1.5)
    a.histogram("tf_h_seconds").observe_batch(10, 0.1)
    b.histogram("tf_h_seconds").observe_batch(5, 0.05)
    snap = empty_snapshot()
    merge_snapshot(snap, a.snapshot())
    merge_snapshot(snap, b.snapshot())
    assert snap["counters"]["tf_x_total"] == 7
    h = snap["histograms"]["tf_h_seconds"]
    assert h["count"] == 15
    assert h["sum"] == pytest.approx(0.15)
    fold_counters(snap, {"tf_x_total": 1, "tf_y_total": 2})
    assert snap["counters"]["tf_x_total"] == 8
    text = render_prometheus(snap)
    assert "# TYPE tf_x_total counter" in text
    assert "tf_x_total 8" in text
    assert 'tf_h_seconds_bucket{le="+Inf"} 15' in text
    assert "tf_h_seconds_count 15" in text
    # cumulative buckets: each le line >= the previous
    lines = [l for l in text.splitlines() if l.startswith("tf_h_seconds_bucket")]
    vals = [float(l.rsplit(" ", 1)[1]) for l in lines]
    assert vals == sorted(vals) and len(vals) == len(DEFAULT_BOUNDS) + 1


def test_dump_metrics_writes_both_formats(tmp_path):
    snap = empty_snapshot()
    fold_counters(snap, {"tf_x_total": 1})
    paths = dump_metrics(snap, str(tmp_path / "m"))
    assert sorted(os.path.basename(p) for p in paths) == ["m.json", "m.prom"]
    for p in paths:
        assert os.path.getsize(p) > 0


def test_worker_metrics_snapshot_records_every_stage():
    tf = Triggerflow(inline_functions=True, commit_policy="every_batch")
    tf.create_workflow("w")
    # two pure-counting joins far from their threshold (the vector triage
    # only claims the non-firing share), a producer (publish path), and the
    # produced subject's sink — every stage histogram gets traffic
    for i in range(2):
        tf.add_trigger("w", make_trigger(
            f"s{i}", condition={"name": "counter", "expected": 100,
                                "aggregate": False},
            action={"name": "noop"}, trigger_id=f"j{i}", transient=False))
    tf.add_trigger("w", make_trigger(
        "p", condition={"name": "true"},
        action={"name": "produce", "subject": "t"},
        trigger_id="tp", transient=False))
    tf.add_trigger("w", make_trigger(
        "t", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="n", transient=False))
    tf.event_store.publish_batch(
        "w", [termination_event(f"s{i % 2}", i) for i in range(10)]
        + [termination_event("p", 100 + i) for i in range(4)])
    w = tf.worker("w")
    w.keep_event_log = False  # the vector join plane requires no event log
    while w.run_once(64):
        pass
    snap = w.metrics_snapshot()
    # 10 join + 4 producer + 4 produced
    assert snap["counters"]["tf_events_processed_total"] == 18
    assert snap["counters"]["tf_fires_total"] == 8  # 4 p + 4 t, joins pending
    for name in ("tf_consume_lag_seconds", "tf_batch_eval_seconds",
                 "tf_fire_seconds", "tf_checkpoint_seconds",
                 "tf_publish_seconds"):
        assert snap["histograms"][name]["count"] > 0, name
    # join triage ran (counter conditions take the vector plane)
    assert snap["histograms"]["tf_join_kernel_seconds"]["count"] > 0
    # consume lag is sane: publish stamped, so lag is small but positive
    lag = snap["histograms"]["tf_consume_lag_seconds"]
    assert 0 <= lag["sum"] < 60


def test_metrics_off_removes_recording():
    tf = Triggerflow(inline_functions=True, commit_policy="every_batch")
    tf.create_workflow("w")
    tf.add_trigger("w", make_trigger(
        "s", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="t", transient=False))
    tf.event_store.publish_batch(
        "w", [termination_event("s", i) for i in range(5)])
    w = tf.worker("w")
    w._metrics = None
    while w.run_once(64):
        pass
    snap = w.metrics_snapshot()
    assert snap["histograms"] == {}
    # counters still derive from WorkerStats at scrape time
    assert snap["counters"]["tf_events_processed_total"] == 5


# -- DLQ accounting parity (batch plane vs scalar oracle) ------------------------

def _dlq_run(batch_plane):
    tf = Triggerflow(inline_functions=True, commit_policy="every_batch")
    tf.create_workflow("w")
    tf.add_trigger("w", make_trigger(
        "s", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="t", transient=False))
    w = tf.worker("w")
    w.batch_plane = batch_plane
    w.keep_event_log = False
    w.set_trigger_enabled("t", False)
    events = [termination_event("s", i) for i in range(12)]          # quarantine
    events += [termination_event("nobody", 100 + i) for i in range(7)]  # drop
    tf.event_store.publish_batch("w", events)
    for _ in range(6):  # several deliveries: redelivery must not re-count
        w.run_once(64)
    quarantined = w.stats.dlq_events
    # redrive cycle: re-enable, requeue the DLQ, drain — the 12 events now
    # commit and must not be counted a second time
    w.set_trigger_enabled("t", True)
    tf.event_store.redrive("w")
    for _ in range(6):
        w.run_once(64)
    return quarantined, w.stats.dlq_events, w.stats.fires


@pytest.mark.parametrize("batch_plane", [True, False])
def test_dlq_one_increment_per_quarantined_event(batch_plane):
    quarantined, after_redrive, fires = _dlq_run(batch_plane)
    assert quarantined == 19          # 12 disabled + 7 unknown-subject
    assert after_redrive == 19        # the redrive cycle re-counts nothing
    assert fires == 12                # the redriven events actually fired


def test_dlq_parity_across_planes():
    assert _dlq_run(True) == _dlq_run(False)


# -- trace propagation: fan-out DAG, one connected tree --------------------------

FANOUT_WIDTH = 4


def _fanout_triggers():
    """a -> b0..b3 -> c0..c3: a three-stage fan-out whose middle/leaf
    subjects spread over partitions (and therefore shards)."""
    trgs = [make_trigger("a", condition={"name": "true"},
                         action={"name": "produce", "subject": f"b{i}"},
                         trigger_id=f"ta{i}", transient=False)
            for i in range(FANOUT_WIDTH)]
    for i in range(FANOUT_WIDTH):
        trgs.append(make_trigger(
            f"b{i}", condition={"name": "true"},
            action={"name": "produce", "subject": f"c{i}"},
            trigger_id=f"tb{i}", transient=False))
        trgs.append(make_trigger(
            f"c{i}", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"tc{i}", transient=False))
    return trgs


FANOUT_STAGES = ({f"ta{i}" for i in range(FANOUT_WIDTH)}
                 | {f"tb{i}" for i in range(FANOUT_WIDTH)}
                 | {f"tc{i}" for i in range(FANOUT_WIDTH)})


def test_trace_inject_and_context_roundtrip():
    e = termination_event("a", 1)
    assert trace_context(e) is None
    inject([e], "T", "S")
    assert trace_context(e) == ("T", "S")
    inject([e], "T2", "S2")  # carried context is never overwritten
    assert trace_context(e) == ("T", "S")
    # the attribute survives the wire codec
    from repro.core.events import CloudEvent
    assert trace_context(CloudEvent.from_dict(e.to_dict())) == ("T", "S")


def test_fanout_trace_connected_thread_pool():
    store = PartitionedEventStore(4)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tracer = Tracer(sample=0.0)  # propagate-only: the root is explicit
    tf.pool.tracer = tracer
    tf.create_workflow("w")
    for trg in _fanout_triggers():
        tf.add_trigger("w", trg)
    root = tracer.start_trace("publish")
    events = [termination_event("a", i) for i in range(40)]
    inject(events, *context_of_span(root))
    store.publish_batch("w", events)
    tf.pool.set_shard_count("w", 3)
    tf.pool.drive("w", timeout=30)
    tracer.end(root)
    tf.shutdown()

    spans = stitch_spans(tracer.collector.spans)
    trees = span_trees(spans)
    assert len(trees) == 1, "one root context -> one trace"
    tree = trees[next(iter(trees))]
    assert tree["connected"], tree["attachments"]
    names = [s["name"] for s in spans]
    assert names.count("publish") == 1
    stages = {s.get("trigger") for s in spans if s["name"] == "fire"}
    assert stages == FANOUT_STAGES


def _proc_fanout(tmp_path, crash):
    pool = ProcessShardPool(str(tmp_path / "pool"), num_partitions=4,
                            batch_size=64, trace="full")
    pool.create_workflow("w")
    for trg in _fanout_triggers():
        pool.add_trigger("w", trg)
    tracer = Tracer(sample=0.0)
    root = tracer.start_trace("publish")
    events = [termination_event("a", i) for i in range(300)]
    inject(events, *context_of_span(root))
    pool.publish_batch("w", events)
    pool.start_shards("w", 2)
    if crash:
        deadline = time.monotonic() + 30
        while pool.total_events_processed("w") == 0:
            assert time.monotonic() < deadline, "no progress before crash"
            time.sleep(0.01)
        victim = pool.shard_ids("w")[0]
        pool.crash_shard("w", victim)
        assert pool.metrics("w")["crashes"] == 1
    pool.wait_drained("w", timeout=60)
    pool.stop_all()
    tracer.end(root)
    spans = stitch_spans(pool.trace_spans(), tracer.collector.spans)
    return pool, spans


def test_fanout_trace_connected_process_pool(tmp_path):
    pool, spans = _proc_fanout(tmp_path, crash=False)
    trees = span_trees(spans)
    assert len(trees) == 1
    assert trees[next(iter(trees))]["connected"]
    stages = {s.get("trigger") for s in spans if s["name"] == "fire"}
    assert stages == FANOUT_STAGES
    shards = {s.get("shard") for s in spans if s["name"] == "fire"}
    assert len(shards) >= 2, "the trace crossed shard processes"


def test_fanout_trace_connected_across_sigkill(tmp_path):
    pool, spans = _proc_fanout(tmp_path, crash=True)
    # span-id dedup: the stitched set has no duplicates, and any span that
    # got both an open record and a completed one kept the completed record
    ids = [s["span"] for s in spans]
    assert len(ids) == len(set(ids))
    trees = span_trees(spans)
    assert len(trees) == 1, "replayed fires rejoin the same trace"
    tree = trees[next(iter(trees))]
    assert tree["connected"], tree["attachments"]
    # and the workload itself stayed exactly-once on commits
    assert len(pool.event_store.committed_events("w")) >= 300


# -- pool scrape surfaces --------------------------------------------------------

def test_thread_pool_obs_snapshot_folds_membership(tmp_path):
    store = PartitionedEventStore(4)
    tf = Triggerflow(event_store=store, inline_functions=True,
                     commit_policy="every_batch")
    tf.create_workflow("w")
    tf.add_trigger("w", make_trigger(
        "s", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="t", transient=False))
    store.publish_batch("w", [termination_event("s", i) for i in range(50)])
    tf.pool.set_shard_count("w", 2)
    tf.pool.drive("w", timeout=30)
    m = tf.pool.metrics("w")
    snap = m["obs"]
    assert snap["counters"]["tf_events_processed_total"] == 50
    assert snap["counters"]["tf_rebalance_total"] >= 1
    assert snap["histograms"]["tf_batch_eval_seconds"]["count"] > 0
    assert m["rebalances"] >= 1
    # retiring a shard keeps its counters in the fold
    tf.pool.set_shard_count("w", 1)
    snap2 = tf.pool.obs_snapshot("w")
    assert snap2["counters"]["tf_events_processed_total"] == 50
    # the facade aggregates the same numbers
    svc = tf.metrics_snapshot("w")
    assert svc["counters"]["tf_events_processed_total"] == 50
    tf.shutdown()


def test_process_pool_obs_snapshot_over_the_pipe(tmp_path):
    pool = ProcessShardPool(str(tmp_path / "pool"), num_partitions=4,
                            batch_size=64)
    pool.create_workflow("w")
    pool.add_trigger("w", make_trigger(
        "s", condition={"name": "true"}, action={"name": "noop"},
        trigger_id="t", transient=False))
    pool.publish_batch("w", [termination_event("s", i) for i in range(100)])
    pool.start_shards("w", 2)
    pool.wait_drained("w", timeout=60)
    snap = pool.obs_snapshot("w")
    assert snap["counters"]["tf_events_processed_total"] == 100
    assert snap["counters"]["tf_rebalance_total"] >= 1
    assert snap["counters"]["tf_log_appends_total"] > 0
    assert snap["histograms"]["tf_checkpoint_seconds"]["count"] > 0
    pool.stop_all()
    # after the clean stop every counter survives in retired_stats
    snap2 = pool.obs_snapshot("w")
    assert snap2["counters"]["tf_events_processed_total"] == 100


def test_autoscaler_metrics_snapshot():
    from repro.core.autoscaler import KedaAutoscaler
    tf = Triggerflow(inline_functions=True)
    scaler = KedaAutoscaler(tf)
    scaler.scale_ups, scaler.scale_downs, scaler.restarts = 3, 2, 1
    snap = scaler.metrics_snapshot()
    assert snap["counters"] == {"tf_scale_ups_total": 3,
                                "tf_scale_downs_total": 2,
                                "tf_restarts_total": 1,
                                "tf_circuit_open_total": 0,
                                "tf_autoscaler_node_recoveries_total": 0}
    assert snap["gauges"]["tf_active_workers"] == 0
    assert snap["gauges"]["tf_restart_backoff_seconds"] == 0.0
    tf.shutdown()


def test_trace_report_cli(tmp_path):
    pool, spans = _proc_fanout(tmp_path, crash=False)
    import subprocess
    import sys
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "trace_report.py"),
         pool.trace_dir, "--assert-connected", "--quiet"],
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "disconnected" in out.stdout  # the "0 disconnected" summary line


# -- the join call's books and the fire path -------------------------------------

def _join_worker(n_triggers, expected=10 ** 6, vector_join="jax",
                 action_plane=True, reset_on_fire=False):
    from repro.core import MemoryEventStore, MemoryStateStore
    from repro.core.functions import FunctionBackend
    from repro.core.worker import TFWorker

    es = MemoryEventStore()
    w = TFWorker("w", es, MemoryStateStore(), FunctionBackend(es, inline=True),
                 commit_policy="every_batch", keep_event_log=False,
                 vector_join=vector_join, action_plane=action_plane)
    for t in range(n_triggers):
        w.add_trigger(make_trigger(
            f"s{t}", condition={"name": "counter", "expected": expected,
                                "reset_on_fire": reset_on_fire},
            action={"name": "noop"}, trigger_id=f"j{t}", transient=False))
    return w


def _publish_round(w, n_events, n_triggers):
    w.event_store.publish_batch("w", [
        termination_event(f"s{i % n_triggers}", i) for i in range(n_events)])


def _join_books(snap):
    h, c = snap["histograms"], snap["counters"]
    return {"kernel": h["tf_join_kernel_seconds"]["sum"],
            "parts": (h["tf_join_host_seconds"]["sum"]
                      + h["tf_join_compile_seconds"]["sum"]
                      + h["tf_join_roundtrip_seconds"]["sum"]),
            "compiles": c["tf_join_compiles_total"],
            "compile_calls": h["tf_join_compile_seconds"]["count"],
            "compile_s": h["tf_join_compile_seconds"]["sum"],
            "roundtrips": h["tf_join_roundtrip_seconds"]["count"],
            "calls": c["tf_join_calls_total"],
            "events": c["tf_join_events_total"],
            "rows": c["tf_join_rows_total"]}


def test_join_call_books_compile_then_round_trip():
    """A new (N, T) shape compiles once, a repeat is a round trip; host +
    compile + round trip is all of triage; rows sum T, events sum N."""
    pytest.importorskip("jax")
    n, t = 8193, 11  # bucket (16384, 128): no other test dispatches it
    w = _join_worker(t)
    _publish_round(w, n, t)
    assert w.run_once(n) == n
    first = _join_books(w.metrics_snapshot())
    assert first["compiles"] == 1 and first["compile_calls"] == 1
    assert first["compile_s"] > 0 and first["roundtrips"] == 0
    _publish_round(w, n, t)
    assert w.run_once(n) == n
    books = _join_books(w.metrics_snapshot())
    assert books["compiles"] == 1 and books["compile_calls"] == 1
    assert books["roundtrips"] == 1
    assert books["parts"] == pytest.approx(books["kernel"], rel=0.01)
    assert books["calls"] == 2
    assert books["events"] == 2 * n and books["rows"] == 2 * t


def test_join_compiles_charged_to_the_compiling_thread():
    """Two shard threads, each compiling its own shape at the same time:
    each compile lands in the registry of the thread that paid for it."""
    pytest.importorskip("jax")
    # buckets (4096, 128) and (8192, 128): no other test dispatches them
    shapes = [(2049, 13), (4097, 17)]
    workers = [_join_worker(t) for _, t in shapes]
    for w, (n, t) in zip(workers, shapes):
        _publish_round(w, n, t)
    start = threading.Barrier(len(workers))
    done = []

    def run(w, n):
        start.wait(timeout=30)
        done.append(w.run_once(n))

    threads = [threading.Thread(target=run, args=(w, n))
               for w, (n, _) in zip(workers, shapes)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive()
    assert sorted(done) == sorted(n for n, _ in shapes)
    for w, (n, t) in zip(workers, shapes):
        books = _join_books(w.metrics_snapshot())
        assert books["compiles"] == 1 and books["roundtrips"] == 0
        assert books["compile_s"] > 0
        assert (books["events"], books["rows"]) == (n, t)


def test_join_shapes_in_one_bucket_compile_once():
    """Two calls of different (N, T) that pad to one shape bucket book one
    compile and one round trip, each with its own unpadded N and T."""
    pytest.importorskip("jax")
    t = 300
    w = _join_worker(t)
    # (600, 300) and (900, 260) share bucket (1024, 512), no other test's
    for n, subjects in ((600, t), (900, 260)):
        _publish_round(w, n, subjects)
        assert w.run_once(n) == n
    books = _join_books(w.metrics_snapshot())
    assert books["compiles"] == 1 and books["roundtrips"] == 1
    assert books["calls"] == 2
    assert books["events"] == 600 + 900 and books["rows"] == t + 260


@pytest.mark.parametrize("backend,pads", [("jax", (512 - 300, 128 - 11)),
                                          ("numpy", (0, 0))])
def test_join_pad_counters_book_the_bucket_padding(backend, pads):
    """A compiling backend books ``bucket - N`` padded events and
    ``bucket - T`` padded rows a call; ``numpy`` runs exact and books 0."""
    if backend == "jax":
        pytest.importorskip("jax")
    n, t = 300, 11
    w = _join_worker(t, vector_join=backend)
    for rnd in (1, 2):
        _publish_round(w, n, t)
        assert w.run_once(n) == n
        c = w.metrics_snapshot()["counters"]
        assert (c["tf_join_pad_events_total"],
                c["tf_join_pad_rows_total"]) == (rnd * pads[0], rnd * pads[1])
        assert c["tf_join_events_total"] == rnd * n


@pytest.mark.parametrize("action_plane", [True, False])
def test_fire_wait_and_delay_count_the_fired_events(action_plane):
    """Over a small join with ``reset_on_fire``, every fired event is booked
    once in each of fire wait and fire delay, on the fire-run path and on
    its per-fire twin."""
    t, expected, rounds = 4, 5, 3
    w = _join_worker(t, expected=expected, vector_join="numpy",
                     action_plane=action_plane, reset_on_fire=True)
    _publish_round(w, t * expected * rounds, t)
    while w.run_once(7):
        pass
    snap = w.metrics_snapshot()
    fires = snap["counters"]["tf_fires_total"]
    assert fires == t * rounds
    h = snap["histograms"]
    assert h["tf_fire_wait_seconds"]["count"] == fires
    assert h["tf_fire_delay_seconds"]["count"] == fires
    assert 0 <= h["tf_fire_wait_seconds"]["sum"] < 60
    assert 0 <= h["tf_fire_delay_seconds"]["sum"] < 60


def test_profiler_spans_disarmed_by_default_and_free_of_jax():
    assert span("tf.a") is span("tf.b")
    with span("tf.a"):
        pass
    code = ("import sys, repro.obs, repro.core; "
            "from repro.obs import span; "
            "assert span('tf.x') is span('tf.y'); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr


def test_profiler_spans_armed_open_annotations():
    jax = pytest.importorskip("jax")
    arm_profiler_spans(True)
    try:
        s = span("tf.consume")
        assert isinstance(s, jax.profiler.TraceAnnotation)
        with s:
            pass
    finally:
        arm_profiler_spans(False)
    assert span("tf.a") is span("tf.b")


def _span_probe(ctx, event, params):
    with open(params["path"], "a", encoding="utf-8") as f:
        f.write("%s\n" % (span("tf.a") is span("tf.b")))


def test_process_shards_never_arm_profiler_spans(tmp_path):
    """A shard process forked from a parent that armed its spans runs
    disarmed: it owns no device and must never open a profiler
    annotation."""
    pytest.importorskip("jax")
    from repro.core import register_action

    register_action("obs_test.span_probe", _span_probe)
    marker = tmp_path / "probe.txt"
    arm_profiler_spans(True)
    try:
        pool = ProcessShardPool(str(tmp_path / "pool"), num_partitions=2,
                                batch_size=16)
        pool.create_workflow("w")
        pool.add_trigger("w", make_trigger(
            "s", condition={"name": "true"},
            action={"name": "obs_test.span_probe", "path": str(marker)},
            trigger_id="t", transient=False))
        pool.publish_batch("w", [termination_event("s", i) for i in range(4)])
        pool.start_shards("w", 1)
        pool.wait_drained("w", timeout=60)
        pool.stop_all()
    finally:
        arm_profiler_spans(False)
    assert marker.read_text().split() == ["True"] * 4
