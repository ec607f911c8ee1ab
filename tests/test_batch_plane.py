"""Batch plane parity + incremental-checkpoint tests.

The scalar per-event interpreter (``batch_plane=False``) is the semantic
oracle.  Every test here runs the same published event stream through a
scalar worker and a batch-plane worker and asserts identical observable
behavior: fires, activation counts, contexts, DLQ contents, commit state.
Crash-recovery tests prove the delta-checkpoint JSONL log reconstructs the
same contexts as full rewrites across worker restarts.
"""
import json
import os
import random

import pytest

from repro.core import (
    MemoryEventStore,
    FileStateStore,
    MemoryStateStore,
    Triggerflow,
    make_trigger,
    termination_event,
    failure_event,
    register_condition,
)
from repro.core.conditions import BATCHED_CONDITIONS
from repro.core.events import TYPE_TIMEOUT, CloudEvent
from repro.core.worker import TFWorker
from repro.core.functions import FunctionBackend


def _mk_worker(state_store=None, batch_plane=True, commit_policy="every_batch",
               vector_join=None):
    es = MemoryEventStore()
    ss = state_store or MemoryStateStore()
    w = TFWorker("w", es, ss, FunctionBackend(es, inline=True),
                 commit_policy=commit_policy, batch_plane=batch_plane,
                 vector_join=vector_join)
    return w


def _drain(w, batch=512, rounds=200):
    for _ in range(rounds):
        if w.run_once(batch) == 0 and not w._sink:
            break


def _ctx_norm(w):
    out = {}
    for tid in w.triggers:
        ctx = dict(w.context_of(tid))
        if isinstance(ctx.get("seen_ids"), (set, frozenset, list)):
            ctx["seen_ids"] = sorted(ctx["seen_ids"])
        out[tid] = ctx
    return out


def _observables(w):
    return {
        "fires": w.stats.fires,
        "activations": w.stats.activations,
        "events": w.stats.events_processed,
        "dlq": w.stats.dlq_events,
        "contexts": _ctx_norm(w),
        "enabled": {tid: t.enabled for tid, t in w.triggers.items()},
        "store_dlq": w.event_store.dlq_size("w"),
        "lag": w.event_store.lag("w"),
    }


def _parity(triggers, events, batch=512, commit_policy="every_batch",
            redeliver=False):
    """Run the same stream through scalar and batch-plane workers."""
    results = []
    for plane in (False, True):
        w = _mk_worker(batch_plane=plane, commit_policy=commit_policy)
        for spec in triggers:
            w.add_trigger(make_trigger(**spec))
        w.event_store.publish_batch("w", events)
        _drain(w, batch)
        if redeliver:
            w.event_store.publish_batch("w", events)  # broker re-publish
            _drain(w, batch)
        results.append(_observables(w))
    assert results[0] == results[1]
    return results[1]


def _stream(rng, subjects, n, dup_frac=0.0, fail_frac=0.0):
    evs = []
    for i in range(n):
        s = rng.choice(subjects)
        if rng.random() < fail_frac:
            evs.append(failure_event(s, error=f"e{i}"))
        else:
            evs.append(termination_event(s, i))
    if dup_frac:
        for e in list(evs):
            if rng.random() < dup_frac:
                evs.append(e)  # same id: at-least-once duplicate
        rng.shuffle(evs)
    return evs


# -- scalar vs batched condition parity ---------------------------------------

def test_counter_parity_randomized():
    rng = random.Random(7)
    for trial in range(6):
        subjects = [f"s{i}" for i in range(rng.randint(1, 5))]
        triggers = []
        for i, s in enumerate(subjects):
            triggers.append(dict(
                subjects=s,
                condition={"name": "counter",
                           "expected": rng.randint(1, 30),
                           "aggregate": rng.random() < 0.5,
                           "reset_on_fire": rng.random() < 0.5,
                           "exactly_once": rng.random() < 0.5},
                action={"name": "noop"},
                trigger_id=f"t{i}", transient=False))
        events = _stream(rng, subjects, rng.randint(10, 200),
                         dup_frac=0.2, fail_frac=0.1)
        _parity(triggers, events, batch=rng.choice([3, 17, 64, 512]))


def test_threshold_join_parity_with_timeouts():
    rng = random.Random(11)
    subjects = ["a", "b"]
    triggers = [dict(
        subjects=s,
        condition={"name": "threshold_join", "expected": 20,
                   "fraction": 0.5, "min_events": 2,
                   "exactly_once": (s == "a")},
        action={"name": "noop"}, trigger_id=f"j_{s}", transient=False)
        for s in subjects]
    events = _stream(rng, subjects, 60, dup_frac=0.15, fail_frac=0.1)
    events.insert(30, CloudEvent(subject="a", type=TYPE_TIMEOUT))
    events.insert(45, CloudEvent(subject="b", type=TYPE_TIMEOUT))
    _parity(triggers, events, batch=16)


def test_same_subject_cross_type_order_preserved():
    """A timeout arriving between result events must be evaluated at its
    arrival position: grouping splits type-uniform *runs*, never reorders
    one subject's stream (reviewer repro: early timeout must not observe
    later results' counts and fire the join prematurely)."""
    triggers = [dict(subjects="s",
                     condition={"name": "threshold_join", "expected": 100,
                                "min_events": 5},
                     action={"name": "noop"}, trigger_id="t", transient=False)]
    events = [termination_event("s", 0),
              CloudEvent(subject="s", type=TYPE_TIMEOUT),
              *[termination_event("s", i) for i in range(1, 9)]]
    res = _parity(triggers, events, batch=512)
    assert res["fires"] == 0  # count was 1 < min_events when the timeout hit


def test_triage_error_degrades_to_exact_path():
    """A poisoned ctx['expected'] (set via introspection) must not kill the
    worker: triage screening errors fall back to the exact path, which
    contains the error per event like the scalar loop."""
    obs = []
    for plane in (False, True):
        w = _mk_worker(batch_plane=plane, vector_join="numpy")
        for i in range(3):
            w.add_trigger(make_trigger(
                f"s{i}", condition={"name": "counter", "expected": 50,
                                    "aggregate": False},
                action={"name": "noop"}, trigger_id=f"t{i}", transient=False))
        w.context_of("t0")["expected"] = "not-a-number"
        w.event_store.publish_batch(
            "w", [termination_event(f"s{i % 3}", i) for i in range(9)])
        _drain(w)  # must not raise
        obs.append(_observables(w))
    # the poisoned trigger's own context legitimately differs (the scalar fn
    # mutates count before int() raises; the batched fn raises first) — the
    # healthy triggers and the stream state must agree
    for key in ("fires", "dlq", "events", "lag", "store_dlq"):
        assert obs[0][key] == obs[1][key], key
    for tid in ("t1", "t2"):
        assert obs[0]["contexts"][tid] == obs[1]["contexts"][tid]
        assert obs[1]["contexts"][tid]["count"] == 3


def test_transient_fire_mid_slice_parity():
    """A transient trigger firing mid-slice must DLQ the tail of its subject's
    slice exactly like the scalar path."""
    triggers = [dict(subjects="x",
                     condition={"name": "counter", "expected": 3},
                     action={"name": "noop"}, trigger_id="t", transient=True)]
    events = [termination_event("x", i) for i in range(10)]
    res = _parity(triggers, events, batch=512)
    assert res["fires"] == 1
    assert res["store_dlq"] == 7  # events after the fire have no enabled trigger


def test_reset_on_fire_multi_fire_within_batch():
    triggers = [dict(subjects="x",
                     condition={"name": "counter", "expected": 4,
                                "aggregate": False, "reset_on_fire": True},
                     action={"name": "noop"}, trigger_id="t", transient=False)]
    events = [termination_event("x", i) for i in range(21)]
    res = _parity(triggers, events, batch=512)
    assert res["fires"] == 5
    assert res["contexts"]["t"]["count"] == 1


def test_exactly_once_under_redelivery_parity():
    triggers = [dict(subjects="x",
                     condition={"name": "counter", "expected": 50,
                                "aggregate": False, "exactly_once": True},
                     action={"name": "noop"}, trigger_id="t", transient=False)]
    events = [termination_event("x", i) for i in range(50)]
    res = _parity(triggers, events, batch=7, redeliver=True)
    assert res["fires"] >= 1
    assert res["contexts"]["t"]["count"] == 50  # dups never double-count


def test_unbatched_condition_degrades_to_scalar():
    register_condition("only_scalar_mod3",
                       lambda ctx, e, p: (e.data or {}).get("result", 0) % 3 == 0)
    assert "only_scalar_mod3" not in BATCHED_CONDITIONS
    triggers = [dict(subjects="x", condition={"name": "only_scalar_mod3"},
                     action={"name": "noop"}, trigger_id="t", transient=False)]
    events = [termination_event("x", i) for i in range(30)]
    res = _parity(triggers, events, batch=512)
    assert res["fires"] == 10


def test_dlq_and_redrive_parity():
    """Out-of-order events (disabled trigger) park in the DLQ in both modes
    and redrive identically once the trigger is enabled."""
    for plane in (False, True):
        w = _mk_worker(batch_plane=plane)
        t = make_trigger("x", condition={"name": "counter", "expected": 3,
                                         "aggregate": False},
                         action={"name": "noop"}, trigger_id="t",
                         transient=False)
        t.enabled = False
        w.add_trigger(t)
        w.event_store.publish_batch(
            "w", [termination_event("x", i) for i in range(5)])
        _drain(w)
        assert w.event_store.dlq_size("w") == 5
        w.set_trigger_enabled("t", True)
        w.event_store.redrive("w")
        _drain(w)
        assert w.stats.fires == 3  # >= expected keeps firing per event
        assert dict(w.context_of("t"))["count"] == 5


def test_dynamic_expected_introspection_parity():
    """An upstream map action sets the join trigger's ``expected`` via
    introspection (§5.1) — the batch plane must honor the dynamic value."""
    obs = []
    for plane in (False, True):
        tf = Triggerflow(inline_functions=True, commit_policy="every_batch")
        tf.create_workflow("w")
        w = tf.worker("w")
        w.batch_plane = plane
        tf.backend.register("work", lambda x: x * 2)
        tf.add_trigger("w", make_trigger(
            "start",
            action={"name": "map_invoke", "fn": "work", "subject": "done",
                    "items": [1, 2, 3, 4, 5], "join_trigger": "join"},
            trigger_id="map"))
        tf.add_trigger("w", make_trigger(
            "done",
            condition={"name": "counter", "expected": 999},
            action={"name": "workflow_end", "pass_result": False,
                    "result": "joined"},
            trigger_id="join"))
        tf.publish("w", termination_event("start", None))
        result = w.run_until_complete(timeout=30)
        ctx = dict(w.context_of("join"))
        obs.append((result["status"], ctx["count"], sorted(ctx["results"])))
    assert obs[0] == obs[1]
    assert obs[1][1] == 5
    assert obs[1][2] == [2, 4, 6, 8, 10]


def test_vector_plane_matches_disabled_plane():
    """The numpy/jax triage tier must be observably identical to the pure
    per-trigger batched path (vector_join='off')."""
    triggers = [dict(subjects=f"s{i}",
                     condition={"name": "counter", "expected": 40,
                                "aggregate": False},
                     action={"name": "noop"}, trigger_id=f"t{i}",
                     transient=False) for i in range(20)]
    events = [termination_event(f"s{i % 20}", i) for i in range(20 * 40)]
    obs = []
    for vj in ("off", "numpy"):
        w = _mk_worker(batch_plane=True, vector_join=vj)
        for spec in triggers:
            w.add_trigger(make_trigger(**spec))
        w.event_store.publish_batch("w", events)
        _drain(w, batch=256)
        obs.append(_observables(w))
    assert obs[0] == obs[1]
    assert obs[1]["fires"] == 20


def test_dynamic_trigger_added_mid_batch_sees_rest_of_batch():
    """A trigger registered by an action mid-slice must see the remainder of
    the batch (scalar oracle semantics); previously those events were
    committed without ever reaching it."""
    from repro.core import Trigger, register_pyfunc

    def add_b(ctx, ev, p):
        if not ctx.get("added"):
            ctx["added"] = True
            ctx.add_trigger(Trigger(
                activation_events=["s"],
                condition={"name": "counter", "expected": 3,
                           "aggregate": False},
                action={"name": "noop"}, trigger_id="B", transient=False))

    register_pyfunc("add_b", add_b)
    triggers = [dict(subjects="s", condition={"name": "true"},
                     action={"name": "pyfunc", "func": "add_b"},
                     trigger_id="A", transient=False)]
    events = [termination_event("s", i) for i in range(6)]
    res = _parity(triggers, events, batch=512)
    assert res["contexts"]["B"]["count"] == 6  # B saw every event in the batch


def test_multiple_dynamic_adds_start_at_their_own_positions():
    """Two triggers added at different points of one slice must each see the
    tail from their own birth event, not from the earliest change point."""
    from repro.core import Trigger, register_pyfunc

    def _adder(tid, expected):
        def add(ctx, ev, p):
            ctx.add_trigger(Trigger(
                activation_events=["s"],
                condition={"name": "counter", "expected": expected,
                           "aggregate": False},
                action={"name": "noop"}, trigger_id=tid, transient=False))
        return add

    register_pyfunc("add_x", _adder("X", 99))
    register_pyfunc("add_y", _adder("Y", 99))
    triggers = [
        dict(subjects="s",
             condition={"name": "python", "expr": "data['result'] == 0"},
             action={"name": "pyfunc", "func": "add_x"},
             trigger_id="A", transient=False),
        dict(subjects="s",
             condition={"name": "python", "expr": "data['result'] == 6"},
             action={"name": "pyfunc", "func": "add_y"},
             trigger_id="B", transient=False),
    ]
    events = [termination_event("s", i) for i in range(10)]
    res = _parity(triggers, events, batch=512)
    assert res["contexts"]["X"]["count"] == 10  # born at e0
    assert res["contexts"]["Y"]["count"] == 4   # born at e6: sees e6..e9 only


def test_trigger_enabled_mid_batch_sees_rest_of_batch():
    from repro.core import register_pyfunc

    def enable_b(ctx, ev, p):
        ctx.enable_trigger("B")

    register_pyfunc("enable_b", enable_b)
    obs = []
    for plane in (False, True):
        w = _mk_worker(batch_plane=plane)
        w.add_trigger(make_trigger(
            "s", condition={"name": "true"},
            action={"name": "pyfunc", "func": "enable_b"},
            trigger_id="A", transient=True))
        b = make_trigger("s", condition={"name": "counter", "expected": 99,
                                         "aggregate": False},
                         action={"name": "noop"}, trigger_id="B",
                         transient=False)
        b.enabled = False
        w.add_trigger(b)
        w.event_store.publish_batch(
            "w", [termination_event("s", i) for i in range(5)])
        _drain(w)
        obs.append(_observables(w))
    assert obs[0] == obs[1]
    assert obs[1]["contexts"]["B"]["count"] == 5


def test_failed_checkpoint_retries_deltas():
    """A store failure during put_contexts_delta must leave dirty tracking
    intact so the (possibly initial ``replace``) delta is re-emitted."""

    class FlakyStore(MemoryStateStore):
        def __init__(self):
            super().__init__()
            self.fail_next = 0

        def put_contexts_delta(self, workflow, deltas):
            if self.fail_next > 0:
                self.fail_next -= 1
                raise OSError("disk full")
            super().put_contexts_delta(workflow, deltas)

    ss = FlakyStore()
    w = _mk_worker(state_store=ss)
    w.add_trigger(make_trigger(
        "x", condition={"name": "counter", "expected": 100, "aggregate": False},
        action={"name": "noop"}, trigger_id="t", transient=False))
    w.event_store.publish_batch(
        "w", [termination_event("x", i) for i in range(3)])
    ss.fail_next = 1
    with pytest.raises(OSError):
        w.run_once()
    assert ss.get_contexts("w") == {}  # nothing acknowledged
    # the next checkpoint (new event arrives) re-emits the retained deltas
    w.event_store.publish("w", termination_event("x", 99))
    _drain(w)
    stored = ss.get_contexts("w")["t"]
    assert stored["count"] == 4  # the initial replace snapshot was re-emitted


def test_vector_plane_rejects_duplicate_ids_in_batch():
    """A re-published duplicate inside one consumed batch must not be
    double-counted by the vectorized triage (it defers to the grouped
    path's in-flight dedup)."""
    obs = []
    for plane in (False, True):
        w = _mk_worker(batch_plane=plane, vector_join="numpy")
        for i in range(2):
            w.add_trigger(make_trigger(
                f"s{i}", condition={"name": "counter", "expected": 100,
                                    "aggregate": False},
                action={"name": "noop"}, trigger_id=f"t{i}", transient=False))
        evs = [termination_event(f"s{i % 2}", i) for i in range(8)]
        w.event_store.publish_batch("w", evs + [evs[0]])  # same-id duplicate
        _drain(w)
        obs.append(_observables(w))
    assert obs[0] == obs[1]
    assert obs[1]["contexts"]["t0"]["count"] == 4  # dup counted once


def test_delta_log_torn_tail_truncated_before_new_appends(tmp_path):
    """Checkpoints appended after a torn line must not be lost: the first
    post-restart append repairs (truncates) the torn tail first."""
    root = str(tmp_path / "repair")
    ss = FileStateStore(root)
    ss.put_contexts_delta("w", {"t": {"replace": {"count": 1}}})
    log = tmp_path / "repair" / "w" / "contexts.delta.jsonl"
    with open(log, "a") as f:
        f.write('{"t": {"set": {"count": 99')  # crash mid-append
    restarted = FileStateStore(root)  # fresh process
    restarted.put_contexts_delta("w", {"t": {"set": {"count": 7}}})
    assert restarted.get_contexts("w")["t"]["count"] == 7
    # and a later cold reader agrees (the torn line is gone from disk)
    assert FileStateStore(root).get_contexts("w")["t"]["count"] == 7


def test_duplicate_copies_commit_once():
    """A re-published duplicate must not double-commit: commit_offset,
    committed_events (§5.3 replay) and the returned count all see the id
    exactly once, and sibling partitions are not starved by inflated counts."""
    es = MemoryEventStore()
    ev = termination_event("x", 1)
    es.publish("w", ev)
    es.publish("w", ev)  # broker-style redelivery
    es.commit("w", [ev.id])
    assert [e.id for e in es.committed_events("w")] == [ev.id]
    assert es.lag("w") == 0

    from repro.bus import PartitionedEventStore

    ps = PartitionedEventStore(4, partitioner=lambda s, n: int(s[1]) % n)
    a = termination_event("p0", 1)
    b = termination_event("p1", 2)
    ps.publish("w", a)
    ps.publish("w", a)  # duplicate in partition 0
    ps.publish("w", b)
    ps.commit("w", [a.id, b.id])
    # the duplicate must not make commit_partitions break before partition 1
    assert ps.lag("w") == 0
    assert sorted(e.id for e in ps.committed_events("w")) == sorted([a.id, b.id])


def test_join_backends_agree():
    np = pytest.importorskip("numpy")
    from repro.kernels.event_join.dispatch import resolve_join_backend

    rng = np.random.default_rng(3)
    events = rng.integers(0, 50, 4096).astype(np.int32)
    counts = rng.integers(0, 5, 50).astype(np.int32)
    expected = rng.integers(1, 120, 50).astype(np.int32)
    _, np_fn = resolve_join_backend("numpy")
    nc_np, f_np = np_fn(events, counts, expected)
    try:
        _, jax_fn = resolve_join_backend("jax")
    except Exception:
        pytest.skip("jax unavailable")
    nc_jx, f_jx = jax_fn(events, counts, expected)
    assert (nc_np == nc_jx).all() and (f_np == f_jx).all()


@pytest.mark.parametrize("vector_join,backend", [(None, "numpy"),
                                                 ("off", "off")])
def test_join_backend_is_recorded(vector_join, backend):
    """Which backend joins a worker's batches shows in its scrape; ``auto``
    (the default) resolves to the host path where no TPU is present."""
    w = _mk_worker(vector_join=vector_join)
    assert w.join_backend == backend
    assert w.metrics_snapshot()["gauges"] == {f"tf_join_backend_{backend}": 1}


def test_tpu_probe_propagates_device_errors(monkeypatch):
    """A device that fails to initialize is an error, not "no TPU": the
    probe must not quietly send the join to the host."""
    import jax

    from repro.kernels.event_join import dispatch

    def broken():
        raise RuntimeError("TPU is held by another process")

    monkeypatch.setattr(jax, "devices", broken)
    saved = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="held by another process"):
            dispatch._on_tpu()
    finally:
        jax.config.update("jax_platforms", saved)


# -- incremental checkpointing -------------------------------------------------

def test_delta_checkpoint_equals_full_rewrite(tmp_path):
    """FileStateStore contexts after N delta checkpoints == the contexts a
    MemoryStateStore (authoritative merge) holds after the same run."""
    fs = FileStateStore(str(tmp_path / "fs"))
    ms = MemoryStateStore()
    streams = random.Random(5)
    events = [termination_event(f"s{i % 3}", i) for i in range(60)]
    ctxs = []
    for ss in (fs, ms):
        w = _mk_worker(state_store=ss, batch_plane=True)
        for i in range(3):
            w.add_trigger(make_trigger(
                f"s{i}", condition={"name": "counter", "expected": 7,
                                    "reset_on_fire": True,
                                    "exactly_once": True},
                action={"name": "noop"}, trigger_id=f"t{i}", transient=False))
        w.event_store.publish_batch("w", events)
        for _ in range(20):
            if w.run_once(9) == 0:
                break
        ctxs.append(ss.get_contexts("w"))
    assert ctxs[0] == ctxs[1]
    # the delta log is real JSONL
    log = tmp_path / "fs" / "w" / "contexts.delta.jsonl"
    assert log.exists()
    lines = [json.loads(x) for x in log.read_text().splitlines() if x.strip()]
    assert lines, "expected incremental checkpoint records"


def test_crash_recovery_replays_from_delta_log(tmp_path):
    """Kill a worker mid-stream (uncommitted events), restart from the same
    stores: replay over delta-checkpointed contexts must converge to the same
    final state as an uninterrupted run."""
    def run(crash_after):
        es = MemoryEventStore()
        ss = FileStateStore(str(tmp_path / f"crash{crash_after}"))
        w = TFWorker("w", es, ss, FunctionBackend(es, inline=True),
                     commit_policy="every_batch", batch_plane=True)
        w.add_trigger(make_trigger(
            "x", condition={"name": "counter", "expected": 100,
                            "aggregate": False, "exactly_once": True},
            action={"name": "noop"}, trigger_id="t", transient=False))
        es.publish_batch("w", [termination_event("x", i) for i in range(100)])
        for _ in range(crash_after):
            w.run_once(13)
        # crash: drop the worker, keep the stores.  Uncommitted events are
        # redelivered to the successor (at-least-once, §3.4).
        w2 = TFWorker("w", es, ss, FunctionBackend(es, inline=True),
                      commit_policy="every_batch", batch_plane=True)
        _drain(w2, batch=13)
        return dict(w2.context_of("t")), w2.stats.fires

    ctx_crash, _ = run(crash_after=4)
    ctx_clean, _ = run(crash_after=0)
    assert ctx_crash["count"] == ctx_clean["count"] == 100


def test_delta_log_compaction(tmp_path):
    ss = FileStateStore(str(tmp_path / "c"), compact_every=5)
    for i in range(12):
        ss.put_contexts_delta("w", {"t": {"set": {"count": i, f"k{i}": i}}})
    got = ss.get_contexts("w")["t"]
    assert got["count"] == 11
    assert all(got[f"k{i}"] == i for i in range(12))
    # two compactions happened: the log holds < compact_every lines
    log = tmp_path / "c" / "w" / "contexts.delta.jsonl"
    lines = [x for x in log.read_text().splitlines() if x.strip()] \
        if log.exists() else []
    assert len(lines) < 5
    # deletions survive compaction
    ss.put_contexts_delta("w", {"t": {"del": ["k3"]}})
    assert "k3" not in ss.get_contexts("w")["t"]


def test_delta_log_ignores_torn_tail(tmp_path):
    ss = FileStateStore(str(tmp_path / "torn"))
    ss.put_contexts_delta("w", {"t": {"replace": {"count": 1}}})
    ss.put_contexts_delta("w", {"t": {"set": {"count": 2}}})
    log = tmp_path / "torn" / "w" / "contexts.delta.jsonl"
    with open(log, "a") as f:
        f.write('{"t": {"set": {"count": 99')  # crash mid-append
    fresh = FileStateStore(str(tmp_path / "torn"))
    assert fresh.get_contexts("w")["t"]["count"] == 2


def test_delta_log_missing_trailing_newline_is_torn(tmp_path):
    """A final line that parses as JSON but lacks its newline was never
    acknowledged (fsync cannot have returned) — it must be treated as torn
    and truncated before new appends land."""
    root = str(tmp_path / "nl")
    ss = FileStateStore(root)
    ss.put_contexts_delta("w", {"t": {"replace": {"count": 1}}})
    ss.put_contexts_delta("w", {"t": {"set": {"count": 2}}})
    log = tmp_path / "nl" / "w" / "contexts.delta.jsonl"
    data = log.read_bytes()
    log.write_bytes(data[:-1])  # strip the final newline: incomplete append
    restarted = FileStateStore(root)
    assert restarted.get_contexts("w")["t"]["count"] == 1
    restarted.put_contexts_delta("w", {"t": {"set": {"count": 3}}})
    assert restarted.get_contexts("w")["t"]["count"] == 3
    assert FileStateStore(root).get_contexts("w")["t"]["count"] == 3


def test_seen_ids_serialized_sorted(tmp_path):
    """The in-memory dedup set checkpoints as a sorted list (satellite 1)."""
    ss = FileStateStore(str(tmp_path / "seen"))
    w = _mk_worker(state_store=ss)
    w.add_trigger(make_trigger(
        "x", condition={"name": "counter", "expected": 100,
                        "exactly_once": True},
        action={"name": "noop"}, trigger_id="t", transient=False))
    evs = [termination_event("x", i) for i in range(10)]
    w.event_store.publish_batch("w", evs)
    _drain(w)
    stored = ss.get_contexts("w")["t"]["seen_ids"]
    assert isinstance(stored, list)
    assert stored == sorted(stored)
    assert set(stored) == {e.id for e in evs}


def test_put_triggers_single_write(tmp_path):
    """Dirty-trigger checkpointing batches all specs into one file write."""
    ss = FileStateStore(str(tmp_path / "trg"))
    writes = []
    orig = ss._write

    def counting_write(path, obj):
        writes.append(os.path.basename(path))
        orig(path, obj)

    ss._write = counting_write
    w = _mk_worker(state_store=ss)
    for i in range(5):
        w.add_trigger(make_trigger(
            "x", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"t{i}", transient=True))
    writes.clear()
    # one batch fires all five transient triggers -> all dirty
    w.event_store.publish("w", termination_event("x", 1))
    _drain(w)
    assert not any(t.enabled for t in w.triggers.values())
    assert writes.count("triggers.json") == 1
    assert ss.get_triggers("w")["t0"]["enabled"] is False


def test_memory_delta_path_matches_put_contexts():
    ms = MemoryStateStore()
    ms.put_contexts("w", {"t": {"a": 1, "b": 2}})
    ms.put_contexts_delta("w", {"t": {"set": {"b": 3, "c": 4}, "del": ["a"]}})
    assert ms.get_contexts("w")["t"] == {"b": 3, "c": 4}
    ms.put_contexts_delta("w", {"t": {"replace": {"z": 0}}, "u": {"set": {"n": 1}}})
    got = ms.get_contexts("w")
    assert got["t"] == {"z": 0}
    assert got["u"] == {"n": 1}
