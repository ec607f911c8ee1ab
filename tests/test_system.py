"""End-to-end behaviour tests: the paper's system qualities exercised
together — autoscaling, crash recovery across the full stack (events +
trigger contexts + model checkpoints), and trigger-orchestrated serving."""
import time

from repro.configs import get_config
from repro.core import (FileEventStore, FileStateStore, KedaAutoscaler,
                        Triggerflow, make_trigger, termination_event)
from repro.serving.engine import ServingEngine


def test_autoscaler_scales_up_and_to_zero():
    tf = Triggerflow(inline_functions=True, commit_policy="every_batch")
    for i in range(4):
        tf.create_workflow(f"w{i}")
        tf.add_trigger(f"w{i}", make_trigger(
            "tick", condition={"name": "true"}, action={"name": "noop"},
            trigger_id=f"w{i}/t", transient=False))
    scaler = KedaAutoscaler(tf, poll_interval=0.03, grace_period=0.15).start()
    for i in range(4):
        for j in range(50):
            tf.publish(f"w{i}", termination_event("tick", j))
    deadline = time.time() + 10
    while time.time() < deadline and any(
            tf.event_store.lag(f"w{i}") for i in range(4)):
        time.sleep(0.02)
    peak = max(n for _, n, _ in scaler.timeline) if scaler.timeline else 0
    time.sleep(0.6)
    scaler._tick()
    final = scaler.timeline[-1][1]
    scaler.stop()
    tf.shutdown()
    assert peak >= 1
    assert final == 0  # scale to zero
    assert scaler.scale_ups >= 4


def test_full_stack_crash_recovery(tmp_path):
    """Workflow-level (event replay) + state-level (checkpoint) recovery."""
    cfg = get_config("llama3.2-3b", smoke=True)
    work = str(tmp_path / "ckpt")
    es = FileEventStore(str(tmp_path / "ev"))
    ss = FileStateStore(str(tmp_path / "st"))
    tf = Triggerflow(event_store=es, state_store=ss, inline_functions=True)
    # run 2 of 4 chunks, then "crash" the orchestrator
    from repro.training.trainer import JaxCluster, build_training_workflow

    cluster = JaxCluster(cfg, work, batch=4, seq=16, total_steps=8)
    build_training_workflow(tf, cluster, "t1", total_steps=8, chunk_steps=2)
    tf.init_workflow("t1")
    w = tf.worker("t1")
    while cluster.step < 4:
        w.run_once()
    tf.evict_worker("t1")

    # restart: fresh stores over the same files, fresh cluster (params lost)
    es2 = FileEventStore(str(tmp_path / "ev"))
    ss2 = FileStateStore(str(tmp_path / "st"))
    tf2 = Triggerflow(event_store=es2, state_store=ss2, inline_functions=True)
    cluster2 = JaxCluster(cfg, work, batch=4, seq=16, total_steps=8)
    build_training_workflow(tf2, cluster2, "t1", total_steps=8, chunk_steps=2)
    res = tf2.run_until_complete("t1", timeout=120)
    assert res["status"] == "succeeded"
    assert cluster2.step == 8
    assert cluster2.history[0]["step"] > 2  # resumed, not restarted


def test_trigger_orchestrated_serving_batches():
    tf = Triggerflow(inline_functions=True)
    eng = ServingEngine(get_config("llama3.2-3b", smoke=True), tf, "srv",
                        max_batch=3, max_new_tokens=3, max_len=48)
    eng.deploy()
    for i in range(6):
        eng.submit(f"r{i}", [1 + i, 2 + i, 3 + i])
    w = tf.worker("srv")
    for _ in range(30):
        w.run_once()
    done = [e for e in w.event_log if e.subject.startswith("serve|done|")]
    assert len(done) == 6
    assert eng.batches == 2  # 6 requests / max_batch 3
    for e in done:
        toks = e.data["result"]["tokens"]
        assert len(toks) == 3
        assert all(0 <= t < eng.cfg.vocab for t in toks)


def test_compile_cache_dir_from_env_else_fixed_in_checkout(monkeypatch,
                                                           tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code then sets nothing;
    without it the cache sits at one fixed path inside the checkout."""
    from pathlib import Path

    import jax

    from repro.launch.compile_cache import use_compile_cache

    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == saved
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
