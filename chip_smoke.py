"""Run Triggerflow's main path once on one TPU chip, and check what comes out.

    python chip_smoke.py [--seed N]

Two phases, each through the entry points a user calls:

``join``  The paper's Table-1 "Join" at its own size: 100 ``counter``
          triggers × 2000 events (200 000 events) published to the
          ``Triggerflow(num_shards=4, num_partitions=8)`` facade and drained by
          the threaded shard pool, whose batch plane folds non-firing join
          batches into the Pallas ``event_join`` kernel compiled for the chip.
          The same workload on the scalar interpreter (``batch_plane=False``)
          is the reference: the fire count and every trigger's final context
          must be identical.
``serve`` Trigger-batched serving of full-width ``llama3.2-3b`` (random
          weights from a fixed seed): ``ServingEngine`` behind
          ``KedaAutoscaler``, as ``launch/serve.py`` runs it, serves 8
          requests at ``max_batch=4`` with 16 new tokens each.  A single
          teacher-forced ``Model.forward`` over prompt + served tokens is the
          reference: each served token must sit within ``TOL_ULPS`` bf16 ulps
          of that row's largest logit.

There is no four-chip phase: serving and training run on one device, and
the shard processes of ``ProcessShardPool`` own no chip.

The script refuses to run anywhere JAX's default device is not a TPU.  Its
last line on success is ``{"ok": true, "device": {...}}``; any failure exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core import (KedaAutoscaler, Triggerflow, make_trigger,  # noqa: E402
                        termination_event)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# A served token passes when the reference puts it within this many bf16
# ulps (at the row's largest logit) of the top: logits leave the model as
# bf16, and the decode step and the full-sequence forward add up attention
# and the residual stream in different orders through every layer.
TOL_ULPS = 8


def _bf16_ulp(x: np.ndarray) -> np.ndarray:
    """Spacing of bf16 values (8 significant bits) at magnitude ``x``."""
    return np.exp2(np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def _table1_join(batch_plane: bool, n_triggers: int, events_each: int,
                 seed: int, timeout: float) -> dict:
    tf = Triggerflow(num_shards=4, num_partitions=8, inline_functions=True,
                     commit_policy="every_batch")
    tf.pool.batch_plane = batch_plane
    tf.pool.keep_event_log = False
    tf.create_workflow("join")
    tids = [f"jt{t}" for t in range(n_triggers)]
    for t, tid in enumerate(tids):
        tf.add_trigger("join", make_trigger(
            f"j{t}", condition={"name": "counter", "expected": events_each},
            action={"name": "noop"}, trigger_id=tid, transient=False))
    rng = np.random.default_rng(seed)
    subjects = rng.permutation(np.repeat(np.arange(n_triggers), events_each))
    results = rng.integers(0, 1 << 30, subjects.size)
    tf.event_store.publish_batch("join", [
        termination_event(f"j{s}", int(r)) for s, r in zip(subjects, results)])
    try:
        t0 = time.perf_counter()
        tf.start_shards("join")
        deadline = time.monotonic() + timeout
        while tf.event_store.lag("join") > 0:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "join did not drain: " + tf.pool.failure_diagnostics("join"))
            time.sleep(0.001)
        wall = time.perf_counter() - t0
        snap = tf.metrics_snapshot("join")
        return {"wall_seconds": wall,
                "fires": tf.pool.total_fires("join"),
                "contexts": {tid: tf.get_trigger_context("join", tid)
                             for tid in tids},
                "counters": snap["counters"], "gauges": snap["gauges"]}
    finally:
        tf.shutdown()


def join_phase(n_triggers: int = 100, events_each: int = 2000, seed: int = 0,
               timeout: float = 300.0) -> dict:
    """Table-1 join on the batch plane vs the scalar interpreter."""
    from repro.kernels.event_join.ops import event_join

    plane = _table1_join(True, n_triggers, events_each, seed, timeout)
    scalar = _table1_join(False, n_triggers, events_each, seed, timeout)
    backends = sorted(g[len("tf_join_backend_"):] for g in plane["gauges"]
                      if g.startswith("tf_join_backend_"))
    out = {
        "backends": backends,
        "calls": plane["counters"].get("tf_join_calls_total", 0),
        "events": plane["counters"].get("tf_join_events_total", 0),
        "kernel_shapes": event_join._cache_size(),
        "fires": plane["fires"], "reference_fires": scalar["fires"],
        "wall_seconds": plane["wall_seconds"],
        "reference_wall_seconds": scalar["wall_seconds"],
    }
    if plane["fires"] != n_triggers or scalar["fires"] != n_triggers:
        raise AssertionError(f"join fired {plane['fires']} times, reference "
                             f"{scalar['fires']}; expected {n_triggers}")
    diff = [t for t in plane["contexts"]
            if plane["contexts"][t] != scalar["contexts"][t]]
    if diff:
        raise AssertionError(f"{len(diff)} trigger contexts differ from the "
                             f"scalar interpreter's, e.g. {diff[0]}")
    if out["calls"] <= 0 or out["events"] <= 0:
        raise AssertionError(f"the vector join plane never ran: {out}")
    return out


def serve_phase(cfg, n_requests: int = 8, max_batch: int = 4,
                new_tokens: int = 16, prompt_len: int = 32, seed: int = 0,
                timeout: float = 600.0) -> dict:
    """Trigger-batched serving vs a teacher-forced forward pass."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    tf = Triggerflow(inline_functions=True)
    eng = ServingEngine(cfg, tf, "serve", max_batch=max_batch,
                        max_new_tokens=new_tokens, max_len=256)
    eng.deploy()
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (n_requests, prompt_len))
    scaler = KedaAutoscaler(tf, poll_interval=0.05, grace_period=0.5).start()
    t0 = time.perf_counter()
    try:
        for i, p in enumerate(prompts):
            eng.submit(f"req-{i}", p.tolist())
        deadline = time.monotonic() + timeout
        while eng.served < n_requests or tf.event_store.lag("serve") > 0:
            if time.monotonic() > deadline:
                break
            time.sleep(0.05)
        wall = time.perf_counter() - t0
    finally:
        scaler.stop()
        tf.shutdown()
    if eng.served != n_requests:
        raise AssertionError(f"served {eng.served} of {n_requests} requests")
    served = {e.data["result"]["id"]: e.data["result"]["tokens"]
              for e in tf.event_store.committed_events("serve")
              if e.subject.startswith("serve|done|")}
    if sorted(served) != sorted(f"req-{i}" for i in range(n_requests)):
        raise AssertionError(f"results for {sorted(served)}")
    tokens = np.array([served[f"req-{i}"] for i in range(n_requests)])
    if tokens.shape != (n_requests, new_tokens):
        raise AssertionError(f"served token array has shape {tokens.shape}")

    # Teacher forcing: position prompt_len-1+j of the reference predicts
    # served token j, given the prompt and served tokens 0..j-1.
    seq = jnp.asarray(np.concatenate([prompts, tokens[:, :-1]], axis=1),
                      jnp.int32)
    logits, _ = jax.jit(eng.model.forward)(eng.params, {"tokens": seq})
    rows = logits[:, prompt_len - 1:]
    top = np.asarray(rows.max(-1))
    picked = np.asarray(jnp.take_along_axis(
        rows, jnp.asarray(tokens)[..., None], -1)[..., 0])
    if not (np.isfinite(top).all() and np.isfinite(picked).all()):
        raise AssertionError("non-finite reference logits")
    gap_ulps = (top - picked) / _bf16_ulp(top)
    exact = np.asarray(rows.argmax(-1)) == tokens
    out = {"served": eng.served, "batches": eng.batches,
           "wall_seconds": wall, "exact_share": float(exact.mean()),
           "max_gap_ulps": float(gap_ulps.max()),
           "within_tol": int((gap_ulps <= TOL_ULPS).sum()),
           "tokens": int(tokens.size)}
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        out["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
    if out["within_tol"] != out["tokens"]:
        raise AssertionError(f"served tokens off the reference: {out}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the join's event order and the prompts")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r}, "
              "not a TPU", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)

    j = join_phase(seed=args.seed)
    print(f"join: backend {','.join(j['backends'])}; {j['fires']} fires "
          f"(scalar reference {j['reference_fires']}), contexts identical; "
          f"VectorJoinPlane.calls {j['calls']} .events {j['events']}; "
          f"{j['kernel_shapes']} kernel shapes compiled; wall "
          f"{j['wall_seconds']} s (reference {j['reference_wall_seconds']} s)",
          flush=True)
    if j["backends"] != ["pallas"]:
        raise SystemExit(f"join ran on {j['backends']}, not the Pallas kernel")

    from repro.configs import get_config

    s = serve_phase(get_config("llama3.2-3b"), seed=args.seed)
    print(f"serve: llama3.2-3b full width, {s['served']}/8 requests in "
          f"{s['batches']} batches, {s['tokens']} tokens, "
          f"{s['within_tol']} within {TOL_ULPS} bf16 ulps of the reference's "
          f"top logit (max gap {s['max_gap_ulps']} ulps), exact argmax share "
          f"{s['exact_share']}; wall {s['wall_seconds']} s; "
          f"peak_bytes_in_use {s.get('peak_bytes_in_use', 'not reported')}",
          flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
