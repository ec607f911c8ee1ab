"""The controls at a size a test run holds, and the refusal to report
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

from chipbench.harness import bench, join, serve

CHECKOUT = Path(__file__).resolve().parents[2]

SMALL = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-5, "name": "small",
         "serving": {"max_batch": 4, "max_new_tokens": 16, "max_len": 32},
         # the limit lies between the program's widest gap at this size
         # (0.040 over seeds 1-6) and the float8 control's least (0.19)
         "check": {"sample": 4, "max_logit_gap": 0.1}}


def test_float8_control_separates_from_the_program():
    """The program's widest gap and the float8 control's, on three seeds:
    put through the rule that decides ``correct``, with the same limit, the
    program comes out correct and the control does not."""
    traffic = {"rate_per_s": 8, "warmup_s": 0.6, "tail_s": 30,
               "arrivals": {"kind": "poisson"},
               "items": {"prompt_len": {"kind": "fixed", "value": 16}}}
    program, control = [], []
    for seed in (1, 2, 3):
        r = serve.run(SMALL, traffic, seed, 1.0, None, lambda: 0.0,
                      lambda: None, control=True)
        program.append({c["name"]: c["value"] for c in r["checks"]}
                       ["max_logit_gap"])
        control.append(r["control_checks"][0]["value"])
        assert bench.judge(r["checks"]), r["checks"]
        assert not bench.judge(r["control_checks"]), r["control_checks"]
        assert r["control_checks"][0]["limit"] == SMALL["check"]["max_logit_gap"]
    assert min(control) > 3 * max(program), (program, control)


def test_batch_firing_at_shutdown_serves_nothing(monkeypatch):
    """Two batches reach the worker at once; the run stops while the first
    is on the device.  The second serves nothing, and the weights are freed
    only once the worker's thread has ended."""
    from repro.core import KedaAutoscaler, Triggerflow
    from repro.serving.engine import ServingEngine

    good = ServingEngine.generate_batch
    started, served, errors = threading.Event(), [], []

    def slow(self, requests):
        started.set()
        time.sleep(0.5)
        try:
            out = good(self, requests)
        except Exception as e:      # a batch run on freed weights
            errors.append(e)
            raise
        served.append(len(out))
        return out

    monkeypatch.setattr(ServingEngine, "generate_batch", slow)
    tf = Triggerflow(inline_functions=True)
    eng = serve.engine_class()(
        serve.model_config(SMALL), tf, serve.WORKFLOW, max_batch=4,
        max_new_tokens=4, max_len=32,
        weights=lambda model: serve.boxed_weights(model, SMALL, 1))
    eng.deploy()
    for i in range(8):
        eng.submit(f"r{i}", [1] * 8)
    scaler = KedaAutoscaler(tf, poll_interval=0.05, grace_period=0.5).start()
    assert started.wait(60)
    serve.stop_serving(tf, scaler, eng)
    assert not tf.worker_alive(serve.WORKFLOW)
    assert eng.params is None
    assert served == [4] and not errors


def test_join_control_breaks_exactly_once():
    cfg = {"triggers": 10, "expected": 50, "reset_on_fire": True,
           "deployment": {"num_shards": 2, "num_partitions": 4,
                          "commit_policy": "every_batch", "batch_plane": True,
                          "keep_event_log": False}}
    traffic = {"rate_per_s": 2000, "warmup_s": 0.3,
               "arrivals": {"kind": "poisson"},
               "items": {"subject": {"kind": "uniform", "values": 10}}}
    r = join.run(cfg, traffic, 9, 1.0, None, lambda: 0.0, lambda: None,
                 control=True)
    assert all(c["value"] == 0 for c in r["checks"])
    got = {c["name"]: c["value"] for c in r["control_checks"]}
    assert got["context_diff"] > 0
    assert bench.judge(r["checks"]) and not bench.judge(r["control_checks"])


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "join.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(p):
    return p.returncode != 0 and not any(
        line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_without_a_tpu():
    p = _run_py(CHECKOUT)
    assert _no_result(p), (p.returncode, p.stdout[-500:])
    assert "not a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert _no_result(p), (p.returncode, p.stdout[-500:])
    json.loads((tmp_path / "BENCHMARK.json").read_text())
