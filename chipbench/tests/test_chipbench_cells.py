"""Each cell end to end at a tiny size on the CPU, with the harness's look
for a chip skipped: the run, its reduction and its comparison, and the
same run with the timed path broken underneath, which must not come out
correct."""
import json
import time

import pytest

from chipbench.harness import bench

TINY_JOIN = {"cfg": {"triggers": 10, "expected": 50},
             "traffic": {"rate_per_s": 2000, "warmup_s": 0.3,
                         "items": {"subject": {"kind": "uniform",
                                               "values": 10}}}}
TINY_SERVE = {
    "cfg": {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
            "serving": {"max_batch": 4, "max_new_tokens": 8, "max_len": 64},
            "check": {"sample": 4, "max_logit_gap": 0.5}},
    "traffic": {"rate_per_s": 8, "warmup_s": 0.6, "tail_s": 20,
                "items": {"prompt_len": {"kind": "fixed", "value": 16}}}}


def _tiny(workload):
    cell = {w["name"]: w for w in bench.load_benchmark()["workloads"]}[workload]
    return json.loads(json.dumps(TINY_SERVE if cell["config"] == "yi-9b-l24"
                                 else TINY_JOIN))


def _run(workload, trace=False, seconds=1.5):
    return bench.run_cell(workload, 2**31 + 5, seconds, trace,
                          time.perf_counter(), require_chip=False,
                          overrides=_tiny(workload))


CELLS = [w["name"] for w in bench.load_benchmark()["workloads"]]
CONFIGS = bench.BENCH_DIR / "configs"


@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end(workload):
    r = _run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    bm = bench.load_benchmark()
    cell = {w["name"]: w for w in bm["workloads"]}[workload]
    want = {m["name"] for m in bench.metrics_of(bm, cell, False)}
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"


def test_traced_join_reads_its_host_layers():
    r = _run("join.steady", trace=True)
    assert r["correct"]
    for name in ("consume_lag_ms.join", "batch_eval_us_per_event.join",
                 "checkpoint_ms.join", "window_compiles.join"):
        assert name in r["metrics"], name
    # no device on the CPU: no device metric is reported
    assert "device_idle_share.join" not in r["metrics"]
    assert "event_join_roofline.join" not in r["metrics"]
    assert r["device"]["window_s"] > 0


def _tiny_serve(seed=2**31 + 5):
    """The serve configuration at a tiny size, run once through its driver
    (``serve.fixed512`` waits outside ``BENCHMARK.json`` for its chip
    readings, so ``run_cell`` cannot name it)."""
    from chipbench.harness import serve as serve_mod

    cfg = json.loads((CONFIGS / "yi-9b-l24.json").read_text())
    cfg.update(TINY_SERVE["cfg"])
    traffic = bench.tr.load_traffic("serve-fixed512")
    traffic.update(TINY_SERVE["traffic"])
    return serve_mod.run(cfg, traffic, seed, 1.0, None, lambda: 0.0,
                         lambda: None)


def test_serve_driver_end_to_end():
    r = _tiny_serve()
    assert bench.judge(r["checks"]), r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(v > 0 for v in r["e2e"].values()), r["e2e"]


# -- faults in the timed path -----------------------------------------------------
def _break_join(monkeypatch, fn):
    from repro.kernels.event_join import dispatch

    monkeypatch.setattr(dispatch, "_numpy_join", fn)
    monkeypatch.setattr(dispatch, "_resolved", {})


@pytest.mark.parametrize("fault", ["altered_count", "half_left_out"])
def test_join_fault_is_not_correct(monkeypatch, fault):
    import numpy as np

    from repro.kernels.event_join import dispatch
    good = dispatch._numpy_join

    def altered(events, counts, expected):
        nc, fired = good(events, counts, expected)
        nc = nc.copy()
        nc[0] += 1
        return nc, fired

    def half(events, counts, expected):
        kept = events.copy()
        kept[len(kept) // 2:] = -1
        return good(kept, counts, expected)

    _break_join(monkeypatch, altered if fault == "altered_count" else half)
    r = _run("join.steady")
    assert not r["correct"]
    assert r["checks"]["context_diff"]["value"] > 0
    assert np.isfinite(r["checks"]["context_diff"]["value"])


@pytest.mark.parametrize("fault", ["altered_token", "half_left_out"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    from repro.serving.engine import ServingEngine
    good = ServingEngine.generate_batch

    def altered(self, requests):
        out = good(self, requests)
        for o in out:
            o["tokens"] = [(t + 128) % 256 for t in o["tokens"]]
        return out

    def half(self, requests):
        return good(self, requests[: len(requests) // 2])

    from chipbench.harness import serve as serve_mod

    monkeypatch.setattr(ServingEngine, "generate_batch",
                        altered if fault == "altered_token" else half)
    monkeypatch.setattr(serve_mod, "DRAIN_S", 3.0)
    r = _tiny_serve()
    checks = {c["name"]: c for c in r["checks"]}
    assert not bench.judge(r["checks"])
    key = "max_logit_gap" if fault == "altered_token" else "unserved"
    assert checks[key]["value"] > checks[key]["limit"]
