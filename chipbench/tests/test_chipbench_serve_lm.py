"""The served Yi-9B stage (``harness/serve_lm.py``) at a tiny size on the
CPU: batches of mixed prompt lengths come out correct against the plain
reference, and not with the padding mask taken out; the serve readers read
the program's counters, and nothing where the program keeps none; the
decode step's least bytes match a count by hand."""
import json

import pytest

from chipbench.harness import bench, serve_lm
from chipbench.harness.bench import reader
from chipbench.harness.serve_work import (decode_step_bytes,
                                          mean_kv_tokens_per_step)
from chipbench.tests.test_chipbench_cells import TINY_SERVE, _run

CONFIG = bench.BENCH_DIR / "configs" / "yi-9b-l24-served.json"
MIXED = {"median": 8, "sigma": 1.0, "min": 4, "max": 32,
         "round_up_to": [8, 16, 32]}


def _mixed_run(seed=2**31 + 11):
    cfg = json.loads(CONFIG.read_text())
    cfg.update(TINY_SERVE["cfg"])
    cfg["check"] = dict(TINY_SERVE["cfg"]["check"], sample=8)
    traffic = bench.tr.load_traffic("serve-lognormal")
    lognormal = dict(traffic["items"]["prompt_len"], **MIXED)
    traffic.update(TINY_SERVE["traffic"], items={"prompt_len": lognormal})
    return serve_lm.run(cfg, traffic, seed, 1.5, None, lambda: 0.0,
                        lambda: None)


def test_mixed_length_batches_are_correct():
    r = _mixed_run()
    assert bench.judge(r["checks"]), r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the sample holds requests that were padded in their batch
    assert any(d["batch_len"] > d["prompt_len"] for d in r["sample_detail"])
    assert r["e2e"]["fire_latency_p95_ms"] > 0
    assert serve_lm.model_config(json.loads(CONFIG.read_text())).rms_eps == 1e-6


def test_unmasked_batches_are_not_correct(monkeypatch):
    """The padding mask taken out (prefill never told the prompt lengths,
    as before the mask existed): padded requests read off the reference."""
    from repro.models import Model

    prefill = Model.prefill

    def unmasked(self, params, batch, max_len=None):
        batch = {k: v for k, v in batch.items() if k != "lengths"}
        return prefill(self, params, batch, max_len=max_len)

    monkeypatch.setattr(Model, "prefill", unmasked)
    r = _mixed_run()
    checks = {c["name"]: c for c in r["checks"]}
    assert not bench.judge(r["checks"])
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]


def test_traced_serve_reads_its_program_layers():
    r = _run("serve.steady", trace=True)
    assert r["correct"], r["checks"]
    for name in ("request_wait_ms.serve", "prefill_ms.serve",
                 "decode_step_ms.serve", "pad_share.serve",
                 "host_syncs_per_token.serve"):
        assert name in r["metrics"], name
    assert r["metrics"]["host_syncs_per_token.serve"]["value"] == 1.0
    # the worker loop the batcher runs on
    for name in ("consume_lag_ms.join", "batch_eval_us_per_event.join",
                 "checkpoint_ms.join", "fire_wait_ms.join",
                 "fire_delay_ms.join"):
        assert name in r["metrics"], name
    # no device on the CPU: no device metric is reported
    for name in ("decode_hbm_roofline.serve", "mfu.serve",
                 "device_idle_share.join"):
        assert name not in r["metrics"], name


def test_decode_programs_are_those_wholly_in_the_window():
    rows = {"host": [["chipbench.window", 100, 900]],
            "modules": [["jit_decode", 50, 100],      # opens before the window
                        ["jit_decode", 150, 100],
                        ["jit__lambda", 300, 200],    # another program
                        ["jit_decode", 600, 300],
                        ["jit_decode", 950, 100]]}    # closes after it
    assert serve_lm.program_runs(rows, serve_lm.DECODE_PROGRAM) == {
        "runs": 2, "device_s": 400 / 1e9}


# -- the readers ------------------------------------------------------------------
SIZES = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10}
PEAKS = {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1e9}


def _hist(s, n):
    return {"bounds": [1.0], "counts": [n, 0], "sum": s, "count": n}


def _snap(counters, histograms):
    return {"counters": counters, "gauges": {}, "histograms": histograms}


# two batches of 2 requests, prompts 3 + 5 and 4 + 4 padded to 5 and 4,
# 2 new tokens each; the window gains sum 0.4 s over 4 decode steps
COUNTERS = {"tf_serve_batches_total": 2, "tf_serve_requests_total": 4,
            "tf_serve_prompt_tokens_total": 16, "tf_serve_pad_tokens_total": 2,
            "tf_serve_tokens_total": 8, "tf_serve_host_syncs_total": 8}
HISTS = {"tf_serve_request_wait_seconds": _hist(2.0, 4),
         "tf_serve_prefill_seconds": _hist(0.3, 2),
         "tf_serve_decode_step_seconds": _hist(0.4, 4)}
BASE = {"tf_serve_request_wait_seconds": _hist(1.0, 2),
        "tf_serve_prefill_seconds": _hist(0.1, 1),
        "tf_serve_decode_step_seconds": _hist(0.2, 2)}


def _gained():
    """A run whose window gained ``COUNTERS`` and ``HISTS``."""
    h1 = {k: _hist(BASE[k]["sum"] + v["sum"], BASE[k]["count"] + v["count"])
          for k, v in HISTS.items()}
    return {"snap0": _snap(COUNTERS, BASE),
            "snap1": _snap({k: 2 * v for k, v in COUNTERS.items()}, h1),
            "cfg": SIZES, "peaks": PEAKS, "decode_programs": DECODE_PROGRAMS}


# the trace holds 3 decode executions wholly in the window, 0.2 s of device
DECODE_PROGRAMS = {"runs": 3, "device_s": 0.2}

# a decode step reads, in bf16, 2 layers of q, k, v, o and the three MLP
# matrices, and the head; and 2 (k, v) x 2 layers x 1 head x 4 per cached
# position
WEIGHT_BYTES = 2 * (2 * (8 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8 + 3 * 8 * 16)
                    + 8 * 10)
KV_BYTES = 2 * 2 * 2 * 1 * 4
# positions read, step by step: batch 1 (3 + 1) + (5 + 1), then (3 + 2) +
# (5 + 2); batch 2 likewise (4 + 1) * 2, then (4 + 2) * 2: 44 over 4 steps
KV_PER_STEP = 44 / 4

WANT = {
    "request_wait_ms.serve": 1e3 * 2.0 / 4,
    "prefill_ms.serve": 1e3 * 0.3 / 2,
    "decode_step_ms.serve": 1e3 * 0.4 / 4,
    "pad_share.serve": 100.0 * 2 / 18,
    "host_syncs_per_token.serve": 1.0,
    "decode_hbm_roofline.serve": 100.0 * 3 * (WEIGHT_BYTES + KV_PER_STEP * KV_BYTES)
    / 1e6 / 0.2,
}


def test_decode_bytes_match_a_count_by_hand():
    assert mean_kv_tokens_per_step(16, 4, 2, 2) == KV_PER_STEP
    assert decode_step_bytes(SIZES, KV_PER_STEP) == (
        WEIGHT_BYTES + KV_PER_STEP * KV_BYTES)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_serve_reader_reads_the_window_gain(metric):
    assert reader(metric)(_gained()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_serve_reader_reads_nothing_without_its_counters(metric):
    other = {"tf_join_kernel_seconds": _hist(0.5, 3)}
    run = {"snap0": _snap({}, {}), "snap1": _snap({"tf_events_total": 5}, other),
           "cfg": SIZES, "peaks": PEAKS}
    assert reader(metric)(run) is None
    assert reader(metric)({}) is None
