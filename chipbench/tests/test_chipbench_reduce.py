"""The benchmark's arithmetic: trace reduction, operations and bytes, the
traffic generator and the join tally."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.harness import trace, traffic, work
from chipbench.references import join_tally

DATA = Path(__file__).parent / "data"


# -- trace reduction ------------------------------------------------------------
def _rows():
    return json.loads((DATA / "trace_small.json").read_text())


def test_recorded_trace_busy_idle_and_breakdown():
    rows = _rows()
    window = trace.window_of(rows)
    ops = rows["device"][sorted(rows["device"])[0]]
    # union by brute force over every nanosecond-bucket boundary
    edges = sorted({window[0], window[1]} | {x for r in ops
                                              for x in (r[1], r[1] + r[2])})
    busy = 0
    for a, b in zip(edges, edges[1:]):
        if a >= window[0] and b <= window[1] and any(
                r[1] <= a and b <= r[1] + r[2] for r in ops):
            busy += b - a
    s = trace.summarize(rows)
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert s["window_s"] == pytest.approx((window[1] - window[0]) / 1e9)
    assert 0 < s["busy_s"] < s["window_s"]
    gaps = s["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10 and all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # the longest gap is at most the idle time of the window
    assert gaps[0][1] <= s["window_s"] - s["busy_s"] + 1e-9
    top = s["breakdown"]["device_ops"]
    assert len(top) <= 10 and sum(t for _, t in top) <= s["busy_s"] * len(
        rows["device"]) + sum(r[2] for r in ops) / 1e9


def test_kernel_time_finds_the_join_kernel_only():
    rows = _rows()
    window = trace.window_of(rows)
    names = ("event_join", "_join_kernel")
    want = sum(max(0, min(s + d, window[1]) - max(s, window[0]))
               for ops in rows["device"].values() for n, s, d, m in ops
               if any(k in n for k in names))
    assert want > 0
    assert trace.kernel_ns(rows, window, names) == want
    # the program's copies around the kernel are not the kernel
    assert all(m == "jit_event_join" for ops in rows["device"].values()
               for n, s, d, m in ops if "event_join" in n)
    assert trace.kernel_ns(rows, window, ("no-such-kernel",)) == 0


def test_merge_and_gap_labels():
    assert trace.merge([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == [
        (1, 4), (5, 11)]
    rows = {"device": {"/device:TPU:0": [["a", 10, 10, ""], ["b", 50, 10, ""]]},
            "host": [["chipbench.window", 0, 100],
                     ["chipbench.publish", 22, 20], ["chipbench.other", 61, 3]]}
    s = trace.summarize(rows)
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["breakdown"]["idle_gaps"][0] == ["other", pytest.approx(40e-9)]
    assert ["publish", pytest.approx(30e-9)] in s["breakdown"]["idle_gaps"]
    assert s["breakdown"]["device_ops"] == [["/a", 1e-8], ["/b", 1e-8]]


def test_trace_without_a_device_reads_nothing():
    rows = {"device": {}, "host": [["chipbench.window", 0, 100]]}
    s = trace.summarize(rows)
    assert s["busy_s"] == 0.0
    from chipbench.harness.readers import idle_share_pct
    assert idle_share_pct({"trace": s}) is None


# -- operations and bytes ------------------------------------------------------------
def test_event_join_work():
    ops, nbytes = work.event_join_work(112, 13)
    assert ops == 112
    assert nbytes == 4 * 112 + 8 * 13 + 8 * 13
    assert work.roofline_s(ops, nbytes, 197e12, 819e9) == pytest.approx(
        nbytes / 819e9)


SIZES = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
         "vocab_size": 100}


def test_dense_lm_flops_by_counting():
    L, D, H, KV, hd, F, V = 2, 64, 4, 2, 16, 96, 100
    per_layer = 2 * (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F)
    P, new = 10, 4
    want = 0
    for pos in range(P + new - 1):           # every token the body runs
        want += L * per_layer
        want += L * 4 * H * hd * (pos + 1)   # QK and PV over pos + 1 keys
    want += new * 2 * D * V                  # the head, once per new token
    assert work.dense_lm_request_flops(SIZES, P, new) == want
    assert work.dense_lm_matmul_params(SIZES) == L * per_layer // 2


# -- traffic -----------------------------------------------------------------------
UNIFORM = {"rate_per_s": 500, "warmup_s": 1.0, "arrivals": {"kind": "poisson"},
           "items": {"subject": {"kind": "uniform", "values": 10}}}


def test_every_seed_gets_the_same_work():
    a = traffic.schedule(UNIFORM, 3, 4.0)
    b = traffic.schedule(UNIFORM, 2**31 + 17, 4.0)
    assert abs(len(a) - len(b)) <= 2
    assert a.due[0] == pytest.approx(-1.0)
    assert (np.diff(a.due) >= 0).all() and a.due[-1] < 4.0
    assert np.sort(np.diff(a.due))[5:-5] == pytest.approx(
        np.sort(np.diff(b.due))[5:-5], rel=0.05)
    ca = np.bincount(a.attrs["subject"], minlength=10)
    assert ca.max() - ca.min() <= 1
    assert not (a.attrs["subject"][:50] == b.attrs["subject"][:50]).all()
    again = traffic.schedule(UNIFORM, 3, 4.0)
    assert (again.due == a.due).all()
    assert (again.attrs["subject"] == a.attrs["subject"]).all()


def test_prompt_length_kinds():
    rng = traffic.rng_for(5, "x")
    spec = {"kind": "lognormal", "median": 128, "sigma": 1.0, "min": 16,
            "max": 512, "round_up_to": [64, 128, 256, 512]}
    n = traffic.kind("lognormal").draw(spec, 1000, rng)
    assert set(n.tolist()) == {64, 128, 256, 512}
    assert abs((n <= 128).mean() - 0.5) < 0.01   # the median is 128
    fixed = traffic.kind("fixed").draw({"kind": "fixed", "value": 512}, 7, rng)
    assert (fixed == 512).all()


def test_committed_traffic_files_name_known_kinds():
    for path in sorted(traffic.TRAFFIC_DIR.glob("*.json")):
        t = traffic.load_traffic(path.stem)
        sched = traffic.schedule(t, 11, 2.0)
        assert len(sched) > 0, path.name
        assert set(sched.attrs) == set(t["items"]), path.name


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="no traffic kind"):
        traffic.schedule(dict(UNIFORM, arrivals={"kind": "no-such-kind"}), 1, 1.0)


# -- the join tally -------------------------------------------------------------------
def test_tally_by_hand():
    subjects = np.array([0, 1, 0, 0, 1, 0, 0])
    results = np.array([10, 20, 11, 12, 21, 13, 14])
    t = join_tally.tally(subjects, results, 3, 2)
    assert t[0] == {"fires": 2, "count": 1, "results": [14],
                    "fired_results": [12, 13]}
    assert t[1] == {"fires": 1, "count": 0, "results": [],
                    "fired_results": [20, 21]}
    assert t[2] == {"fires": 0, "count": 0, "results": [],
                    "fired_results": None}
    assert join_tally.same_context(
        {"count": 1, "results": [14], "fired_results": [12, 13]}, t[0])
    assert not join_tally.same_context(
        {"count": 1, "results": [14], "fired_results": [11, 13]}, t[0])
