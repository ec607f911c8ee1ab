"""The readers of the program's own join and fire-path histograms: each
reads its window mean (or gain) where the program keeps the histogram, and
nothing where it does not, as on a program that predates it."""
import pytest

from chipbench.harness.bench import reader
from chipbench.tests.test_chipbench_cells import _run

# metric -> (histogram, what a gain of sum 0.2 over 4 observations in a
# 2 s window reads)
READERS = {
    "join_compile_ms_per_s.join": ("tf_join_compile_seconds", 0.2e3 / 2.0),
    "join_roundtrip_us.join": ("tf_join_roundtrip_seconds", 0.05e6),
    "join_host_us_per_event.join": ("tf_join_host_seconds", 0.05e6),
    "fire_wait_ms.join": ("tf_fire_wait_seconds", 0.05e3),
    "fire_delay_ms.join": ("tf_fire_delay_seconds", 0.05e3),
}
EMPTY = {"bounds": [1.0], "counts": [0, 0], "sum": 0.0, "count": 0}


def _run_with(histograms0, histograms1):
    return {"snap0": {"counters": {}, "gauges": {}, "histograms": histograms0},
            "snap1": {"counters": {}, "gauges": {}, "histograms": histograms1},
            "window_s": 2.0}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing_without_its_histogram(metric):
    other = dict(EMPTY, counts=[3, 0], sum=0.5, count=3)
    run = _run_with({"tf_join_kernel_seconds": EMPTY},
                    {"tf_join_kernel_seconds": other})
    assert reader(metric)(run) is None
    assert reader(metric)({}) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_the_window_gain(metric):
    name, want = READERS[metric]
    before = dict(EMPTY, counts=[2, 0], sum=1.0, count=2)
    after = dict(EMPTY, counts=[6, 0], sum=1.2, count=6)
    assert reader(metric)(_run_with({name: before}, {name: after})) \
        == pytest.approx(want)


def test_compile_rate_reads_zero_when_nothing_compiled_in_the_window():
    h = dict(EMPTY, counts=[2, 0], sum=1.0, count=2)
    run = _run_with({"tf_join_compile_seconds": h},
                    {"tf_join_compile_seconds": h})
    assert reader("join_compile_ms_per_s.join")(run) == 0.0


def test_traced_join_reads_the_program_histograms():
    r = _run("join.steady", trace=True)
    assert r["correct"]
    for metric in READERS:
        assert metric in r["metrics"], metric
    m = r["metrics"]
    assert m["join_roundtrip_us.join"]["value"] > 0
    assert m["fire_wait_ms.join"]["value"] > 0
    assert m["fire_delay_ms.join"]["value"] >= 0
    # the harness arms no program span: a traced run leaves them off
    from repro.obs import span
    assert span("tf.a") is span("tf.b")
