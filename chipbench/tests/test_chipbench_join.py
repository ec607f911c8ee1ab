"""The join tally against the program's scalar interpreter and its batch
plane, at a tiny size on the CPU."""
import time

import numpy as np
import pytest

from chipbench.harness import join, traffic
from chipbench.references import join_tally

CFG = {"triggers": 6, "expected": 40, "reset_on_fire": True,
       "deployment": {"num_shards": 2, "num_partitions": 4,
                      "commit_policy": "every_batch", "batch_plane": True,
                      "keep_event_log": False}}


def _drain(batch_plane: bool, seed: int):
    from repro.core import termination_event

    cfg = dict(CFG, deployment=dict(CFG["deployment"], batch_plane=batch_plane))
    t = {"rate_per_s": 1000, "arrivals": {"kind": "poisson"},
         "items": {"subject": {"kind": "uniform", "values": 6}}}
    sched = traffic.schedule(t, seed, 1.0)
    subj = sched.attrs["subject"]
    res = traffic.rng_for(seed, "results").integers(0, 1 << 30, len(subj))
    log = join.FireLog()
    tf = join.build(cfg, log)
    try:
        tf.event_store.publish_batch(join.WORKFLOW, [
            termination_event(f"j{s}", r)
            for s, r in zip(subj.tolist(), res.tolist())])
        tf.start_shards(join.WORKFLOW)
        deadline = time.monotonic() + 60
        while tf.event_store.lag(join.WORKFLOW) > 0:
            assert time.monotonic() < deadline, "join did not drain"
            time.sleep(0.005)
        ctxs = [tf.get_trigger_context(join.WORKFLOW, f"jt{i}")
                for i in range(6)]
    finally:
        tf.shutdown()
    fires = np.bincount([i for i, _ in log.fires], minlength=6)
    return subj, res, ctxs, fires


@pytest.mark.parametrize("seed", [4, 2**31 + 9])
@pytest.mark.parametrize("batch_plane", [False, True])
def test_tally_matches_the_program(seed, batch_plane):
    subj, res, ctxs, fires = _drain(batch_plane, seed)
    want = join_tally.tally(subj, res, 6, 40)
    assert [w["fires"] for w in want] == fires.tolist()
    assert sum(fires) > 0
    for c, w in zip(ctxs, want):
        assert join_tally.same_context(c, w), (c.get("count"), w["count"])
