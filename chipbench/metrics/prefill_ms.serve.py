"""Model prefill: mean host time of a batch's prefill until its logits are
ready (``tf_serve_prefill_seconds``), in ms."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_serve_prefill_seconds", 1e3)
