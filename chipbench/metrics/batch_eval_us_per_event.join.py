"""Worker batch plane: host time of the per-trigger evaluation path per
event it took, in us (``tf_batch_eval_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_batch_eval_seconds", 1e6)
