"""Worker batch plane: mean host time of one checkpoint and commit, in ms
(``tf_checkpoint_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_checkpoint_seconds", 1e3)
