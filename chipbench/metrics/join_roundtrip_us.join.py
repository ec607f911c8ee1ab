"""Join kernel dispatch: mean host time of a join call that compiled
nothing, upload + kernel + download, in us (``tf_join_roundtrip_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_join_roundtrip_seconds", 1e6)
