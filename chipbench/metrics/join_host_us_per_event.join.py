"""Vector join plane: host time of ``VectorJoinPlane.triage`` outside the
backend call, per event it claimed, in us: screening, result columns, row
expansion, applying the counts (``tf_join_host_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_join_host_seconds", 1e6)
