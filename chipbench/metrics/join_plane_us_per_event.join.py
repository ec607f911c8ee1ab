"""Vector join plane: host time of ``VectorJoinPlane.triage`` per event it
claimed, in us: screening, upload, the kernel and download
(``tf_join_kernel_seconds``, which names more than the kernel)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_join_kernel_seconds", 1e6)
