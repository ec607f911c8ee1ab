"""Bus: mean time a fired event waited between its publish stamp and the
consume of its batch, in ms (``tf_fire_wait_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_fire_wait_seconds", 1e3)
