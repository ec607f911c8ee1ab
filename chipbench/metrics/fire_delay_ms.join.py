"""Worker batch plane: mean time from a fired event's batch consume to the
start of its fire run, in ms: the triage and compiles of the batch that
ran first (``tf_fire_delay_seconds``)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_fire_delay_seconds", 1e3)
