"""Bus: mean publish-to-consume lag of an event, in ms, over the window
(``tf_consume_lag_seconds``; each batch credits its oldest event's lag to
all its events, so this bounds the mean from above)."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_consume_lag_seconds", 1e3)
