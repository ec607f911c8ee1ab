"""Model decode: mean host time of one decode step, token reads included
(``tf_serve_decode_step_seconds``), in ms."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_serve_decode_step_seconds", 1e3)
