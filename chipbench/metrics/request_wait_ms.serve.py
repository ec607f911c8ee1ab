"""Serving batcher: mean time from a request's bus stamp to the start of
the batch that serves it (``tf_serve_request_wait_seconds``), in ms."""
from chipbench.harness.readers import hist_mean


def read(run):
    return hist_mean(run, "tf_serve_request_wait_seconds", 1e3)
