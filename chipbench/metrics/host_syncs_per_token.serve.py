"""Model decode: device-to-host reads of the decode loop per generated
token (``tf_serve_host_syncs_total`` / ``tf_serve_tokens_total``)."""
from chipbench.harness.readers import counter_delta


def read(run):
    tokens = counter_delta(run, "tf_serve_tokens_total")
    if not tokens:
        return None
    return counter_delta(run, "tf_serve_host_syncs_total") / tokens
