"""Model prefill: share of the prefilled token slots that are left padding,
``100 * pad / (pad + prompt)`` over the window's batches
(``tf_serve_pad_tokens_total``, ``tf_serve_prompt_tokens_total``), in %."""
from chipbench.harness.readers import counter_delta


def read(run):
    pad = counter_delta(run, "tf_serve_pad_tokens_total")
    prompt = counter_delta(run, "tf_serve_prompt_tokens_total")
    if not prompt:
        return None
    return 100.0 * pad / (pad + prompt)
