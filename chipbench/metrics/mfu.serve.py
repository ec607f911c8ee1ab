"""Model step: model FLOPs the served requests need (their unpadded prompts,
then each generated token, attention included) over the summed time of
the ``generate_batch`` calls that served them and the chip's bf16 peak,
in %, for the batches begun in the window."""
from chipbench.harness.work import dense_lm_request_flops


def read(run):
    batches, peaks = run.get("batches"), run.get("peaks")
    if not batches or not peaks:
        return None
    flops = sum(dense_lm_request_flops(run["cfg"], n, run["new_tokens"])
                for b in batches for n in b["prompt_lens"])
    busy = sum(b["end"] - b["start"] for b in batches)
    return 100.0 * flops / (busy * peaks["bf16_flops_per_s"])
