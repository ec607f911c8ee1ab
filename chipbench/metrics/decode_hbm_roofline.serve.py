"""Model decode: the least time the window's decode steps need at the
chip's HBM bandwidth (every matmul weight and the output head, and each
row's valid cached keys and values: ``harness/serve_work.py``), over the
device time of the decode program's executions in the trace
(``decode_programs``), in %.  The cached positions a step reads are the
mean over the window's batches (``tf_serve_*`` counters)."""
from chipbench.harness.readers import counter_delta
from chipbench.harness.serve_work import decode_step_bytes, mean_kv_tokens_per_step


def read(run):
    programs, peaks = run.get("decode_programs"), run.get("peaks")
    if not programs or not programs["runs"] or not peaks:
        return None
    batches = counter_delta(run, "tf_serve_batches_total")
    requests = counter_delta(run, "tf_serve_requests_total")
    if not batches or not requests:
        return None
    new = counter_delta(run, "tf_serve_tokens_total") / requests
    kv = mean_kv_tokens_per_step(
        counter_delta(run, "tf_serve_prompt_tokens_total"), requests, batches,
        new)
    least_s = (programs["runs"] * decode_step_bytes(run["cfg"], kv)
               / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / programs["device_s"]
