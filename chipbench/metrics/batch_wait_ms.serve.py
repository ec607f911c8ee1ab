"""Serving batcher: mean time from a request's due time to the start of
the ``generate_batch`` that serves it, in ms, over the batches begun in
the window (the benchmark's span around ``generate_batch``)."""


def read(run):
    waits = [w for b in run.get("batches", ()) for w in b["waits"]]
    return sum(waits) / len(waits) * 1e3 if waits else None
