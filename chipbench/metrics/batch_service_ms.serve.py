"""Model step: mean host time of one ``generate_batch`` (prefill and every
decode step of a batch), in ms, over the batches begun in the window."""


def read(run):
    b = run.get("batches", ())
    return sum(x["end"] - x["start"] for x in b) / len(b) * 1e3 if b else None
