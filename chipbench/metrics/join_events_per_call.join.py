"""Vector join plane: events folded into one join call, over the window
(``tf_join_events_total`` / ``tf_join_calls_total``)."""
from chipbench.harness.readers import counter_delta


def read(run):
    calls = counter_delta(run, "tf_join_calls_total")
    if not calls:
        return None
    return counter_delta(run, "tf_join_events_total") / calls
