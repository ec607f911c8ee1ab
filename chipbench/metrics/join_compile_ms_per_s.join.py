"""Join kernel dispatch: host time of the join calls that compiled a
kernel shape, summed over the shard threads, in ms per second of the
window (``tf_join_compile_seconds``; 0 where nothing compiled there)."""


def read(run):
    if "snap0" not in run or not run.get("window_s"):
        return None
    h1 = run["snap1"]["histograms"].get("tf_join_compile_seconds")
    if h1 is None:
        return None
    h0 = run["snap0"]["histograms"].get("tf_join_compile_seconds",
                                         {"sum": 0.0})
    return (h1["sum"] - h0["sum"]) * 1e3 / run["window_s"]
