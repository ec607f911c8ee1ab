"""Join kernel: the least time the chip could take for the window's join
calls (each call's operations and bytes from its shapes, at the peaks)
over the kernel's device time in the trace, in %.  The kernel is found by
its jit and kernel names."""
from chipbench.harness import trace, work

NAMES = ("event_join", "_join_kernel")


def read(run):
    rows, calls, peaks = run.get("trace_rows"), run.get("join_calls"), run.get("peaks")
    if not rows or not calls or not peaks:
        return None
    device_ns = trace.kernel_ns(rows, trace.window_of(rows), NAMES)
    if device_ns <= 0:
        return None
    least = sum(work.roofline_s(*work.event_join_work(n, t),
                                peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
                for n, t in calls)
    return 100.0 * least / (device_ns / 1e9)
